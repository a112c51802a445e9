"""The training driver: `train_fit`. One Trainer (the compiled step) and
one state: set-up drives it through its first steps, which the reference
follows afterwards, and hands the same object to the window, which is one
call of Trainer.fit driven by its own `callback` and `stop`."""

from __future__ import annotations

import statistics
import sys
import time

import numpy as np

from benchmarks.lib import harness
from benchmarks.lib.spec import Cell

CHECK_STEPS = 2     # steps the reference follows (two, not three: its time)
TRACE_STEPS = 3     # steps the profiler sees


def batches(seed: int, batch: int, seq_len: int, vocab: int):
    """Step k's batch from the seed: fresh rows every step, all different."""
    k = 0
    while True:
        rng = np.random.default_rng([int(seed), k])
        tok = rng.integers(0, vocab, (batch, seq_len + 1), dtype=np.int32)
        yield {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
        k += 1


def first_grad_norms(leaf_names, optimizer: str, state) -> dict:
    """Each leaf's gradient norm at step 1, worked out from the optimizer
    state after that step: adafactor's second-moment statistics start
    with decay 0, so they hold mean(g*g) over the factored axis (or g*g
    itself for a small leaf); adamw's first moment holds 0.1 * g.
    `leaf_names` is the architecture's: the program's tree flattened to
    the reference's names."""
    import jax
    import jax.numpy as jnp

    params = leaf_names(state.params)

    def find(node, field):
        if hasattr(node, field):
            return getattr(node, field)
        if isinstance(node, (tuple, list)):
            for sub in node:
                got = find(sub, field)
                if got is not None:
                    return got
        return None

    if optimizer == "adafactor":
        v_row = leaf_names(find(state.opt_state, "v_row"))
        v = leaf_names(find(state.opt_state, "v"))

        def norms(v_row, v):
            return {k: jnp.sqrt(
                jnp.sum(v_row[k]) * (params[k].size / v_row[k].size)
                if v_row[k].size > 1 else jnp.sum(v[k])) for k in v}

        got = jax.jit(norms)(v_row, v)
    elif optimizer == "adamw":
        mu = leaf_names(find(state.opt_state, "mu"))
        got = jax.jit(lambda mu: {
            k: jnp.sqrt(jnp.sum(m.astype(jnp.float32) ** 2)) / 0.1
            for k, m in mu.items()})(mu)
    else:
        raise ValueError(f"no first-gradient rule for {optimizer!r}")
    return {k: float(x) for k, x in got.items()}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    names = [k for k in ref if keep is None or k in keep]
    floor = statistics.median(ref[k] for k in names)
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in names)


def compare(cell: Cell, prog: dict, ref: dict) -> list:
    """The numbers that decide `correct`, each beside its limit."""
    limits = cell.traffic["limits"][cell.config_name]
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["loss"], ref["loss"]))
    # leaves whose gradient is nought to rounding move by round-off alone
    g_med = statistics.median(ref["grad_norm"].values())
    moved = {k for k, g in ref["grad_norm"].items() if g >= g_med / 1000.0}
    return [
        ("loss_gap", loss_gap, limits["loss_gap"]),
        ("grad_norm_gap", worst_leaf_gap(prog["grad_norm"], ref["grad_norm"]),
         limits["grad_norm_gap"]),
        ("change_norm_gap",
         worst_leaf_gap(prog["change_norm"], ref["change_norm"], moved),
         limits["change_norm_gap"]),
    ]


def run_reference(cell: Cell, seed: int, lowp=None, rows=None) -> dict:
    """The reference's readings of the first CHECK_STEPS steps, from the
    follower the cell's architecture builds."""
    tr = cell.config["trainer"]
    ref = cell.arch.train_reference(cell, seed, lowp=lowp, rows=rows)
    feed = batches(seed, tr["global_batch"], tr["seq_len"], cell.dims.vocab)
    out = {"loss": [], "grad_norm": None}
    try:
        for _ in range(CHECK_STEPS):
            b = next(feed)
            got = ref.step(b["tokens"], b["targets"])
            out["loss"].append(got["loss"])
            if out["grad_norm"] is None:
                out["grad_norm"] = got["grad_norm"]
        out["change_norm"] = ref.change_norms(seed)
    finally:
        ref.close()
    return out


def build(cell: Cell, seed: int, devices, overrides: dict):
    """The Trainer and its first state, weights from the benchmark."""
    import jax

    from kubeflow_tpu.parallel.mesh import build_mesh
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer, TrainState

    tr = dict(cell.config["trainer"], **overrides)
    cfg = TrainConfig.from_dict(dict(
        tr, model=cell.config["program"]["model"], task="lm",
        vocab_size=cell.dims.vocab, seed=seed & 0x7FFFFFFF, log_every=10**9,
        model_kwargs=cell.arch.model_kwargs(cell, max_seq_len=tr["seq_len"])))
    trainer = Trainer(cfg, mesh=build_mesh(cfg.mesh, list(devices)))
    sh = trainer.state_shardings
    with trainer.mesh:
        params = cell.arch.make_program_params(cell.dims, seed, sh.params)
        want = jax.tree.map(lambda a: a.shape, trainer.abstract_state.params)
        got = jax.tree.map(lambda a: a.shape, params)
        if want != got:
            raise RuntimeError("the program's parameter tree is not the one "
                               f"{cell.arch.__name__} makes")
        step, opt_state = jax.jit(
            lambda p: (jax.numpy.zeros((), jax.numpy.int32), trainer.tx.init(p)),
            out_shardings=(sh.step, sh.opt_state))(params)
    feed = batches(seed, cfg.global_batch, cfg.seq_len, cell.dims.vocab)
    trainer.data_iter = lambda *a, **kw: feed      # the benchmark's feed
    state = TrainState(step=step, params=params, batch_stats={},
                       opt_state=opt_state, tx=trainer.tx)
    return trainer, state, cfg


def run(cell: Cell, seed: int, seconds: float, trace_on: bool, t_start: float,
        overrides: dict | None = None, require_tpu: bool = True,
        break_trainer=None) -> dict:
    """One run of a training cell. `break_trainer` is for the tests: it is
    given the Trainer before its first step, to plant a fault."""
    import jax

    devices = harness.devices_for(cell.chips, require_tpu)
    harness.configure_cache()
    compiles = harness.CompileCounter()
    trainer, state, cfg = build(cell, seed, devices, overrides or {})
    if break_trainer:
        break_trainer(trainer)
    trace = harness.TraceWindow(trace_on)
    tokens_per_step = cfg.global_batch * cfg.seq_len

    # -- set-up: the first steps, through the window's own call and feed --
    prog = {"loss": []}

    def note_loss(i, m):
        prog["loss"].append(float(m["loss"]))

    for k in range(1, CHECK_STEPS + 1):
        state, _ = trainer.fit(steps=k, state=state, callback=note_loss)
        if k == 1:
            prog["grad_norm"] = first_grad_norms(
                cell.arch.leaf_names, cfg.optimizer, state)
    prog["change_norm"] = cell.arch.change_norms(cell.dims, seed, state.params)

    # -- the window: one fit call; its first step is the lead-in ----------
    marks: list = []
    n0 = [0]

    def callback(i, m):
        float(m["loss"])              # read back before the clock is read
        marks.append(time.monotonic())
        if len(marks) == 1:
            n0[0] = compiles.n
            trace.start()
        if trace.enabled and len(marks) == 1 + TRACE_STEPS:
            trace.stop()

    def stop():
        return bool(marks) and time.monotonic() - marks[0] >= seconds

    state, _ = trainer.fit(steps=10**9, state=state, callback=callback,
                           stop=stop)
    trace.stop()
    n_compiles = compiles.n - n0[0]
    steps = len(marks) - 1
    window_s = marks[-1] - marks[0]
    setup_s = marks[0] - t_start
    mem = harness.memory_peak_bytes(devices)
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        leaf.delete()
    del state
    red = trace.reduce()
    t_ref = time.monotonic()
    ref = run_reference(cell, seed)
    checks = compare(cell, prog, ref)
    print(f"reference: {CHECK_STEPS} steps in "
          f"{time.monotonic() - t_ref:.1f} s", file=sys.stderr)
    metrics = {
        "setup_s": {"value": setup_s, "unit": "s"},
        "train_tok_per_s": {"value": steps * tokens_per_step / window_s,
                            "unit": "tokens/s"},
    }
    ctx = {
        "cell": cell, "window_s": window_s, "trace": red,
        "device_kind": devices[0].device_kind, "chips": len(devices),
        "compiles": n_compiles, "steps": steps,
        "step_gaps_s": [b - a for a, b in zip(marks, marks[1:])],
        "tokens_per_step": tokens_per_step, "batch": cfg.global_batch,
        "seq_len": cfg.seq_len, "trace_steps": TRACE_STEPS,
    }
    return {"correct": harness.judge(checks), "attempted": steps,
            "failed": 0, "metrics": metrics,
            "device": harness.device_line(devices, red),
            "memory_peak_bytes": mem, "ctx": ctx, "checks": checks,
            "trace": red}
