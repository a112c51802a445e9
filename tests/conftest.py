"""Test harness: force an 8-device virtual CPU mesh.

The reference has no fake backend for distributed tests — its distributed
behavior is only exercised on real per-CI-run GKE clusters (SURVEY.md §4).
This conftest is the fake backend: every test sees 8 XLA host devices, so
dp/fsdp/tp/sp/ep shardings compile and run hermetically.

Must run before jax initializes a backend, hence env mutation at import
time (pytest imports conftest before test modules).
"""

import functools
import os

# Unconditional: tests are hermetic CPU by design, whatever the machine
# holds (the chip is reached through chip_smoke.py, never through pytest).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep XLA/CPU from oversubscribing the test machine.
os.environ.setdefault("JAX_ENABLE_X64", "0")

from kubeflow_tpu.utils import compile_cache  # noqa: E402

# Persistent XLA compilation cache: many tests build Trainers over the
# same tiny models, and each new jit closure recompiles identical HLO.
# The disk cache turns those (and repeat suite runs) into ~ms loads.
# Placed by JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache — the
# same rule as the launcher and the server.
compile_cache.configure()

import pytest  # noqa: E402

# Chaos tier knobs (the TPU_RACE_* convention, threaded here so every
# chaos test agrees on one seed set): TPU_CHAOS_RATE scales the per-call
# fault probability of the chaos-parameterized reruns and the soak;
# TPU_CHAOS_SEED re-bases the seed sweep so CI can explore fresh fault
# schedules without editing tests. Defaults are the committed, verified
# schedule — every seed in CHAOS_SEEDS converges deterministically.
CHAOS_RATE = float(os.environ.get("TPU_CHAOS_RATE") or 0.05)
CHAOS_SEED_BASE = int(os.environ.get("TPU_CHAOS_SEED") or 1)
CHAOS_SEEDS = tuple(CHAOS_SEED_BASE + i for i in range(5))


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process e2e tests (gang worlds, real subprocesses); "
        "run explicitly or via the full suite",
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection tier (tests/test_chaos.py); knobs: "
        "TPU_CHAOS_SEED / TPU_CHAOS_RATE; the full-platform soak is also "
        "marked slow",
    )


def pytest_collection_modifyitems(config, items):
    """Default tier: deselect `slow` tests — but never when the user
    passed an explicit -m expression, or named the test's file directly
    (pytest tests/test_gang_e2e.py must run its tests)."""
    if config.option.markexpr:
        return
    explicit = {
        os.path.abspath(a.split("::")[0])
        for a in config.args
        if a.split("::")[0].endswith(".py")
    }
    deselected = [
        it for it in items
        if "slow" in it.keywords and str(it.fspath) not in explicit
    ]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        dropped = set(deselected)
        items[:] = [it for it in items if it not in dropped]


def make_segments(b, l, n_docs, seed=7):
    """Random monotone sequence-packing ids [b, l] (1-based spans) —
    shared by the flash/ring/ulysses segment-masking tests."""
    import numpy as np

    rng = np.random.RandomState(seed)
    seg = np.zeros((b, l), np.int32)
    for r in range(b):
        cuts = np.sort(rng.choice(np.arange(1, l), n_docs - 1, replace=False))
        seg[r] = np.searchsorted(cuts, np.arange(l), side="right")
    import jax.numpy as jnp

    return jnp.asarray(seg)


# -- a SlotDecoder of each kind of step over each cache ---------------------
#
# The scheduler loop is one (serving/continuous.py), whatever step it
# drives (serving/steps.py): the tests of the loop's contract run over
# these modes, on toy models of one geometry (prompts of 8, pages of 4).

DECODER_MODES = ("token-dense", "token-paged", "block", "spec-dense",
                 "spec-paged")
BLOCK_MODEL = dict(moe_every=1, n_experts=4, expert_top_k=2, moe_d_ff=32,
                   qk_norm=True, gen_block=4, gen_mask_id=63)


@functools.lru_cache(maxsize=None)
def _toy_lm(**more):
    import jax
    import numpy as np

    from kubeflow_tpu.models.registry import get_model

    model = get_model("transformer-test", vocab_size=64, max_seq_len=24,
                      **more)
    return model, model.init(jax.random.PRNGKey(0),
                             np.zeros((1, 1), np.int32), train=False)


def slot_decoder(mode: str, **kw):
    """A SlotDecoder of `mode` (one of DECODER_MODES); `kw` goes to it.
    A speculative one drafts with the target's own weights over a dense
    cache, two tokens a round."""
    from kubeflow_tpu.serving.continuous import SlotDecoder

    kind, _, cache = mode.partition("-")
    more = dict(BLOCK_MODEL) if kind == "block" else {}
    if cache != "dense":
        more.update(kv_pages=33, kv_page_size=4)
    model, variables = _toy_lm(**more)
    if kind == "spec":
        kw = dict(draft_model=_toy_lm()[0], draft_variables=variables,
                  draft_k=2, **kw)
    return SlotDecoder(model, variables, prompt_len=8, **kw)


def answer_tokens(answer) -> list:
    """The tokens of what `submit` returned: a block model's answer is
    ``{"tokens": [...], "fixed_at": [...]}``."""
    return answer["tokens"] if isinstance(answer, dict) else answer


# -- virtual-time determinism guard (ISSUE 16) -------------------------------
#
# The bench-contract tests assert byte-stable decision fingerprints, which
# only holds if the modules under test never read the wall clock during a
# replay. tpulint's DET6xx family proves that statically; this fixture is
# the dynamic twin: it snapshots time.time() call counts per calling
# module across the test and fails on any read attributed to a
# replay-critical module (the docs/scale.md "Determinism contract" list).

REPLAY_CRITICAL_MODULES = (
    "kubeflow_tpu.control.scheduler",
    "kubeflow_tpu.control.cache",
    "kubeflow_tpu.serving.router",
    "kubeflow_tpu.serving.continuous",
    "kubeflow_tpu.obs",
    "kubeflow_tpu.control.jaxservice",
    "kubeflow_tpu.control.jaxjob",
)


@pytest.fixture
def virtual_time_guard(monkeypatch):
    """Fail the test if a replay-critical module reads time.time().

    Yields the live {caller module -> call count} snapshot so a test can
    also assert on reads it *expects* (e.g. from the bench harness
    itself, which owns the virtual clock and may read real time freely).
    """
    import sys
    import time as _time

    real_time = _time.time
    reads: dict = {}

    def guarded_time():
        mod = sys._getframe(1).f_globals.get("__name__", "<unknown>")
        reads[mod] = reads.get(mod, 0) + 1
        return real_time()

    monkeypatch.setattr(_time, "time", guarded_time)
    yield reads
    offenders = {m: n for m, n in sorted(reads.items())
                 if m.startswith(REPLAY_CRITICAL_MODULES)}
    assert not offenders, (
        "wall-clock time.time() read from replay-critical module(s) "
        f"during a bench-contract test: {offenders} — inject a clock "
        "(see docs/scale.md 'Determinism contract')")
