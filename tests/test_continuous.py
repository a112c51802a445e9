"""Continuous batching (serving/continuous.py): slot-based lockstep
decode must produce exactly what generate() produces, while requests
join and leave independently."""

import threading

import numpy as np
import pytest
from conftest import DECODER_MODES, answer_tokens, slot_decoder


@pytest.fixture(scope="module")
def lm():
    import jax

    from kubeflow_tpu.models.registry import get_model

    model = get_model("transformer-test", vocab_size=64, max_seq_len=16)
    tok = np.zeros((1, 1), np.int32)
    variables = model.init(jax.random.PRNGKey(0), tok, train=False)
    return model, variables


def reference_generate(model, variables, tokens, prompt_len=8, max_new=4):
    import jax.numpy as jnp

    from kubeflow_tpu.runtime.generate import generate

    row = [int(t) for t in tokens][-prompt_len:]
    pad = prompt_len - len(row)
    prompt = jnp.asarray([[0] * pad + row], jnp.int32)
    out = generate(model, variables, prompt, max_new_tokens=max_new,
                   pad_len=jnp.asarray([pad], jnp.int32))
    return [int(t) for t in np.asarray(out)[0, prompt_len:]]


class TestSlotDecoder:
    def test_matches_generate_exactly_greedy(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=4, prompt_len=8,
                          max_new_tokens=4)
        try:
            prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9], [10, 11]]
            want = [reference_generate(model, variables, p) for p in prompts]
            got = [dec.submit(p) for p in prompts]  # sequential joins
            assert got == want
        finally:
            dec.close()

    def test_concurrent_staggered_requests_stay_exact(self, lm):
        """Requests arriving WHILE others decode (the continuous-batching
        point) must not perturb each other's tokens."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=3, prompt_len=8,
                          max_new_tokens=6)
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(7)]  # > slots
            want = {tuple(p): reference_generate(
                model, variables, p, max_new=6) for p in prompts}
            results: dict = {}
            errs: list = []

            def go(p):
                try:
                    results[tuple(p)] = dec.submit(p)
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=go, args=(p,))
                       for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errs, errs
            assert results == want  # slot reuse + lockstep never leak
        finally:
            dec.close()

    def test_slot_reuse_after_drain(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=2, prompt_len=8,
                          max_new_tokens=3)
        try:
            for round_ in range(3):  # 3 waves through 2 slots
                p = [round_ + 1, round_ + 2]
                assert dec.submit(p) == reference_generate(
                    model, variables, p, max_new=3)
            assert dec.active_slots == 0
        finally:
            dec.close()

    @pytest.mark.parametrize("mode", DECODER_MODES)
    def test_close_fails_pending_cleanly(self, mode):
        dec = slot_decoder(mode, slots=1, max_new_tokens=2)
        dec.close()
        with pytest.raises(RuntimeError, match="shut down"):
            dec.submit([1, 2, 3])


class TestContinuousServing:
    """The TF-Serving REST contract answered from the slot decoder."""

    def test_http_predict_matches_generate(self, lm):
        import requests

        from kubeflow_tpu.serving.server import (
            ModelServer, serve_lm_generator)

        model, variables = lm
        srv = ModelServer()
        srv.register(serve_lm_generator(
            "cb-lm", "transformer-test", prompt_len=8, max_new_tokens=4,
            vocab_size=64,  # max_seq_len derives from prompt+new
            continuous_batching=True, decode_slots=4))
        svc = srv.serve(host="127.0.0.1", port=0)
        svc.serve_background()
        try:
            base = f"http://127.0.0.1:{svc.port}"
            r = requests.post(
                f"{base}/v1/models/cb-lm:predict",
                json={"instances": [{"tokens": [1, 2, 3]},
                                    {"tokens": [4, 5]}]},
                timeout=300)
            assert r.status_code == 200, r.text
            preds = r.json()["predictions"]
            assert preds[0] == reference_generate(model, variables, [1, 2, 3])
            assert preds[1] == reference_generate(model, variables, [4, 5])
            meta = requests.get(
                f"{base}/v1/models/cb-lm/metadata", timeout=30).json()
            sig = meta["metadata"]["signature_def"]
            assert sig["continuous_batching"] is True
        finally:
            svc.shutdown()
            srv.close()

    def test_mesh_sharded_continuous_batching(self, lm):
        """--mesh and --continuous-batching compose: the slot decoder's
        prefill/step programs run over sharded variables."""
        import requests

        from kubeflow_tpu.serving.server import (
            ModelServer, serve_lm_generator)

        model, variables = lm
        srv = ModelServer()
        srv.register(serve_lm_generator(
            "cb-mesh", "transformer-test", prompt_len=8, max_new_tokens=4,
            vocab_size=64, mesh={"fsdp": 2, "model": 4},
            continuous_batching=True, decode_slots=2))
        svc = srv.serve(host="127.0.0.1", port=0)
        svc.serve_background()
        try:
            r = requests.post(
                f"http://127.0.0.1:{svc.port}/v1/models/cb-mesh:predict",
                json={"instances": [{"tokens": [1, 2, 3]}]}, timeout=300)
            assert r.status_code == 200, r.text
            preds = r.json()["predictions"]
            # sharding is placement, not numerics: unsharded-exact
            assert preds[0] == reference_generate(
                model, variables, [1, 2, 3])
        finally:
            svc.shutdown()
            srv.close()


class TestSchedulingFairness:
    def test_idle_burst_prefills_as_one_batch(self, lm):
        """An IDLE decoder takes the whole waiting burst through one
        batched prefill instead of burst_size serial scans."""
        import time as _time

        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=4, prompt_len=8,
                          max_new_tokens=3)
        try:
            calls: list = []
            real_prefill = dec.step._prefill

            def spy(params, prompts, pads):
                calls.append(int(prompts.shape[0]))
                return real_prefill(params, prompts, pads)

            # hold the loop while the burst queues up: pause via a fake
            # empty free list, then restore
            dec.step._prefill = spy
            held, dec._free = dec._free, []
            prompts = [[i + 1, i + 2] for i in range(4)]
            want = [reference_generate(model, variables, p, max_new=3)
                    for p in prompts]
            results: dict = {}
            threads = [threading.Thread(
                target=lambda i=i: results.__setitem__(
                    i, dec.submit(prompts[i]))) for i in range(4)]
            for t in threads:
                t.start()
            _time.sleep(0.3)  # burst fully queued while no slots "free"
            dec._free = held
            for t in threads:
                t.join(timeout=120)
            assert [results[i] for i in range(4)] == want
            assert calls and calls[0] == 4, calls  # ONE batch-4 prefill
        finally:
            dec.close()


    def test_at_most_one_prefill_between_decode_ticks(self, lm):
        """A burst must not stall generations: once anything is active,
        the loop alternates admit-one / step (never two prefills
        back-to-back)."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=4, prompt_len=8,
                          max_new_tokens=4)
        try:
            trace: list = []
            real_prefill, real_step = dec.step._prefill, dec._step

            def spy_prefill(*a, **k):
                trace.append("P")
                return real_prefill(*a, **k)

            def spy_step(*a, **k):
                trace.append("S")
                return real_step(*a, **k)

            dec.step._prefill, dec._step = spy_prefill, spy_step
            prompts = [[i + 1, i + 2] for i in range(4)]
            want = [reference_generate(model, variables, p) for p in prompts]
            results: dict = {}

            def go(i):
                results[i] = dec.submit(prompts[i])

            # make it deterministic: get one generation ACTIVE first,
            # then burst the rest — those must admit one per tick
            t0 = threading.Thread(target=go, args=(0,))
            t0.start()
            import time as _time

            for _ in range(200):
                if dec.active_slots >= 1:
                    break
                _time.sleep(0.01)
            threads = [threading.Thread(target=go, args=(i,))
                       for i in range(1, 4)]
            for t in threads:
                t.start()
            for t in [t0] + threads:
                t.join(timeout=120)
            assert [results[i] for i in range(4)] == want
            for a, b in zip(trace, trace[1:]):
                assert not (a == "P" and b == "P"), trace
        finally:
            dec.close()


def test_serve_bench_tool_runs_both_modes():
    """tools/serve_bench.py: the serving-side ledger must emit one valid
    JSON line per mode (plumbing check; numbers come from TPU runs)."""
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, jax, importlib.util\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "sys.argv = ['sb', '--model', 'transformer-test', '--vocab-size',"
        " '64', '--prompt-len', '8', '--max-new-tokens', '3',"
        " '--requests', '6', '--concurrency', '2', '--slots', '2',"
        " '--param-dtype', '']\n"
        "spec = importlib.util.spec_from_file_location("
        "'sb', 'tools/serve_bench.py')\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "sys.exit(m.main())\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=here,
                       capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-500:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert {d["mode"] for d in lines} == {"micro", "continuous"}
    for d in lines:
        assert d["tokens_per_sec"] > 0 and d["p50_ms"] > 0


class TestFailureContainment:
    """The high-effort decode review's findings, pinned."""

    @pytest.mark.parametrize("mode", DECODER_MODES)
    def test_malformed_row_in_burst_fails_only_its_caller(self, lm, mode):
        """A wrong-length submit_padded row must fail THAT caller; valid
        co-batched requests get THEIR OWN continuations (row/prefill
        alignment survives the drop)."""
        model, variables = lm
        dec = slot_decoder(mode, slots=4, max_new_tokens=3)
        try:
            held, dec._free = dec._free, []  # queue the burst together
            results: dict = {}

            def good(i):
                results[i] = dec.submit([i + 1, i + 2])

            def bad():
                try:
                    dec.submit_padded([1, 2, 3], 0)  # wrong length
                    results["bad"] = "no error"
                except ValueError:
                    results["bad"] = "valueerror"

            threads = [threading.Thread(target=bad)] + [
                threading.Thread(target=good, args=(i,)) for i in range(3)]
            for t in threads:
                t.start()
            import time as _time

            _time.sleep(0.3)
            dec._free = held
            for t in threads:
                t.join(timeout=120)
            assert results["bad"] == "valueerror"
            for i in range(3):
                # what the request gets alone, and (one token a step,
                # speculative or not) what plain greedy decode gives
                assert results[i] == dec.submit([i + 1, i + 2]), i
                if mode != "block":
                    assert results[i] == reference_generate(
                        model, variables, [i + 1, i + 2], max_new=3), i
        finally:
            dec.close()

    @pytest.mark.parametrize("where", ["round", "prefill"])
    @pytest.mark.parametrize("mode", DECODER_MODES)
    def test_step_failure_recovers_instead_of_zombie(self, mode, where,
                                                     monkeypatch):
        """A runtime failure in a donated program (the round's, or an
        admission's prefill) poisons in-flight requests ONCE and the
        decoder rebuilds its step's state and the allocator: later
        submits succeed (no permanent zombie serving errors forever),
        and no page is left claimed. Its speculative dense-prefill case
        was `test_spec_round_failure_recovers_instead_of_zombie`."""
        import jax

        from kubeflow_tpu.serving import steps

        dec = slot_decoder(mode, slots=2, max_new_tokens=3)
        try:
            want = dec.submit([1, 2, 3])         # while it is healthy
            blew = []

            def exploding(real):
                def call(*args, **kw):
                    if blew:
                        return real(*args, **kw)
                    blew.append(1)
                    # simulate the donation: the failed call consumed
                    # the input buffers before dying
                    jax.tree.map(lambda a: a.delete(), dec.state)
                    raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")
                return call

            if where == "prefill" and dec.paged:
                dec._prefill_at = {n: exploding(program) for n, program
                                   in dec._prefill_at.items()}
            elif where == "prefill":
                name = ("_spec_admit_dense" if mode == "spec-dense"
                        else "_prefill")
                setattr(dec.step, name, exploding(getattr(dec.step, name)))
            elif mode.startswith("spec"):
                monkeypatch.setattr(steps, "lockstep_verify",
                                    exploding(steps.lockstep_verify))
            else:
                dec._step = exploding(dec._step)
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                dec.submit([1, 2, 3])
            assert blew
            if dec.paged:
                dec.alloc.check()
                st = dec.stats()
                # (the healthy request's prompt page may sit in the prefix
                # index: a reset forgot that too)
                assert st["kv_pages_free"] == st["kv_pages_total"]
            # rebuilt: the very next request decodes correctly
            assert dec.submit([1, 2, 3]) == want
        finally:
            dec.close()

    def test_geometry_past_max_seq_len_is_refused(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm  # max_seq_len = 16
        with pytest.raises(ValueError, match="max_seq_len"):
            SlotDecoder(model, variables, slots=2, prompt_len=12,
                        max_new_tokens=8)
        import jax.numpy as jnp

        from kubeflow_tpu.runtime.generate import generate

        with pytest.raises(ValueError, match="max_seq_len"):
            generate(model, variables, jnp.ones((1, 12), jnp.int32),
                     max_new_tokens=8)


# What stats() returned in each mode at commit aa28e3e, before the step
# kinds were classes: the benchmark's sampler and its metrics read these
# keys, and a key that comes or goes is a metric that turns to null.
STATS_KEYS = {
    "admitted", "cache_bytes", "completed", "deadline_canceled",
    "first_token_s_sum", "first_tokens", "mode", "peak_active",
    "phase_s.admit", "phase_s.complete", "phase_s.idle", "phase_s.pages",
    "phase_s.prefill", "phase_s.readback", "phase_s.tick",
    # (the two counters of the flash prefill came with it, and the
    # block pairs of its rungs' kernels with PR 38)
    "prefill_behind_hit", "prefill_flash",
    "prefill_flash_pairs_run", "prefill_flash_pairs_reachable",
    "prefill_tokens_computed", "prompt_tokens_real",
    "prompt_tokens_submitted", "queue_wait_s_sum", "rounds", "spec_drafted",
    "spec_rounds", "spec_tokens_accepted", "spec_tokens_emitted",
    "speculative",
    # (what the loop counts of every pass came with PR 37)
    "ticks", "tokens_decoded", "rounds.plain", "round_s.plain",
    "rounds.fused", "round_s.fused", "rounds.other", "round_s.other",
    # (the rounds dispatched while the one before was unread)
    "rounds_ahead"}
STATS_KEYS_PAGED = {
    "cow_clones", "kv_page_size", "kv_pages_free", "kv_pages_tabled",
    "kv_pages_total", "kv_pages_used", "kv_pages_walked", "prefill_shapes",
    "prefix_hit_pages", "prefix_hit_tokens"}
STATS_KEYS_BLOCK = {"block_passes", "blocks_committed", "moe_expert_visits",
                    "moe_kernel_pairs", "moe_load_max", "moe_pairs"}
# a paged decoder's rounds behind one admission, by the ladder's rung
# (conftest's prompt_len 8 over pages of 4)
STATS_KEYS_RUNGS = {f"{kind}.rung{n}" for kind in ("rounds", "round_s")
                    for n in (4, 8)}


@pytest.mark.parametrize("mode", DECODER_MODES)
def test_stats_keys_and_the_one_decoder_the_harness_finds(mode):
    """stats() has the keys it had in every mode, before and after a
    request; and the benchmark's harness, which looks for the one new
    live object with `stats()` and `active_slots`, finds the decoder and
    not its step."""
    from benchmarks.lib.serve import _decoders

    earlier = {id(o) for o in _decoders()}
    dec = slot_decoder(mode, slots=2, max_new_tokens=4)
    try:
        want = set(STATS_KEYS)
        if dec.paged:
            want |= STATS_KEYS_PAGED | STATS_KEYS_RUNGS
        if mode == "block":
            want |= STATS_KEYS_BLOCK
        first = dec.stats()
        assert set(first) == want
        assert len(answer_tokens(dec.submit([1, 2, 3]))) == 4
        st = dec.stats()
        assert set(st) == want
        assert st["mode"] == ("paged" if dec.paged else "dense")
        assert st["speculative"] == mode.startswith("spec")
        assert (st["spec_rounds"] > 0) == mode.startswith("spec")
        assert [o for o in _decoders() if id(o) not in earlier] == [dec]
        # the harness blocks on `state` and frees its leaves
        import jax

        assert jax.tree.leaves(dec.state) and dec.state is dec.step.state
    finally:
        dec.close()


BUSY_PHASES = ("admit", "prefill", "pages", "tick", "readback", "complete")


def round_classes(st: dict) -> dict:
    """class -> (its rounds, their seconds), from a `stats()`."""
    return {k[len("rounds."):]: (n, st["round_s." + k[len("rounds."):]])
            for k, n in st.items() if k.startswith("rounds.")}


@pytest.mark.parametrize("mode", DECODER_MODES)
def test_the_loop_counts_ticks_tokens_and_rounds_by_class(mode):
    """For every kind of step: `ticks` is the `ticks` of each
    `step.dispatch` summed, `tokens_decoded` the tokens of the answers
    once the decoder is empty, and a pass that dispatched is of exactly
    one class: the classes' rounds add up to `rounds`, their seconds to
    the six busy phases'."""
    dec = slot_decoder(mode, slots=2, max_new_tokens=12)
    dispatched, real = [], dec.step.dispatch
    dec.step.dispatch = lambda owners, ticks, table: (
        dispatched.append(ticks), real(owners, ticks, table))[1]
    budgets = [([1, 2, 3], 12), ([4, 5], 3), ([7, 8, 9, 1], 1), ([3, 3], 5),
               ([6], 12)]
    answers = []
    try:
        first = dec.stats()
        assert first["ticks"] == first["tokens_decoded"] == 0
        threads = [threading.Thread(
            target=lambda p=p, n=n: answers.append(
                answer_tokens(dec.submit(p, max_new=n))))
            for p, n in budgets]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        st = dec.stats()
    finally:
        dec.close()
    assert sorted(map(len, answers)) == sorted(n for _, n in budgets)
    assert st["tokens_decoded"] == sum(map(len, answers))
    assert st["ticks"] == sum(dispatched) >= st["rounds"] == len(dispatched)
    classes = round_classes(st)
    assert set(classes) == {"plain", "fused", "other"} | (
        {"rung4", "rung8"} if dec.paged else set())
    assert sum(n for n, _ in classes.values()) == st["rounds"]
    assert all((s > 0) == (n > 0) for n, s in classes.values())
    # a fused dispatch is `fused`, or `other` where the pass admitted too
    assert classes["fused"][0] <= sum(t > 1 for t in dispatched) \
        <= classes["fused"][0] + classes["other"][0]
    busy = sum(st[f"phase_s.{p}"] for p in BUSY_PHASES)
    seconds = sum(s for _, s in classes.values())
    # (apart: the stamps between two phases, and the idle passes' looks
    # at the queue, microseconds each)
    assert seconds == pytest.approx(busy, rel=0.02, abs=2e-3)


def test_a_round_behind_one_admission_is_of_its_rungs_class():
    """One request at a time, each with fewer tokens to go than a fused
    round: its first pass holds one admission at its prompt's rung and
    one tick, every other pass a single tick."""
    dec = slot_decoder("token-paged", slots=2, max_new_tokens=6,
                       prefix_cache=False)
    try:
        assert dec._ladder == (4, 8)
        dec.submit([1, 2, 3])                 # three real tokens: rung 4
        dec.submit([1, 2, 3, 4, 5, 6])        # six: rung 8
        dec.submit([9, 8, 7, 6, 5])
        st = dec.stats()
    finally:
        dec.close()
    classes = {k: n for k, (n, _) in round_classes(st).items()}
    assert classes == {"rung4": 1, "rung8": 2, "plain": st["rounds"] - 3,
                       "fused": 0, "other": 0}
    assert st["ticks"] == st["rounds"] and st["tokens_decoded"] == 18


def test_an_admission_before_a_fused_dispatch_is_of_no_rungs_class():
    """Nothing waits and the request has a fused round's tokens to go:
    the pass that admits it dispatches FUSE ticks, and is `other`."""
    from kubeflow_tpu.serving.steps import TokenStep

    dec = slot_decoder("token-paged", slots=2, max_new_tokens=12)
    try:
        dec.submit([1, 2, 3])
        st = dec.stats()
    finally:
        dec.close()
    classes = {k: n for k, (n, _) in round_classes(st).items()}
    assert classes["other"] == 1 and classes["rung4"] == 0
    assert st["ticks"] == st["rounds"] + (TokenStep.FUSE - 1) * (
        classes["other"] + classes["fused"])
    assert st["tokens_decoded"] == 12


def test_a_canceled_requests_tokens_stay_counted():
    """`tokens_decoded` is what the device decoded: the tokens of a
    request canceled in its slot were, and stay."""
    from kubeflow_tpu.serving.router import DeadlineExceeded

    now = [0.0]
    dec = slot_decoder("token-paged", slots=2, max_new_tokens=6,
                       clock=lambda: now[0])
    real = dec.step.readback

    def readback(owners):
        now[0] += 1.0           # the deadline passes after three rounds
        return real(owners)

    dec.step.readback = readback
    try:
        with pytest.raises(DeadlineExceeded):
            dec.submit([1, 2, 3], deadline=2.5)
        st = dec.stats()
    finally:
        dec.close()
    assert st["deadline_canceled"] == 1 and st["completed"] == 0
    assert st["tokens_decoded"] == st["ticks"] == 3


# -- a round in flight: the loop dispatches before it reads back ------------

# (prompt, tokens asked for): ten requests on three slots, budgets from
# one token to a fused round and more, prompts that share their first
# page, so that slots finish inside the rounds in flight, are admitted
# again while one is in flight, and hit each other's prompt pages
AHEAD_REQUESTS = [([1, 2, 3, 4, 5], 12), ([1, 2, 3, 4, 6, 7], 3),
                  ([1, 2, 3, 4], 1), ([9, 8, 7], 9), ([1, 2, 3, 4, 5, 6], 5),
                  ([2], 12), ([1, 2, 3, 4, 9], 2), ([5, 5, 5, 5, 5], 10),
                  ([7, 1], 4), ([1, 2, 3, 4, 5, 7, 7], 8)]
# a decoder that runs ahead, by what it keeps: the one-token step with
# the prefix cache on, the one-token step with the window kind's pages,
# the block step
AHEAD_MODES = ("token-prefix", "token-window", "block")


def ahead_decoder(mode: str, **kw):
    import dataclasses

    from conftest import _toy_lm

    from kubeflow_tpu.serving.continuous import SlotDecoder, window_pages_for

    if mode != "token-window":
        return slot_decoder("token-paged" if mode == "token-prefix" else mode,
                            slots=3, max_new_tokens=12, **kw)
    model, variables = _toy_lm(kv_pages=41, kv_page_size=4,
                               attention_window=6)
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, kv_window_pages=window_pages_for(
            model.cfg, 3, 8, 12, prefix_cache=False)))
    dec = SlotDecoder(model, variables, slots=3, prompt_len=8,
                      max_new_tokens=12, prefix_cache=False, **kw)
    assert dec.alloc.window == 6
    return dec


def wait_until(what, timeout=30.0) -> bool:
    import time

    t_end = time.monotonic() + timeout
    while not what() and time.monotonic() < t_end:
        time.sleep(0.005)
    return what()


def serve_at_once(dec, requests=AHEAD_REQUESTS) -> list:
    """Every request submitted at once, each from its own thread: the
    answers in the requests' order."""
    got = [None] * len(requests)

    def go(i, prompt, n):
        try:
            got[i] = dec.submit(prompt, max_new=n)
        except Exception as e:  # pragma: no cover
            got[i] = e

    threads = [threading.Thread(target=go, args=(i, p, n))
               for i, (p, n) in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return got


@pytest.mark.parametrize("mode", AHEAD_MODES)
def test_running_ahead_answers_as_the_serial_loop(mode):
    """A full decoder of a kind that runs ahead dispatches round n+1
    before it reads round n back. Its answers (a block model's
    `fixed_at` among them) are the serial loop's token for token, with
    slots that finished inside a round in flight; the pages all come
    back; the classes' rounds add up to `rounds` and their seconds to
    the busy phases'."""
    got, stats = {}, {}
    for ahead in (True, False):
        dec = ahead_decoder(mode)
        if not ahead:        # the loop as it was: every round read back
            dec.step.runs_ahead = False
        finished_in_flight, real = [], dec.step.readback

        def readback(owners, real=real, dec=dec, seen=finished_in_flight):
            done, counts = real(owners)
            seen.append(bool(done) and bool(dec.step._flight))
            return done, counts

        dec.step.readback = readback
        try:
            got[ahead] = serve_at_once(dec)
            stats[ahead] = st = dec.stats()
            dec.alloc.check()
        finally:
            dec.close()
        assert (st["rounds_ahead"] > 0) == ahead
        assert any(finished_in_flight) == ahead
        assert st["completed"] == len(AHEAD_REQUESTS)
        assert st["tokens_decoded"] == sum(n for _, n in AHEAD_REQUESTS)
        classes = round_classes(st)
        assert sum(n for n, _ in classes.values()) == st["rounds"]
        busy = sum(st[f"phase_s.{p}"] for p in BUSY_PHASES)
        assert sum(s for _, s in classes.values()) == pytest.approx(
            busy, rel=0.02, abs=2e-3)
    assert got[True] == got[False]
    assert [len(answer_tokens(a)) for a in got[True]] == [
        n for _, n in AHEAD_REQUESTS]
    if mode == "token-prefix":
        assert stats[True]["prefix_hit_pages"] > 0
    if mode == "token-window":
        assert stats[True]["kv_window_pages_released"] > 0


@pytest.mark.parametrize("mode", ["spec-dense", "spec-paged"])
def test_a_speculative_decoder_never_runs_ahead(mode):
    """Its verify chunk is built from the round before's read-back."""
    dec = slot_decoder(mode, slots=2, max_new_tokens=6)
    try:
        got = serve_at_once(dec, [(p, min(n, 6)) for p, n in AHEAD_REQUESTS])
        st = dec.stats()
    finally:
        dec.close()
    assert all(isinstance(a, list) for a in got)
    assert st["rounds"] > 0 and st["rounds_ahead"] == 0


@pytest.mark.parametrize("mode", ["token-paged", "block"])
def test_a_decoder_with_a_free_slot_never_runs_ahead(mode):
    """Two requests at a time on four slots: a waiter could enter sooner
    than a round in flight would let it, so every round is read back
    before the next is dispatched."""
    dec = slot_decoder(mode, slots=4, max_new_tokens=12)
    try:
        for pair in zip(AHEAD_REQUESTS[::2], AHEAD_REQUESTS[1::2]):
            serve_at_once(dec, list(pair))
        st = dec.stats()
    finally:
        dec.close()
    assert st["completed"] == len(AHEAD_REQUESTS)
    assert st["rounds"] > 0 and st["rounds_ahead"] == 0


def test_a_deadline_that_passes_with_a_round_in_flight_cancels_cleanly():
    """The slot is cancelled at the top of a pass while the round before
    is still unread: its waiter gets DeadlineExceeded, the others their
    answers, and every page comes back."""
    from kubeflow_tpu.serving.router import DeadlineExceeded

    now = [0.0]
    dec = slot_decoder("token-paged", slots=2, max_new_tokens=12,
                       clock=lambda: now[0])
    real_readback, real_cancel = dec.step.readback, dec.step.cancel
    in_flight = []

    def readback(owners):
        now[0] += 1.0
        return real_readback(owners)

    def cancel(slots):
        in_flight.append(bool(dec.step._flight))
        real_cancel(slots)

    dec.step.readback, dec.step.cancel = readback, cancel
    want = {}
    try:
        ref = slot_decoder("token-paged", slots=2, max_new_tokens=12)
        try:
            for p, n in AHEAD_REQUESTS[3:6]:
                want[tuple(p)] = ref.submit(p, max_new=n)
        finally:
            ref.close()
        caught, got = [], {}

        def go(p, n, deadline=None):
            try:
                got[tuple(p)] = dec.submit(p, max_new=n, deadline=deadline)
            except DeadlineExceeded as e:
                caught.append(e)

        # admitted first, it would still be decoding when the clock
        # passes its deadline
        threads = [threading.Thread(target=go, args=([3, 1, 4], 12, 3.5))]
        threads[0].start()
        assert wait_until(lambda: dec.stats()["admitted"] == 1)
        threads += [threading.Thread(target=go, args=(p, n))
                    for p, n in AHEAD_REQUESTS[3:6]]
        for t in threads[1:]:
            t.start()
        for t in threads:
            t.join(timeout=120)
        st = dec.stats()
        dec.alloc.check()
    finally:
        dec.close()
    assert len(caught) == 1 and got == want
    assert st["deadline_canceled"] == 1 and st["rounds_ahead"] > 0
    assert True in in_flight
    assert st["kv_pages_free"] + st["kv_pages_used"] == st["kv_pages_total"]


def test_a_round_in_flight_writes_no_page_its_predecessors_read_back_gives():
    """Where a read-back completes a slot while the next round is in
    flight, the pages it gives away (back to the pool, or kept as the
    prefix cache's) are written by that round only where the completed
    slot idles: its frozen position, past its answer's end. No prompt
    page the prefix cache keeps is written by a round in flight."""
    dec = slot_decoder("token-paged", slots=3, max_new_tokens=12)
    ps, writes, rounds = dec.page_size, {}, {}
    # the last page of a row holds an index past it (the device clamps)
    last, prompt_pages = dec.alloc.table.shape[1] - 1, dec.P // ps
    real_writes, real_dispatch = dec.step.writes, dec.step.dispatch
    real_complete = dec._complete
    checked = []

    def spy_writes(slot, r, ticks):
        start, end = real_writes(slot, r, ticks)
        # an idle slot's ticks write its frozen position
        writes[slot] = (range(start, end) if end > start else [start],
                        end == start, r)
        return start, end

    def spy_dispatch(owners, ticks, table):
        real_dispatch(owners, ticks, table)
        table = np.asarray(table)
        rounds[id(dec.step._flight[-1])] = {
            (int(table[s_, min(p // ps, last)]), s_, p, idle, r)
            for s_, (at, idle, r) in writes.items() for p in at}
        writes.clear()

    def spy_complete(owners, slot):
        r, a = owners[slot], dec.alloc
        free_before = set(a._free)
        real_complete(owners, slot)
        given = (set(a._free) - free_before) | set(a._page_key)
        for rnd in dec.step._flight:        # the rounds still in flight
            for page, s_, p, idle, r_ in rounds[id(rnd)]:
                assert page not in a._page_key, (page, s_, p)
                if page in given:
                    # ... and in a page of the completed slot's answer
                    assert s_ == slot and r_ is r and idle \
                        and p >= dec.step.end(r) \
                        and min(p // ps, last) >= prompt_pages, (page, s_, p)
                    checked.append(page)

    dec.step.writes, dec.step.dispatch = spy_writes, spy_dispatch
    dec._complete = spy_complete
    try:
        got = serve_at_once(dec)
        st = dec.stats()
        dec.alloc.check()
    finally:
        dec.close()
    assert [len(a) for a in got] == [n for _, n in AHEAD_REQUESTS]
    assert st["rounds_ahead"] > 0 and st["prefix_hit_pages"] > 0
    # the idle writes did land in given pages: the check had work
    assert checked


class TestPerRequestBudgets:
    """Per-instance max_new_tokens caps (ISSUE 9): honored on EVERY
    decode path, not just the slot decoder, and validated hard."""

    def test_continuous_budget_is_ragged_and_exact(self, lm):
        from kubeflow_tpu.serving.server import serve_lm_generator

        model, variables = lm
        served = serve_lm_generator(
            "cb-budget", "transformer-test", prompt_len=8,
            max_new_tokens=4, vocab_size=64,
            continuous_batching=True, decode_slots=2)
        try:
            full = reference_generate(model, variables, [1, 2, 3])
            out = served.predict([
                {"tokens": [1, 2, 3], "max_new_tokens": 2},
                {"tokens": [1, 2, 3], "max_new_tokens": 4}])
            assert out[0] == full[:2] and out[1] == full
        finally:
            served.close()

    def test_plain_generate_budget_applies_too(self):
        from kubeflow_tpu.serving.server import serve_lm_generator

        served = serve_lm_generator(
            "plain-budget", "transformer-test", prompt_len=8,
            max_new_tokens=4, vocab_size=64)
        try:
            full = served.predict([{"tokens": [1, 2, 3]}])[0]
            capped = served.predict(
                [{"tokens": [1, 2, 3], "max_new_tokens": 2}])[0]
            assert capped == full[:2]
        finally:
            served.close()

    def test_out_of_range_budget_is_400(self):
        from kubeflow_tpu.serving.server import serve_lm_generator
        from kubeflow_tpu.utils.httpd import ApiHttpError

        served = serve_lm_generator(
            "bad-budget", "transformer-test", prompt_len=8,
            max_new_tokens=4, vocab_size=64)
        try:
            with pytest.raises(ApiHttpError):
                served.predict(
                    [{"tokens": [1, 2, 3], "max_new_tokens": 9}])
        finally:
            served.close()


# -- the paged prefill computes the prompt and not its padding -------------
#
# Prompts of 32 in pages of 4: the ladder is 8, 16, 24, 32. Each model
# is served twice, by the decoder as it is and by one whose ladder has the
# one rung 32 (the whole padded row, every request: what the decoder did
# before it had a ladder), and a one-token model by generate() besides.

LADDER_P, LADDER_PAGE, LADDER_NEW = 32, 4, 6
LADDER_KINDS = {
    "dense": {},
    "block": dict(moe_every=1, n_experts=4, expert_top_k=2, moe_d_ff=32,
                  qk_norm=True, gen_block=4, gen_mask_id=63),
}
# a prompt at each rung, one under and one over
LADDER_LENGTHS = sorted({n for r in (8, 16, 24, 32) for n in
                         (r - 1, r, r + 1) if 1 <= n <= LADDER_P} | {1})


def ladder_prompt(real):
    return [(7 * real + 3 * i) % 61 + 1 for i in range(real)]


@pytest.fixture(scope="module")
def compile_events():
    """XLA compilations, cache hits among them, as the benchmark counts
    them for `server.compiles.*`."""
    from benchmarks.lib.harness import CompileCounter

    return CompileCounter()


@pytest.fixture(scope="module", params=sorted(LADDER_KINDS))
def laddered(request):
    """(kind, model, variables, the decoder, answers of the decoder whose
    only rung is the whole row)."""
    import jax

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.runtime import kvcache
    from kubeflow_tpu.serving.continuous import SlotDecoder

    kind = request.param
    model = get_model("transformer-test", vocab_size=64, max_seq_len=48,
                      kv_pages=65, kv_page_size=LADDER_PAGE,
                      **LADDER_KINDS[kind])
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    kw = dict(slots=3, prompt_len=LADDER_P, max_new_tokens=LADDER_NEW,
              prefix_cache=False)
    ladder = kvcache.prefill_ladder
    kvcache.prefill_ladder = lambda prompt_len, page_size: (prompt_len,)
    try:
        whole = SlotDecoder(model, variables, **kw)
        try:
            assert whole._ladder == (LADDER_P,)
            want = {n: whole.submit(ladder_prompt(n))
                    for n in LADDER_LENGTHS}
            assert whole.stats()["prefill_tokens_computed"] == (
                LADDER_P * len(LADDER_LENGTHS))
        finally:
            whole.close()
    finally:
        kvcache.prefill_ladder = ladder
    dec = SlotDecoder(model, variables, **kw)
    yield kind, model, variables, dec, want
    dec.close()


class TestPrefillLadder:
    def test_every_rung_is_compiled_at_the_build(self, laddered):
        _, _, _, dec, _ = laddered
        assert dec._ladder == (8, 16, 24, 32)
        assert sorted(dec._prefill_at) == list(dec._ladder)
        assert dec.stats()["prefill_shapes"] == 0

    @pytest.mark.parametrize("real", LADDER_LENGTHS)
    def test_tokens_equal_the_whole_row_prefill(self, laddered, real):
        kind, model, variables, dec, want = laddered
        before = dec.stats()
        got = dec.submit(ladder_prompt(real))
        assert got == want[real]
        if kind == "dense":
            assert got == reference_generate(
                model, variables, ladder_prompt(real),
                prompt_len=LADDER_P, max_new=LADDER_NEW)
        after = dec.stats()
        computed = (after["prefill_tokens_computed"]
                    - before["prefill_tokens_computed"])
        assert computed == next(n for n in dec._ladder if n >= real)
        assert (after["prompt_tokens_real"]
                - before["prompt_tokens_real"]) == real
        assert (after["prompt_tokens_submitted"]
                - before["prompt_tokens_submitted"]) == LADDER_P
        # the pages of the padding were never drawn
        assert after["kv_pages_used"] == 0
        dec.alloc.check()

    def test_no_length_compiles_after_the_build(self, laddered,
                                                compile_events):
        """Once the single and the fused step have run, prompts of every
        length, one at a time and in a burst, compile nothing: every
        suffix is a rung, and every rung was compiled at the build."""
        _, _, _, dec, _ = laddered
        dec.submit(ladder_prompt(5))
        dec.submit(ladder_prompt(5), max_new=2)
        n0 = compile_events.n
        for real in range(0, LADDER_P + 1):
            assert dec.submit(ladder_prompt(real), max_new=2)
        threads = [threading.Thread(
            target=dec.submit, args=(ladder_prompt(real),))
            for real in (2, 9, 17, 30)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert compile_events.n == n0
        st = dec.stats()
        assert st["prefill_shapes"] == len(dec._ladder)
        assert st["completed"] == st["admitted"]

    def test_what_the_trash_page_holds_changes_no_token(self, laddered):
        """Pad pages are the trash page, which idle slots and the
        padding's own positions write: large finite values there, keys
        and values, leave every answer as it was."""
        import jax

        _, _, _, dec, want = laddered
        assert dec.active_slots == 0
        dec.state = (jax.tree.map(lambda pool: pool.at[0].set(1e4),
                                  dec.state[0]),) + tuple(dec.state[1:])
        for real in (1, 7, 16, 23):
            assert dec.submit(ladder_prompt(real)) == want[real]


def test_a_prefix_hit_is_cut_back_to_a_rung(lm):
    """With the prefix cache on, a second prompt that shares 20 of its 29
    tokens with the first computes 16 positions, a rung, where 9 are new,
    and serves the tokens it would alone."""
    import jax

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import SlotDecoder

    model = get_model("transformer-test", vocab_size=64, max_seq_len=48,
                      kv_pages=65, kv_page_size=LADDER_PAGE)
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    first = ladder_prompt(29)
    second = first[:20] + [(t + 7) % 61 + 1 for t in first[20:]]
    want = reference_generate(model, variables, second,
                              prompt_len=LADDER_P, max_new=LADDER_NEW)
    dec = SlotDecoder(model, variables, slots=2, prompt_len=LADDER_P,
                      max_new_tokens=LADDER_NEW)
    try:
        dec.submit(first)
        assert dec.submit(second) == want
        st = dec.stats()
        # pad 3: pages 0-4 hit (positions 0-19 hold 17 shared tokens), and
        # the rung of 16 starts at 16: four of them are claimed
        assert st["prefix_hit_pages"] == 4 and st["cow_clones"] == 0
        assert st["prefill_tokens_computed"] == 32 + 16
        assert st["prefill_shapes"] == 2
        dec.alloc.check()
    finally:
        dec.close()
