"""The bridge from obs/trace.py to the profiler's trace, and what rides
on it: the decoder loop's host phases and per-request stamps, the
trainer's host split of a step, and one request's tree from the router
to its slot. CPU, toy model: counts and structure, never a speed."""

import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from kubeflow_tpu.obs import trace as tr

REQUEST_ATTRS = {"queue_wait_s", "first_token_s", "prompt_tokens",
                 "prefill_tokens_computed", "new_tokens", "slot", "outcome"}


class Recorder:
    """Stands in for jax.profiler.TraceAnnotation; `enabled` for
    whether a profiler session is open."""

    log: list = []
    enabled = False

    @staticmethod
    def is_enabled() -> bool:
        return Recorder.enabled

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        Recorder.log.append(("enter", self.name, self.attrs))
        return self

    def __exit__(self, *exc):
        Recorder.log.append(("exit", self.name, self.attrs))
        return False


@pytest.fixture
def recorder(monkeypatch):
    import jax

    Recorder.log, Recorder.enabled = [], False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return Recorder


class TestBridge:
    def test_span_enters_and_leaves_one_annotation_with_its_name(
            self, recorder):
        tracer = tr.Tracer()
        with tracer.span("serve.predict", model="m") as sp:
            assert recorder.log == [("enter", "serve.predict", {"model": "m"})]
        assert recorder.log == [("enter", "serve.predict", {"model": "m"}),
                                ("exit", "serve.predict", {"model": "m"})]
        assert sp.end is not None and tracer.collector.spans() == [sp]

    def test_span_still_records_the_error_and_leaves_the_annotation(
            self, recorder):
        tracer = tr.Tracer()
        with pytest.raises(KeyError):
            with tracer.span("boom"):
                raise KeyError("x")
        assert [e[0] for e in recorder.log] == ["enter", "exit"]
        assert tracer.collector.spans()[0].status == "ERROR"

    def test_detached_begin_and_record_enter_none(self, recorder):
        tracer = tr.Tracer()
        sp = tracer.begin("jaxjob", detached=True)
        tracer.finish(sp)
        tracer.finish(tracer.begin("held"))
        tracer.record("serve.request", 1.0, 2.5)
        assert recorder.log == []

    def test_record_makes_a_finished_child_of_the_ambient_span(self):
        tracer = tr.Tracer()
        with tracer.span("serve.predict") as up:
            sp = tracer.record("serve.request", 10.0, 12.5, outcome="ok")
            assert tracer.current() == up.context()   # never ambient itself
        assert (sp.trace_id, sp.parent_id) == (up.trace_id, up.span_id)
        assert sp.duration == pytest.approx(2.5)
        assert sp.attrs == {"outcome": "ok"}
        assert sp in tracer.collector.spans()

    def test_importing_the_module_leaves_jax_out(self):
        code = ("import sys; import kubeflow_tpu.obs.trace as t; "
                "c = t.PhaseClock('sched', ('tick',), {}); "
                "\nwith c('tick'): pass\n"
                "\nwith t.TRACER.span('x'): pass\n"
                "print('jax' in sys.modules, t.profiling())")
        out = subprocess.run([sys.executable, "-c", code], text=True,
                             capture_output=True, check=True)
        assert out.stdout.strip() == "False False"

    def test_profiling_is_the_sessions_own_flag(self, recorder, tmp_path):
        assert tr.profiling() is False
        recorder.enabled = True
        assert tr.profiling() is True

    def test_profiling_follows_a_real_session(self, tmp_path):
        import jax

        assert tr.profiling() is False
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert tr.profiling() is True
        finally:
            jax.profiler.stop_trace()
        assert tr.profiling() is False


class TestPhaseClock:
    def test_keys_exist_from_construction_and_are_flat_numbers(self):
        from kubeflow_tpu.serving.continuous import SCHED_PHASES

        table = {"admitted": 0}
        tr.PhaseClock("sched", SCHED_PHASES, table)
        for p in SCHED_PHASES:
            assert table[f"phase_s.{p}"] == 0.0
        assert len(table) == 1 + len(SCHED_PHASES)

    def test_a_phase_adds_its_seconds_and_annotates(self, recorder):
        table = {}
        clock = tr.PhaseClock("sched", ("tick", "idle"), table)
        before = dict(table)
        with clock("tick", fused=1):
            pass
        once = table["phase_s.tick"]
        with clock("tick", fused=0):
            pass
        assert table["phase_s.tick"] > once > 0.0
        assert table["phase_s.idle"] == 0.0
        assert before["phase_s.tick"] == 0.0    # the copy did not move
        assert [e[:2] for e in recorder.log] == [
            ("enter", "kftpu.sched.tick"), ("exit", "kftpu.sched.tick")] * 2
        assert recorder.log[0][2] == {"fused": 1}
        assert recorder.log[2][2] == {"fused": 0}

    def test_unknown_phase_is_an_error_not_a_new_key(self):
        table = {}
        clock = tr.PhaseClock("sched", ("tick",), table)
        with pytest.raises(KeyError):
            clock("tock")
        assert set(table) == {"phase_s.tick"}

    def test_names_carry_the_one_prefix(self):
        from kubeflow_tpu.serving.continuous import SCHED_PHASES

        assert tr.ANNOTATION_PREFIX == "kftpu."
        assert tr.PhaseClock("train", ("wait",), {})("wait").name \
            == "kftpu.train.wait"
        # PERF.md, docs/observability.md and the per-layer metrics name these
        assert SCHED_PHASES == ("admit", "prefill", "pages", "tick",
                                "readback", "complete", "idle")


# -- the decoder -------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    import jax

    from kubeflow_tpu.models.registry import get_model

    model = get_model("transformer-test", vocab_size=64, max_seq_len=24)
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    return model, variables


def paged_decoder(lm, **kw):
    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import SlotDecoder

    pm = get_model("transformer-test", vocab_size=64, max_seq_len=24,
                   kv_pages=25, kv_page_size=4)
    return SlotDecoder(pm, lm[1], slots=3, prompt_len=8, max_new_tokens=6,
                       **kw)


def request_spans(trace_id=None):
    return [s for s in tr.COLLECTOR.spans() if s.name == "serve.request"
            and (trace_id is None or s.trace_id == trace_id)]


def entered(recorder, phase: str) -> int:
    """How often the loop entered `kftpu.sched.<phase>`."""
    return sum(1 for e in list(recorder.log)
               if e[:2] == ("enter", f"kftpu.sched.{phase}"))


class TestDecoderPhases:
    def test_counters_stamps_and_request_spans(self, lm, recorder):
        from kubeflow_tpu.serving.continuous import SCHED_PHASES

        dec = paged_decoder(lm)
        try:
            first = dec.stats()
            for p in SCHED_PHASES:           # there from construction
                assert f"phase_s.{p}" in first
            for k in ("rounds", "queue_wait_s_sum", "first_token_s_sum",
                      "first_tokens"):
                assert first[k] == 0
            snapshot = dict(first)
            prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
            with tr.TRACER.span("test.submitter") as up:
                ctx = up.context()
                threads = [threading.Thread(
                    target=lambda p=p: (tr.TRACER.attach(ctx),
                                        dec.submit(p, max_new=1 + len(p))))
                    for p in prompts]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
            st = dec.stats()
        finally:
            dec.close()
        assert first == snapshot             # an earlier snapshot is a copy
        assert set(st) == set(first)         # and no key appeared later
        assert st["completed"] == st["admitted"] == 5
        assert st["first_tokens"] == st["completed"]
        # a round is one dispatched step program and the read-back of it
        assert entered(recorder, "tick") == st["rounds"] > 0
        assert entered(recorder, "readback") == st["rounds"]
        assert entered(recorder, "prefill") == 5
        assert 0.0 < st["queue_wait_s_sum"] <= st["first_token_s_sum"]
        for p in ("admit", "prefill", "pages", "tick", "readback",
                  "complete"):
            assert st[f"phase_s.{p}"] > 0.0, p
        spans = request_spans(ctx.trace_id)
        assert len(spans) == 5
        for s in spans:
            assert set(s.attrs) == REQUEST_ATTRS
            assert s.parent_id == ctx.span_id
            assert s.attrs["outcome"] == "ok"
            assert s.attrs["prompt_tokens"] == 3
            assert s.attrs["new_tokens"] == 4
            assert 0 <= s.attrs["slot"] < 3
            assert 0.0 < s.attrs["queue_wait_s"] <= s.attrs["first_token_s"] \
                <= s.duration
        assert sum(s.attrs["queue_wait_s"] for s in spans) == pytest.approx(
            st["queue_wait_s_sum"])
        assert sum(s.attrs["prefill_tokens_computed"] for s in spans) \
            == st["prefill_tokens_computed"]
        json.dumps(tr.to_chrome_trace(spans))   # exportable as they are

    def test_a_canceled_request_says_so(self, lm):
        from kubeflow_tpu.serving.router import DeadlineExceeded

        now = [0.0]
        dec = paged_decoder(lm, clock=lambda: now[0])
        try:
            with tr.TRACER.span("test.cancel") as up:
                with pytest.raises(DeadlineExceeded):
                    now[0] = 10.0
                    dec.submit([1, 2, 3], deadline=5.0)   # shed in the queue
                assert dec.submit([1, 2, 3]) != []
            assert dec.stats()["deadline_canceled"] == 1
        finally:
            dec.close()
        by_outcome = {s.attrs["outcome"]: s
                      for s in request_spans(up.trace_id)}
        assert set(by_outcome) == {"canceled", "ok"}
        shed = by_outcome["canceled"]
        assert shed.parent_id == up.span_id
        assert shed.attrs["queue_wait_s"] is None       # never admitted
        assert shed.attrs["first_token_s"] is None
        assert shed.attrs["new_tokens"] == 0 and shed.attrs["slot"] == -1

    def test_the_waits_reach_both_sinks_once_a_request(self, lm):
        from kubeflow_tpu.runtime.metrics import REGISTRY

        dec = paged_decoder(lm, metrics_name="bridge-test")
        try:
            dec.submit([4, 5, 6])
            dec.submit([7, 8])
        finally:
            dec.close()
        text = REGISTRY.render()
        for series in ("serving_queue_wait_seconds",
                       "serving_first_token_seconds"):
            assert f'{series}_count{{model="bridge-test"}} 2' in text

    def test_speculative_loop_keeps_the_same_books(self, lm, recorder):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=2, prompt_len=8,
                          max_new_tokens=4, draft_model=model,
                          draft_variables=variables, draft_k=2)
        try:
            assert len(dec.submit([1, 2, 3])) == 4
            st = dec.stats()
        finally:
            dec.close()
        assert st["first_tokens"] == st["completed"] == 1
        assert entered(recorder, "tick") == st["rounds"] > 0
        assert entered(recorder, "prefill") == 1

    def test_one_capture_holds_the_phases_as_host_events(self, lm, tmp_path):
        import jax

        from benchmarks.lib import xplane

        dec = paged_decoder(lm)
        try:
            dec.submit([1, 2, 3])            # compile outside the capture
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                dec.submit([4, 5, 6])
            finally:
                jax.profiler.stop_trace()
        finally:
            dec.close()
        names = host_event_names(xplane.find_xplane(str(tmp_path)))
        for phase in ("tick", "readback", "prefill", "complete"):
            assert any(n.startswith(f"kftpu.sched.{phase}") for n in names), (
                phase, sorted(n for n in names if "kftpu" in n))


def profiled_spans(since: int = 0):
    return [s for s in tr.COLLECTOR.spans()[since:]
            if s.name == "serve.profiled"]


def wait_for(what, timeout=10.0):
    """Polls until `what()` is truthy (the loop sees an edge at its next
    dispatch or idle pass, 50 ms at the most)."""
    t_end = time.monotonic() + timeout
    while not what() and time.monotonic() < t_end:
        time.sleep(0.01)
    return what()


class TestProfiledStretch:
    """The loop sees a profiler session open and close by itself and
    writes what it dispatched in between as one `serve.profiled` span."""

    def test_a_real_session_leaves_one_span_of_what_it_held(self, lm,
                                                            tmp_path):
        import jax

        dec = paged_decoder(lm)
        try:
            dec.submit([1, 2, 3])            # compile outside the session
            mark = len(tr.COLLECTOR.spans())
            before = dec.stats()
            t_before = tr._EPOCH + time.perf_counter()
            jax.profiler.start_trace(str(tmp_path))
            try:
                for p in ([4, 5, 6], [7, 8], [9, 1, 2, 3]):
                    dec.submit(p, max_new=4)
                inside = dec.stats()
            finally:
                jax.profiler.stop_trace()
            assert wait_for(lambda: profiled_spans(mark))
            t_after = tr._EPOCH + time.perf_counter()
            dec.submit([5, 5, 5])            # behind the session: no part
            st = dec.stats()
        finally:
            dec.close()
        (span,) = profiled_spans(mark)
        assert span.attrs["admitted"] == 3
        assert span.attrs["tokens_decoded"] == 12
        for key in ("ticks", "rounds", "rounds.plain", "rounds.rung4",
                    "prefill_tokens_computed", "prompt_tokens_real",
                    "kv_pages_walked", "phase_s.tick", "round_s.plain"):
            assert span.attrs[key] == pytest.approx(
                inside[key] - before[key]), key
            assert span.attrs[key] > 0, key
        assert set(span.attrs) == set(dec._counters)   # all, no list
        assert st["admitted"] == before["admitted"] + 4
        # its ends lie inside the session's and bracket the admissions
        # and ends of the requests it held, and of those only
        assert t_before <= span.start < span.end <= t_after
        held = [s for s in request_spans()
                if s.attrs["queue_wait_s"] is not None and span.start
                <= s.start + s.attrs["queue_wait_s"] <= span.end]
        assert len(held) == 3 and all(s.end <= span.end for s in held)
        json.dumps(tr.to_chrome_trace([span]))

    def test_edges_in_mid_flight_count_what_was_dispatched_between(
            self, lm, recorder):
        """The session opens and closes while requests are in their
        slots: the span's `ticks` and `admitted` are those of the
        dispatches the loop made while it saw the session open."""
        dec = paged_decoder(lm)
        seen, real = [], dec.step.dispatch

        def dispatch(owners, ticks, table):
            # (the loop has just looked: what it dispatches now is in)
            seen.append((dec._profiled is not None, ticks))
            real(owners, ticks, table)
            if len(seen) == 3:
                recorder.enabled = True
            if len(seen) == 9:
                recorder.enabled = False

        dec.step.dispatch = dispatch
        try:
            mark = len(tr.COLLECTOR.spans())
            threads = [threading.Thread(
                target=lambda p=p: dec.submit(p, max_new=6))
                for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6])]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert wait_for(lambda: profiled_spans(mark))
        finally:
            dec.close()
        (span,) = profiled_spans(mark)
        assert span.attrs["ticks"] == sum(t for on, t in seen if on) > 0
        assert span.attrs["rounds"] == sum(on for on, _ in seen) == 6
        assert 0 <= span.attrs["admitted"] <= 4

    def test_no_session_no_span(self, lm):
        mark = len(tr.COLLECTOR.spans())
        dec = paged_decoder(lm)
        try:
            dec.submit([1, 2, 3])
            dec.submit([4, 5, 6])
        finally:
            dec.close()
        assert profiled_spans(mark) == []

    def test_a_stretch_open_at_close_is_recorded(self, lm, recorder):
        mark = len(tr.COLLECTOR.spans())
        dec = paged_decoder(lm)
        try:
            recorder.enabled = True
            dec.submit([1, 2, 3], max_new=5)
        finally:
            dec.close()
        (span,) = profiled_spans(mark)
        assert span.attrs["admitted"] == 1
        assert span.attrs["tokens_decoded"] == 5

    def test_a_dense_and_a_speculative_decoder_write_it_too(self, lm,
                                                            recorder):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        mark = len(tr.COLLECTOR.spans())
        dec = SlotDecoder(model, variables, slots=2, prompt_len=8,
                          max_new_tokens=4, draft_model=model,
                          draft_variables=variables, draft_k=2)
        try:
            recorder.enabled = True
            assert len(dec.submit([1, 2, 3])) == 4
            recorder.enabled = False
            assert wait_for(lambda: profiled_spans(mark))
        finally:
            dec.close()
        (span,) = profiled_spans(mark)
        assert span.attrs["admitted"] == 1
        assert span.attrs["tokens_decoded"] == 4
        assert span.attrs["ticks"] == span.attrs["rounds"] \
            == span.attrs["spec_rounds"]


def host_event_names(path) -> set:
    """Names of the host plane's events. benchmarks/lib/xplane.read wants
    a TPU plane beside them, which a CPU capture lacks; the host half is
    read the way it reads it."""
    import jax

    from benchmarks.lib import xplane

    try:
        return {name for name, _, _ in xplane.read(path)["host"]}
    except ValueError:      # "no device plane": a capture off the chip
        data = jax.profiler.ProfileData.from_file(path)
        return {e.name for plane in data.planes
                if plane.name == xplane.HOST_PLANE
                for line in plane.lines for e in line.events}


# -- the trainer -------------------------------------------------------------


class TestTrainerSplit:
    def test_step_spans_carry_the_host_split_and_classify_as_before(
            self, recorder):
        import jax

        from kubeflow_tpu.obs import goodput
        from kubeflow_tpu.parallel.mesh import MeshSpec, build_mesh
        from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

        cfg = TrainConfig.from_dict(dict(
            model="transformer-test", task="lm", global_batch=4, seq_len=32,
            vocab_size=256, mesh=MeshSpec(data=1), total_steps=3,
            log_every=100))
        trainer = Trainer(cfg, mesh=build_mesh(cfg.mesh,
                                               devices=jax.devices()[:1]))
        steps_seen = []
        with tr.TRACER.span("test.fit") as up:
            trainer.fit(callback=lambda i, m: steps_seen.append(i))
        spans = tr.COLLECTOR.trace(up.trace_id)
        steps = [s for s in spans if s.name == "train.step"]
        assert [s.attrs["step"] for s in steps] == [0, 1, 2]
        assert [bool(s.attrs.get("compile")) for s in steps] == [
            True, False, False]
        for s in steps:
            for attr in ("data_wait_s", "dispatch_s", "device_wait_s"):
                assert s.attrs[attr] >= 0.0, attr
            # the span's extent is still dispatch to block_until_ready
            assert s.attrs["dispatch_s"] + s.attrs["device_wait_s"] \
                <= s.duration + 1e-3
        want = [goodput.COMPILE, goodput.PRODUCTIVE, goodput.PRODUCTIVE]
        assert [(goodput.BUCKETS[p], t0, t1)
                for p, t0, t1 in goodput.classify(steps)] == [
            (bucket, s.start, s.end) for bucket, s in zip(want, steps)]
        assert any(s.name == "train.init" for s in spans)
        entered = [e[1] for e in recorder.log if e[0] == "enter"]
        for phase in ("data", "dispatch", "wait", "save", "eval", "callback"):
            assert entered.count(f"kftpu.train.{phase}") == 3, phase
        assert steps_seen == [0, 1, 2]


# -- one request, one tree ---------------------------------------------------


class _Req:
    """The slice of HttpReq the router's front end touches."""

    def __init__(self, body, headers):
        self.body = json.dumps(body).encode()
        self.params = {"model": "chain"}
        self._headers = {k.lower(): v for k, v in headers.items()}

    def json(self):
        return json.loads(self.body)

    def header(self, name, default=None):
        return self._headers.get(name.lower(), default)


def test_router_predict_request_is_one_tree_under_the_traceparent():
    from kubeflow_tpu.runtime.metrics import MetricsRegistry
    from kubeflow_tpu.serving import server
    from kubeflow_tpu.serving.router import (HttpTransport, Member,
                                             RouterFrontend, TokenRouter)

    srv = server.ModelServer()
    srv.register(server.serve_lm_generator(
        "chain", "transformer-test", prompt_len=8, max_new_tokens=3,
        continuous_batching=True, decode_slots=2, kv_pages=13,
        kv_page_size=4, vocab_size=64))
    svc = srv.serve(host="127.0.0.1", port=0)
    svc.serve_background()
    try:
        port = svc._server.server_address[1]
        router = TokenRouter(service="svc", namespace="ns",
                             registry=MetricsRegistry(), prom_sink=False)
        router.set_members([Member(
            name="r0", transport=HttpTransport(f"http://127.0.0.1:{port}"))])
        fe = RouterFrontend(router, max_new_tokens=3)
        fe.hedging = False
        root = tr.SpanContext(tr.new_trace_id(), tr.new_span_id())
        body = {"instances": [{"tokens": [1, 2, 3]}, {"tokens": [4, 5]}]}
        out = fe.predict(_Req(body, {"traceparent": root.to_traceparent()}))
        assert [len(p) for p in out["predictions"]] == [3, 3]
        assert set(out) == {"predictions"}       # the body did not change
    finally:
        srv.close()
        svc.shutdown()
    spans = tr.COLLECTOR.trace(root.trace_id)
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    dispatch, = by_name["router.dispatch"]
    predict, = by_name["serve.predict"]
    requests = by_name["serve.request"]
    assert dispatch.parent_id == root.span_id
    assert predict.parent_id == dispatch.span_id
    # two rows go through pool threads: both still hang under the predict
    assert [r.parent_id for r in requests] == [predict.span_id] * 2
    assert {s.span_id for s in (dispatch, predict, *requests)} <= \
        tr.reachable(spans, root.span_id)
    # set-up is in the same place, as spans
    names = {s.name for s in tr.COLLECTOR.spans()}
    assert {"serve.materialize", "serve.quantize",
            "serve.decoder_build"} <= names
