"""JAX model server: TF-Serving REST surface, jit-compiled predict path.

Endpoints (the contract test_tf_serving.py:105-133 exercises, plus the
status surface its readiness poll uses):

- GET  /v1/models/{model}                     -> model version status
- GET  /v1/models/{model}/metadata            -> signature metadata
- POST /v1/models/{model}:predict             -> {"predictions": [...]}
- POST /v1/models/{model}/versions/{v}:predict

TPU serving notes: predict functions are jit-compiled once per input
shape; batches are padded up to the next power of two so XLA reuses a
small set of compiled programs instead of recompiling per request size
(static shapes are an XLA requirement, SURVEY.md north-star notes).
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from kubeflow_tpu.obs import trace as obs_trace
from kubeflow_tpu.runtime.metrics import REGISTRY as METRICS_REGISTRY
from kubeflow_tpu.runtime.metrics import device_info
from kubeflow_tpu.serving.router import (DeadlineExceeded, HEADER_DEADLINE,
                                         _retry_after_headers)
from kubeflow_tpu.utils import httpd
from kubeflow_tpu.utils.httpd import ApiHttpError, HttpReq, Router

log = logging.getLogger("kubeflow_tpu.serving")

# the request deadline (ABSOLUTE time.monotonic value), set by the HTTP
# handler from the x-request-deadline-s header and read by predict
# closures on the SAME thread (the direct / continuous-batching path;
# the micro-batch worker thread intentionally doesn't see it — there the
# deadline is enforced at admission, see docs/robustness.md)
_REQUEST_DEADLINE: contextvars.ContextVar[float | None] = \
    contextvars.ContextVar("request_deadline", default=None)


def request_deadline() -> float | None:
    """Absolute monotonic deadline of the request being handled on this
    thread, or None."""
    return _REQUEST_DEADLINE.get()

def _metric(name, kind, doc, **kw):
    from kubeflow_tpu.runtime.metrics import prom_metric

    return prom_metric(name, kind, doc, **kw)


def predict_latency():
    import prometheus_client as prom

    return _metric("serving_predict_seconds", prom.Histogram,
                   "end-to-end predict handler latency",
                   labelnames=("model",),
                   buckets=(.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10))


def device_batch_size():
    import prometheus_client as prom

    return _metric("serving_device_batch_size", prom.Histogram,
                   "instances per device call after micro-batch coalescing",
                   labelnames=("model",),
                   buckets=(1, 2, 4, 8, 16, 32, 64, 128))


def predict_errors():
    import prometheus_client as prom

    return _metric("serving_predict_errors_total", prom.Counter,
                   "failed predict requests", labelnames=("model",))


def speculative_counters():
    import prometheus_client as prom

    return (_metric("serving_speculative_drafted_total", prom.Counter,
                    "draft tokens proposed", labelnames=("model",)),
            _metric("serving_speculative_accepted_total", prom.Counter,
                    "draft tokens accepted by the target "
                    "(accepted/drafted = acceptance rate; low rates mean "
                    "the draft is wasting rounds)", labelnames=("model",)))


class _ReplicaMeter:
    """Replica-side serving signals, exported to BOTH sinks (the PR 4
    convention): the MetricsRegistry text a JAXService control plane
    scrapes for autoscaling (``serving_queue_depth``,
    ``serving_tokens_generated_total``, ``serving_request_instances``) and
    prometheus_client for dashboards. Queue depth counts requests that
    have entered ``predict`` and not yet returned — the micro-batch
    window plus the decode itself — which is exactly the congestion a
    router should not add to."""

    def __init__(self, registry=METRICS_REGISTRY):
        import collections

        self.registry = registry
        self._lock = threading.Lock()
        self._inflight: dict[str, int] = {}
        # completion timestamps (perf_counter) per model: the drain-rate
        # window behind Retry-After on the overload 429
        self._done: dict[str, Any] = {}
        self._deque = collections.deque

    def _publish_locked(self, model: str) -> None:
        import prometheus_client as prom

        depth = self._inflight.get(model, 0)
        self.registry.gauge(
            "serving_queue_depth", depth,
            help_="requests inside predict (queued + decoding)",
            model=model)
        _metric("serving_queue_depth", prom.Gauge,
                "requests inside predict (queued + decoding)",
                labelnames=("model",)).labels(model).set(depth)

    def enter(self, model: str, n_requests: int) -> None:
        import prometheus_client as prom

        with self._lock:
            self._inflight[model] = self._inflight.get(model, 0) + 1
            self._publish_locked(model)
        self.registry.histogram(
            "serving_request_instances", n_requests,
            help_="instances per predict call",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128), model=model)
        _metric("serving_request_instances", prom.Histogram,
                "instances per predict call", labelnames=("model",),
                buckets=(1, 2, 4, 8, 16, 32, 64, 128)) \
            .labels(model).observe(n_requests)

    def exit(self, model: str) -> None:
        with self._lock:
            self._inflight[model] = max(0, self._inflight.get(model, 0) - 1)
            if model not in self._done:
                self._done[model] = self._deque(maxlen=64)
            self._done[model].append(time.perf_counter())
            self._publish_locked(model)

    def depth(self, model: str) -> int:
        with self._lock:
            return self._inflight.get(model, 0)

    def retry_after(self, model: str) -> float:
        """Seconds until the current queue should have drained, from the
        observed completion rate (the Retry-After a 429 carries; the
        router's backoff floor honors it). Conservative default of 1s
        before any completion history exists."""
        with self._lock:
            done = self._done.get(model)
            depth = self._inflight.get(model, 0)
            if not done or len(done) < 2:
                return 1.0
            span = done[-1] - done[0]
            if span <= 0:
                return 1.0
            rate = (len(done) - 1) / span
            return float(min(max(math.ceil((depth + 1) / rate), 1.0), 120.0))

    def tokens(self, model: str, n: int) -> None:
        if n <= 0:
            return
        self.registry.counter_inc(
            "serving_tokens_generated_total", by=float(n),
            help_="new tokens generated (rate = this replica's "
                  "tokens/sec, the autoscaler signal)",
            model=model)
        import prometheus_client as prom

        _metric("serving_tokens_generated_total", prom.Counter,
                "new tokens generated", labelnames=("model",)) \
            .labels(model).inc(n)


REPLICA_METER = _ReplicaMeter()


def _generated_tokens(result: list, signature: dict) -> int:
    """New-token count of a generate response (lists of token ids per
    row after _unstack); non-generate signatures contribute none."""
    if signature.get("method_name") != "generate":
        return 0
    total = 0
    for row in result or []:
        if hasattr(row, "__len__"):
            total += len(row)
    return total


@dataclass
class ServedModel:
    """One versioned model: predict_fn maps a batched np array / dict of
    arrays to predictions. batch_window_ms > 0 turns on cross-request
    micro-batching: concurrent /predict calls within the window coalesce
    into ONE padded device call (each jit dispatch has fixed overhead and
    the MXU wants large batches; serving traffic is many small
    requests — the TPU-native answer is coalescing, not more threads)."""

    name: str
    predict_fn: Callable[[Any], Any]
    version: int = 1
    signature: dict = field(default_factory=dict)
    pad_batches: bool = True
    batch_window_ms: float = 0.0
    max_batch: int = 64
    # minimum padded batch (power of two): mesh-sharded models need the
    # batch divisible by the product of data-parallel axis sizes
    pad_multiple: int = 1
    # replica-side overload gate: >0 caps concurrent predict calls; the
    # excess gets 429 + Retry-After (queue-drain estimate) instead of
    # stacking unbounded latency the router can't see
    max_inflight: int = 0
    _batcher: "MicroBatcher | None" = field(default=None, repr=False)

    def _predict_now(self, instances: list) -> list:
        batch = _stack(instances)
        n = _batch_size(batch)
        device_batch_size().labels(self.name).observe(n)
        if self.pad_batches:
            padded = _pad_batch(batch, _next_pow2(max(n, self.pad_multiple)))
        else:
            padded = batch
        out = self.predict_fn(padded)
        return _unstack(out, n)

    def __post_init__(self):
        # constructed eagerly (not lazily) so concurrent first requests
        # can't race a lazy init
        if self.batch_window_ms > 0:
            self._batcher = MicroBatcher(
                self._predict_now, max_batch=self.max_batch,
                max_wait_ms=self.batch_window_ms)

    def predict(self, instances: list) -> list:
        if not instances:
            raise ApiHttpError(400, "instances must be non-empty")
        if self.max_inflight > 0 \
                and REPLICA_METER.depth(self.name) >= self.max_inflight:
            ra = REPLICA_METER.retry_after(self.name)
            raise ApiHttpError(
                429, f"replica overloaded ({self.max_inflight} in flight)",
                headers=_retry_after_headers(ra))
        REPLICA_METER.enter(self.name, len(instances))
        try:
            if self._batcher is not None:
                result = self._batcher.submit(instances)
            else:
                result = self._predict_now(instances)
        finally:
            REPLICA_METER.exit(self.name)
        REPLICA_METER.tokens(
            self.name, _generated_tokens(result, self.signature))
        return result

    def close(self) -> None:
        if self._batcher is not None:
            self._batcher.close()


class _Pending:
    __slots__ = ("instances", "event", "result", "error")

    def __init__(self, instances: list):
        self.instances = instances
        self.event = threading.Event()
        self.result: list | None = None
        self.error: BaseException | None = None


class MicroBatcher:
    """Coalesces concurrent predict calls into single batched calls.

    A worker thread blocks for the first pending request, then keeps
    collecting arrivals until max_wait_ms elapses or max_batch instances
    are queued, concatenates all instance lists into one call of
    `fn(instances) -> results`, and scatters the per-request slices
    back. Errors from fn propagate to every caller in that batch."""

    def __init__(self, fn: Callable[[list], list], max_batch: int = 64,
                 max_wait_ms: float = 5.0):
        import queue as _queue

        self.fn = fn
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self._q: "_queue.Queue[_Pending | None]" = _queue.Queue()
        self._lock = threading.Lock()
        self._closed = False
        self._carry: _Pending | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serving-microbatch")
        self._thread.start()

    def submit(self, instances: list) -> list:
        p = _Pending(instances)
        # enqueue under the same lock close() takes to set _closed, so
        # every pending lands strictly before the shutdown sentinel (a
        # request behind the sentinel would block its caller forever)
        with self._lock:
            if self._closed:
                raise RuntimeError("MicroBatcher is closed")
            self._q.put(p)
        # the worker sets the event on every dispatch outcome (result,
        # error, shutdown drain), so the park cannot leak
        p.event.wait()  # tpulint: disable=NET501  worker guarantees set
        if p.error is not None:
            raise p.error
        return p.result  # type: ignore[return-value]

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._q.put(None)
        self._thread.join(timeout=5)

    def _run(self) -> None:
        import queue as _queue
        import time as _time

        while True:
            head = self._carry or self._q.get()
            self._carry = None
            if head is None:
                return
            group = [head]
            total = len(head.instances)
            deadline = _time.monotonic() + self.max_wait
            stop = False
            while total < self.max_batch:
                timeout = deadline - _time.monotonic()
                if timeout <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=timeout)
                except _queue.Empty:
                    break
                if nxt is None:
                    stop = True
                    break
                if total + len(nxt.instances) > self.max_batch:
                    # would overshoot the device-batch cap (and pow2
                    # padding would amplify it) — start the next group
                    self._carry = nxt
                    break
                group.append(nxt)
                total += len(nxt.instances)
            self._dispatch(group)
            if stop:
                if self._carry is not None:
                    self._dispatch([self._carry])
                    self._carry = None
                return

    def _dispatch(self, group: list[_Pending]) -> None:
        flat = [inst for p in group for inst in p.instances]
        try:
            results = self.fn(flat)
        except BaseException as e:  # noqa: BLE001 - propagate to callers
            for p in group:
                p.error = e
                p.event.set()
            return
        off = 0
        for p in group:
            p.result = results[off:off + len(p.instances)]
            off += len(p.instances)
            p.event.set()


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _ragged_ok_asarray(rows: list) -> np.ndarray:
    """np.asarray, falling back to an object array for ragged rows
    (e.g. variable-length token prompts — padded later by the model's
    own host-side handling)."""
    try:
        return np.asarray(rows)
    except ValueError:
        arr = np.empty(len(rows), dtype=object)
        for i, r in enumerate(rows):
            arr[i] = r
        return arr


def _stack(instances: list) -> Any:
    if not instances:
        raise ApiHttpError(400, "instances must be non-empty")
    first = instances[0]
    if isinstance(first, dict):
        return {k: _ragged_ok_asarray([inst[k] for inst in instances])
                for k in first}
    return _ragged_ok_asarray(instances)


def _batch_size(batch: Any) -> int:
    if isinstance(batch, dict):
        return len(next(iter(batch.values())))
    return len(batch)


def _pad_batch(batch: Any, to: int) -> Any:
    def pad(a: np.ndarray) -> np.ndarray:
        if len(a) == to:
            return a
        reps = np.repeat(a[-1:], to - len(a), axis=0)
        return np.concatenate([a, reps], axis=0)

    if isinstance(batch, dict):
        return {k: pad(v) for k, v in batch.items()}
    return pad(batch)


def _unstack(out: Any, n: int) -> list:
    if isinstance(out, dict):
        arrs = {k: np.asarray(v)[:n] for k, v in out.items()}
        return [{k: arrs[k][i].tolist() for k in arrs} for i in range(n)]
    if isinstance(out, list):
        # ragged rows (per-request max_new_tokens budgets differ), or a
        # block model's {"tokens", "fixed_at"} a row
        return [r if isinstance(r, dict) else list(r) for r in out[:n]]
    return np.asarray(out)[:n].tolist()


class ModelServer:
    def __init__(self):
        self._models: dict[str, dict[int, ServedModel]] = {}
        self._lock = threading.Lock()

    def register(self, model: ServedModel) -> None:
        with self._lock:
            versions = self._models.setdefault(model.name, {})
            old = versions.get(model.version)
            versions[model.version] = model
        if old is not None:
            # hot-swap: release the replaced model's micro-batch worker
            # (and with it the old predict closure) instead of leaking
            # one thread per reload
            old.close()

    def close(self) -> None:
        """Shut down every model's micro-batch worker (service exit)."""
        with self._lock:
            models = [m for vs in self._models.values() for m in vs.values()]
        for m in models:
            m.close()

    def _get(self, name: str, version: int | None = None) -> ServedModel:
        versions = self._models.get(name)
        if not versions:
            raise ApiHttpError(404, f"model {name!r} not found")
        if version is None:
            return versions[max(versions)]
        if version not in versions:
            raise ApiHttpError(404, f"model {name!r} version {version} not found")
        return versions[version]

    # -- handlers -----------------------------------------------------------

    def list_models(self, req: HttpReq):
        """Inventory endpoint: every served model with versions and the
        signature method (classify vs generate) — what a router or the
        dashboard needs to enumerate the serving surface."""
        with self._lock:
            out = []
            for name, versions in sorted(self._models.items()):
                latest = versions[max(versions)]
                out.append({
                    "name": name,
                    "versions": sorted(versions),
                    "method": latest.signature.get("method_name", "predict"),
                    "micro_batching": latest.batch_window_ms > 0,
                })
        return {"models": out}

    def status(self, req: HttpReq):
        name = req.params["model"]
        versions = self._models.get(name)
        if not versions:
            raise ApiHttpError(404, f"model {name!r} not found")
        return {"model_version_status": [
            {"version": str(v), "state": "AVAILABLE",
             "status": {"error_code": "OK", "error_message": ""}}
            for v in sorted(versions)
        ]}

    def metadata(self, req: HttpReq):
        m = self._get(req.params["model"])
        return {"model_spec": {"name": m.name, "version": str(m.version)},
                "metadata": {"signature_def": m.signature},
                # which device answers: a client timing this server must
                # be able to tell a chip from a CPU
                "device": device_info()}

    def predict(self, req: HttpReq):
        name = req.params["model"]
        version = int(req.params["version"]) if "version" in req.params else None
        body = req.json() or {}
        instances = body.get("instances")
        if instances is None:
            raise ApiHttpError(400, 'request body must contain "instances"')
        model = self._get(name, version)
        import time as _time

        # deadline propagation, replica hop: the header carries REMAINING
        # seconds (the router re-derives it per attempt); expose the
        # absolute monotonic deadline to same-thread predict closures
        deadline = None
        raw = req.headers.get(HEADER_DEADLINE)
        if raw:  # missing OR empty ("" is the shell's missing-header)
            try:
                remaining = float(raw)
            except ValueError:
                raise ApiHttpError(
                    400, f"bad {HEADER_DEADLINE} header: {raw!r}")
            if remaining <= 0:
                raise ApiHttpError(504, "deadline exceeded")
            deadline = _time.monotonic() + remaining
        token = _REQUEST_DEADLINE.set(deadline)
        t0 = _time.perf_counter()
        try:
            # under the request's own traceparent where it sent one, so
            # that router.dispatch -> serve.predict -> serve.request is
            # one tree, found by the trace id the client chose
            with obs_trace.TRACER.span(
                    "serve.predict",
                    parent=obs_trace.parse_traceparent(
                        req.headers.get("traceparent")),
                    model=name, instances=len(instances)):
                preds = model.predict(instances)
        except ApiHttpError:
            predict_errors().labels(name).inc()
            raise
        except DeadlineExceeded as e:
            predict_errors().labels(name).inc()
            raise ApiHttpError(504, f"deadline exceeded: {e}")
        except Exception as e:
            predict_errors().labels(name).inc()
            log.exception("predict failed for %s", name)
            raise ApiHttpError(400, f"prediction failed: {e}")
        finally:
            _REQUEST_DEADLINE.reset(token)
        predict_latency().labels(name).observe(_time.perf_counter() - t0)
        return {"predictions": preds}

    def router(self) -> Router:
        r = Router("serving")
        r.route("POST", "/v1/models/{model}:predict", self.predict)
        r.route("POST", "/v1/models/{model}/versions/{version}:predict", self.predict)
        r.route("GET", "/v1/models/{model}/metadata", self.metadata)
        r.route("GET", "/v1/models/{model}", self.status)
        r.route("GET", "/v1/models", self.list_models)
        httpd.add_health_routes(r)
        httpd.add_metrics_route(r)
        return r

    def serve(self, host: str = "0.0.0.0", port: int = 8500) -> httpd.HttpService:
        return httpd.HttpService(self.router(), host, port)


# ---------------------------------------------------------------------------
# model builders


class _ServingMesh:
    """Mesh-sharded parameter holder for serving (SURVEY north-star: a
    model too big for one chip's HBM — e.g. llama-1b f32 on v5e — is
    served by sharding parameters over the mesh: tensor-parallel leaves
    follow their nn.with_partitioning annotations, the rest fall to the
    fsdp heuristic in parallel/shardings.py, and GSPMD inserts the
    activation collectives into one compiled program per shape).

    Variables materialize on the FIRST predict (shardings are inferred
    from eval_shape of the real input), either restored from orbax and
    device_put onto their shards, or initialized directly sharded via
    jit out_shardings — the full replicated tree never exists on any
    single device.
    """

    def __init__(self, mesh_spec, seed: int, checkpoint_dir: str | None,
                 param_dtype: str | None = None):
        from kubeflow_tpu.parallel.mesh import BATCH_AXES, build_mesh

        self.mesh = build_mesh(mesh_spec)
        self.seed = seed
        self.checkpoint_dir = checkpoint_dir
        self.param_dtype = param_dtype
        if checkpoint_dir:
            # a missing/empty checkpoint must fail AT REGISTRATION
            # (crashloop + readiness gate), not as a 500 on the first
            # routed request. A cheap latest_step probe only — NOT a full
            # restore: pinning every registered model's unsharded host
            # tree until its first request would multiply host RSS.
            # Builders that know their input shape (the LM generator)
            # materialize eagerly right after construction, catching
            # corrupt/shape-mismatched checkpoints at registration too.
            from kubeflow_tpu.runtime.checkpoint import Checkpointer

            ck = Checkpointer(checkpoint_dir, async_save=False)
            try:
                if ck.latest_step() is None:
                    raise FileNotFoundError(
                        f"no checkpoint found in {checkpoint_dir}")
            finally:
                ck.close()
        self.variables = None
        self._lock = threading.Lock()
        # every batch axis, INCLUDING expert (BATCH_AXES widened in round
        # 4): padding to a multiple the batch sharding doesn't divide
        # would fail device_put at request time on MoE serving meshes
        dp = 1
        for a in BATCH_AXES:
            dp *= self.mesh.shape[a]
        if dp & (dp - 1):
            raise ValueError(
                f"serving mesh data axes product {dp} must be a power of "
                "two (batches are padded to powers of two)")
        self.pad_multiple = dp

    def get_variables(self, model, example):
        with self._lock:
            if self.variables is not None:
                return self.variables
            with obs_trace.TRACER.span(
                    "serve.materialize",
                    source="checkpoint" if self.checkpoint_dir else "init",
                    mesh=str(dict(self.mesh.shape))):
                self.variables = self._make_variables(model, example)
            return self.variables

    def _make_variables(self, model, example):
        import jax

        from kubeflow_tpu.parallel import shardings as S

        rng = jax.random.PRNGKey(self.seed)
        abstract = jax.eval_shape(
            lambda: model.init(rng, example, train=False))
        shardings = S.infer_shardings(abstract, self.mesh)
        if self.checkpoint_dir:
            from kubeflow_tpu.runtime.checkpoint import restore_variables

            host_vars, step = restore_variables(self.checkpoint_dir)
            log.info("restored variables from %s step %d (sharded %s)",
                     self.checkpoint_dir, step, dict(self.mesh.shape))
            if self.param_dtype:
                host_vars = cast_params(host_vars, self.param_dtype)
            return jax.device_put(S.unbox(host_vars), shardings)
        with self.mesh:
            def init_fn(r):
                v = S.unbox(model.init(r, example, train=False))
                return (cast_params(v, self.param_dtype)
                        if self.param_dtype else v)

            return jax.jit(init_fn, out_shardings=shardings)(rng)


def serve_flax_classifier(name: str, model_name: str, input_key: str | None = None,
                          seed: int = 0, checkpoint_dir: str | None = None,
                          mesh: "Any | None" = None,
                          **model_kwargs) -> ServedModel:
    """Wrap a zoo model into a ServedModel with a jitted softmax head.
    With `checkpoint_dir`, weights come from the latest orbax training
    checkpoint (runtime.checkpoint.restore_variables) — the analogue of
    TF-Serving pointing at an exported SavedModel; otherwise they are
    randomly initialized and the serving contract is shape/latency-
    exercised, matching the reference's mnist golden-compare approach.

    With `mesh` (a MeshSpec/dict), parameters are sharded over the device
    mesh (tensor parallelism + fsdp heuristic) and every predict runs as
    one GSPMD program across it."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model

    model = get_model(model_name, **model_kwargs)
    sm = _ServingMesh(mesh, seed, checkpoint_dir) if mesh is not None else None
    params = None
    if sm is None and checkpoint_dir:
        from kubeflow_tpu.runtime.checkpoint import restore_variables

        params, step = restore_variables(checkpoint_dir)
        log.info("model %s: restored variables from %s step %d", name,
                 checkpoint_dir, step)

    @jax.jit
    def fwd(params, x):
        logits = model.apply(params, x, train=False)
        return jax.nn.softmax(logits, axis=-1)

    state = {}

    def predict(batch):
        nonlocal params
        x = batch[input_key] if input_key and isinstance(batch, dict) else batch
        x = jnp.asarray(x, jnp.float32)
        if sm is not None:
            use_params = sm.get_variables(model, x)
        else:
            if params is None:
                state["rng"] = jax.random.PRNGKey(seed)
                params = model.init(state["rng"], x, train=False)
            use_params = params
        with (sm.mesh if sm is not None else contextlib.nullcontext()):
            return np.asarray(fwd(use_params, x))

    return ServedModel(name=name, predict_fn=predict,
                       pad_multiple=sm.pad_multiple if sm else 1,
                       signature={"inputs": input_key or "array",
                                  "method_name": "predict"})


def _prepare_serving_params(variables, param_dtype):
    """Serving-time weight preparation: 'int8'/'int4' quantize
    (weight-only, serving/quant.py), any other dtype casts, None
    passes through."""
    if param_dtype in ("int8", "int4"):
        from kubeflow_tpu.serving.quant import quantize_params

        return quantize_params(variables,
                               bits=4 if param_dtype == "int4" else 8)
    return cast_params(variables, param_dtype) if param_dtype else variables


def cast_params(variables, dtype):
    """Inference-time parameter cast (f32 training checkpoints -> bf16
    serving): KV-cache decode is HBM-bandwidth-bound on WEIGHT reads, so
    halving weight bytes is the single biggest single-chip decode lever.
    Floating leaves only; integer leaves pass
    through untouched."""
    import jax
    import jax.numpy as jnp

    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        # astype(int8) would silently truncate weights to garbage; int8
        # serving goes through _prepare_serving_params -> quantize_params
        raise ValueError(
            f"cast_params target must be floating, got {dtype!r} "
            "(use param_dtype='int8' via _prepare_serving_params)")

    def leaf(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            # a leaf that is there in `dtype` already stays the array it
            # is: no second copy of weights that fill the device once
            return x if x.dtype == jnp.dtype(dtype) else x.astype(dtype)
        return x

    return jax.tree.map(leaf, variables)


def serve_lm_generator(name: str, model_name: str, *, prompt_len: int = 128,
                       max_new_tokens: int = 32, temperature: float = 0.0,
                       top_k: int = 0, seed: int = 0,
                       checkpoint_dir: str | None = None,
                       batch_window_ms: float = 0.0, max_batch: int = 64,
                       mesh: "Any | None" = None,
                       continuous_batching: bool = False,
                       decode_slots: int = 8,
                       kv_pages: int = 0, kv_page_size: int = 0,
                       prefix_cache: bool = True,
                       param_dtype: str | None = None,
                       draft_model: str | None = None,
                       draft_checkpoint_dir: str | None = None,
                       draft_k: int = 4,
                       max_inflight: int = 0,
                       **model_kwargs) -> ServedModel:
    """Wrap a zoo LM into a generative ServedModel (the transformer-era
    analogue of the TF-Serving classifier path).

    Request instances are `{"tokens": [int, ...]}` (pre-tokenized
    prompts); each is left-padded/truncated host-side to the fixed
    `prompt_len` and decoded with the KV-cache loop
    (runtime/generate.py) for exactly `max_new_tokens` steps — one
    compiled program per batch bucket, never per request shape (static
    shapes are an XLA requirement). Over the paged cache the padding
    is geometry only: its pages are never allocated, and prefill
    computes the shortest of a fixed ladder of suffix lengths that
    covers the real tokens (runtime/kvcache.py `prefill_ladder`), each
    compiled when the decoder is built, at the first request. Responses carry the new tokens only;
    a block model's (cfg.gen_block > 0, continuous batching over the
    paged cache only) are `{"tokens": [...], "fixed_at": [...]}`, the
    denoising step of its block at which each token was fixed.
    `param_dtype="bfloat16"` leaves leaves that are bfloat16 already as
    they are.
    """
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.runtime.generate import generate

    # speculative decoding needs k positions of verify-chunk headroom
    seq_budget = prompt_len + max_new_tokens + (draft_k if draft_model else 0)
    if kv_pages and not continuous_batching:
        raise ValueError("kv_pages (the paged KV cache) requires "
                         "continuous_batching — the page pool is shared "
                         "across decode slots")
    if kv_pages and mesh is not None:
        raise ValueError("the paged KV cache is single-chip for now "
                         "(no mesh)")
    if kv_pages and not kv_page_size:
        raise ValueError("kv_pages requires kv_page_size > 0")
    if kv_pages:
        model_kwargs = dict(model_kwargs,
                            kv_pages=kv_pages, kv_page_size=kv_page_size)
    # a block model's last block may reach gen_block positions past the
    # last token asked for
    seq_budget += int(model_kwargs.get("gen_block", 0) or 0)
    model = get_model(model_name, max_seq_len=seq_budget, **model_kwargs)
    if getattr(model.cfg, "gen_block", 0) and not (
            continuous_batching and kv_pages):
        raise ValueError(
            "a block model (gen_block > 0) is served by the slot decoder "
            "over the paged KV cache only (continuous_batching with "
            "kv_pages and kv_page_size): no other path has its "
            "block-causal mask or its block step")
    if draft_model:
        if temperature > 0:
            raise ValueError("speculative decoding is greedy-only "
                             "(temperature must be 0)")
        if mesh is not None:
            raise ValueError("speculative decoding is single-chip for "
                             "now (no mesh)")
        if getattr(model.cfg, "rolling_kv_cache", False):
            # fail at REGISTRATION like the other exclusions — the
            # per-request guard in runtime/speculative.py would otherwise
            # 500 every decode on a server that reported healthy
            raise ValueError("speculative decoding requires the full KV "
                             "cache (rolling_kv_cache evicts positions a "
                             "rejected draft must rewind over)")
    if kv_pages and getattr(model.cfg, "rolling_kv_cache", False):
        raise ValueError(
            "the paged KV cache is exclusive with rolling_kv_cache: it "
            "keeps a window layer's pages by itself (with prefix_cache "
            "off it takes back the pages behind the window while the "
            "request runs, runtime/kvcache.py)")
    if kv_pages and hasattr(model.cfg, "layers"):
        # pages kept by layer kind: a rule over what is there, no option
        import dataclasses

        from kubeflow_tpu.serving.continuous import window_pages_for

        model = model.clone(cfg=dataclasses.replace(
            model.cfg, kv_window_pages=window_pages_for(
                model.cfg, decode_slots, prompt_len, max_new_tokens,
                prefix_cache=prefix_cache, draft=bool(draft_model))))
    quantized = param_dtype in ("int8", "int4")
    if quantized and mesh is not None:
        raise ValueError(f"param_dtype={param_dtype!r} serving is "
                         "single-chip for now (mesh-sharded weights "
                         "stay bf16)")
    if quantized:
        # weight-only int8/int4 (serving/quant.py): HBM streams the
        # narrow ints, the (unpack+)dequant fuses into the decode
        # matmuls inside jit
        from kubeflow_tpu.serving.quant import QuantizedModel

        model = QuantizedModel(model)
    sm = (_ServingMesh(mesh, seed, checkpoint_dir, param_dtype=param_dtype)
          if mesh is not None else None)
    if sm is not None and checkpoint_dir:
        # input shape is known here: materialize now so a shape-mismatched
        # checkpoint (wrong model/vocab) crashes registration, not the
        # first routed request
        sm.get_variables(model, jnp.zeros((1, 1), jnp.int32))

    # The set-up spans (serve.materialize, serve.quantize,
    # serve.decoder_build) add no block: each ends where the host goes
    # on, so device work still in flight then is counted where the host
    # next waits for it (the decoder's build, or the first read-back).
    def _quantize(v):
        with obs_trace.TRACER.span("serve.quantize", model=name,
                                   param_dtype=str(param_dtype)):
            return _prepare_serving_params(v, param_dtype)

    variables = None
    if sm is None and checkpoint_dir:
        from kubeflow_tpu.runtime.checkpoint import restore_variables

        with obs_trace.TRACER.span("serve.materialize", model=name,
                                   source="checkpoint"):
            variables, step = restore_variables(checkpoint_dir)
        variables = _quantize(variables)
        log.info("model %s: restored variables from %s step %d", name,
                 checkpoint_dir, step)

    def _materialize(prompt_col):
        """Non-mesh variables: lazy init + serving cast/quantize — the
        ONE place uncast f32 weights could otherwise leak from."""
        with obs_trace.TRACER.span("serve.materialize", model=name,
                                   source="init"):
            v = model.init(jax.random.PRNGKey(seed), prompt_col,
                           train=False)
        return _quantize(v)

    draft_box: list = []

    def _draft():
        """Lazy draft model + variables (same cast/quantize treatment
        as the target)."""
        if not draft_box:
            dm = get_model(draft_model, max_seq_len=seq_budget)
            if quantized:
                from kubeflow_tpu.serving.quant import QuantizedModel

                dm = QuantizedModel(dm)
            if draft_checkpoint_dir:
                from kubeflow_tpu.runtime.checkpoint import restore_variables

                dvars, _ = restore_variables(draft_checkpoint_dir)
            else:
                dvars = dm.init(jax.random.PRNGKey(seed + 1),
                                jnp.zeros((1, 1), jnp.int32), train=False)
            draft_box.extend([dm, _prepare_serving_params(dvars, param_dtype)])
        return draft_box[0], draft_box[1]

    import itertools

    # temperature>0: each request gets a fresh seed (generate() takes it
    # as a traced scalar, so this does NOT recompile per request);
    # temperature==0 stays at the fixed seed — greedy is deterministic.
    request_seed = itertools.count(seed).__next__

    decoder_box: list = []  # lazy SlotDecoder (needs materialized vars)
    _decoder_lock = threading.Lock()

    def _validated_rows(toks):
        # host-side ragged handling: LEFT-pad / keep the LAST prompt_len
        # tokens so the most recent context survives a trim; pad_lens
        # mask the pad positions out of decode attention (generate.py)
        vocab = model.cfg.vocab_size
        rows, pad_lens = [], []
        for row in np.asarray(toks, dtype=object):
            row = [int(t) for t in (row if hasattr(row, "__len__") else [row])]
            bad = [t for t in row if not 0 <= t < vocab]
            if bad:
                # JAX gather clamps out-of-range indices silently; a
                # tokenizer/vocab mismatch must be a 400, not garbage
                raise ApiHttpError(
                    400, f"token ids out of range [0, {vocab}): {bad[:5]}")
            row = row[-prompt_len:]
            pad_lens.append(prompt_len - len(row))
            rows.append([0] * (prompt_len - len(row)) + row)
        return rows, pad_lens

    def _validated_max_news(batch, n):
        """Optional per-instance "max_new_tokens" cap (every instance
        must carry the key or none): a paged decoder reserves pages for
        the REQUEST's budget, not the server-wide ceiling."""
        caps = (batch.get("max_new_tokens")
                if isinstance(batch, dict) else None)
        if caps is None:
            return [None] * n
        flat = np.asarray(caps, dtype=object).reshape(-1)
        if len(flat) != n:
            # a short list would silently zip-truncate the batch,
            # dropping requests and misaligning instance -> prediction
            raise ApiHttpError(
                400, f"max_new_tokens must be one value per instance "
                     f"(got {len(flat)} for {n} instances)")
        out = []
        for c in flat:
            c = int(c)
            if not 1 <= c <= max_new_tokens:
                raise ApiHttpError(
                    400, f"max_new_tokens must be in 1..{max_new_tokens}, "
                         f"got {c}")
            out.append(c)
        return out

    def _capped_rows(out_rows, maxnews):
        """Apply per-instance budgets to whole-batch decode output:
        every path honors the documented cap, not just the slot
        decoder (ragged results when budgets differ)."""
        if all(c is None for c in maxnews):
            return out_rows
        return [list(np.asarray(row)[:c if c is not None else len(row)])
                for row, c in zip(out_rows, maxnews)]

    def predict(batch):
        nonlocal variables
        toks = batch["tokens"] if isinstance(batch, dict) else batch
        rows, pad_lens = _validated_rows(toks)
        maxnews = _validated_max_news(batch, len(rows))
        if continuous_batching:
            # slot-based lockstep decode: rows join the shared decoder at
            # step boundaries and finish independently — a long
            # generation never blocks a short one (serving/continuous.py)
            from kubeflow_tpu.serving.continuous import SlotDecoder

            with _decoder_lock:  # concurrent first requests: one decoder
                if not decoder_box:
                    if sm is not None:
                        use_vars = sm.get_variables(
                            model, jnp.zeros((1, 1), jnp.int32))
                    else:
                        use_vars = variables or _materialize(
                            jnp.zeros((1, 1), jnp.int32))
                    dm = dv = None
                    if draft_model:
                        dm, dv = _draft()
                    with obs_trace.TRACER.span("serve.decoder_build",
                                               model=name,
                                               slots=decode_slots):
                        decoder_box.append(SlotDecoder(
                            model, use_vars, slots=decode_slots,
                            prompt_len=prompt_len,
                            max_new_tokens=max_new_tokens,
                            temperature=temperature, top_k=top_k, seed=seed,
                            mesh=sm.mesh if sm is not None else None,
                            prefix_cache=prefix_cache,
                            draft_model=dm, draft_variables=dv,
                            draft_k=draft_k, metrics_name=name))
            dec = decoder_box[0]
            # capture the handler thread's deadline HERE: pool.map runs
            # submit_padded on worker threads that don't inherit the
            # contextvar (micro-batched callers see None — admission-time
            # enforcement only, docs/robustness.md)
            dl = request_deadline()
            if len(rows) == 1:  # hot path: no thread churn per request
                outs = [dec.submit_padded(rows[0], pad_lens[0],
                                          maxnews[0], dl)]
            else:
                import concurrent.futures as cf

                # each row's serve.request span parents on this thread's
                # ambient span: a copy of its context a pool thread
                # (a Context is entered by one thread at a time)
                ctxs = [contextvars.copy_context() for _ in rows]
                with cf.ThreadPoolExecutor(max_workers=len(rows)) as pool:
                    outs = list(pool.map(
                        lambda c, *a: c.run(dec.submit_padded, *a),
                        ctxs, rows, pad_lens, maxnews, [dl] * len(rows)))
            if isinstance(outs[0], dict):
                # a block model's prediction: the tokens, and the step of
                # its block at which each was fixed
                return outs
            # per-request budgets produce ragged rows; pad the response
            # rows only when a caller actually mixed budgets
            if len({len(o) for o in outs}) > 1:
                return [list(o) for o in outs]
            return np.asarray(outs, dtype=np.int64)
        prompt = jnp.asarray(rows, jnp.int32)
        if sm is not None:
            use_vars = sm.get_variables(model, prompt[:, :1])
        else:
            if variables is None:
                variables = _materialize(prompt[:, :1])
            use_vars = variables
        if draft_model:
            # speculative: batch-1 rounds per row (accept lengths are
            # data-dependent); concurrency comes from the micro-batcher
            from kubeflow_tpu.runtime.speculative import speculative_generate

            dm, dv = _draft()
            drafted_c, accepted_c = speculative_counters()
            outs = []
            for r in range(prompt.shape[0]):
                toks, stats = speculative_generate(
                    model, use_vars, dm, dv, prompt[r:r + 1],
                    max_new_tokens=max_new_tokens, k=draft_k,
                    pad_len=jnp.asarray(pad_lens[r:r + 1], jnp.int32))
                drafted_c.labels(model=name).inc(stats["drafted"])
                accepted_c.labels(model=name).inc(stats["accepted"])
                outs.append(np.asarray(toks)[0])
            return _capped_rows(np.stack(outs)[:, prompt_len:], maxnews)
        with (sm.mesh if sm is not None else contextlib.nullcontext()):
            out = np.asarray(generate(
                model, use_vars, prompt, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k,
                seed=request_seed() if temperature > 0 else seed,
                pad_len=jnp.asarray(pad_lens, jnp.int32)))
        return _capped_rows(out[:, prompt_len:], maxnews)  # new tokens only

    served = ServedModel(
        name=name, predict_fn=predict,
        # the slot decoder handles raggedness natively, and the
        # speculative path is sequential batch-1 rounds; pow2 padding
        # would just decode phantom rows in both
        pad_batches=not (continuous_batching or draft_model),
        batch_window_ms=batch_window_ms, max_batch=max_batch,
        pad_multiple=sm.pad_multiple if sm else 1,
        max_inflight=max_inflight,
        signature={"inputs": "tokens", "method_name": "generate",
                   "prompt_len": prompt_len,
                   "max_new_tokens": max_new_tokens,
                   **({"continuous_batching": True,
                       "decode_slots": decode_slots}
                      if continuous_batching else {}),
                   **({"kv_pages": kv_pages,
                       "kv_page_size": kv_page_size,
                       "prefix_cache": prefix_cache}
                      if kv_pages else {}),
                   **({"param_dtype": param_dtype} if param_dtype else {}),
                   **({"draft_model": draft_model, "draft_k": draft_k}
                      if draft_model else {}),
                   **({"mesh": {k: v for k, v in sm.mesh.shape.items()
                                if v > 1}} if sm else {})})
    if continuous_batching:
        orig_close = served.close

        def _close():
            if decoder_box:
                decoder_box[0].close()
            orig_close()

        served.close = _close  # type: ignore[method-assign]
    return served


def main() -> None:  # pragma: no cover - container entry
    import argparse

    p = argparse.ArgumentParser("kubeflow-tpu-serving")
    p.add_argument("--port", type=int, default=8500)
    p.add_argument("--model", action="append", default=[],
                   help="name=zoo_model, e.g. mnist=resnet18")
    p.add_argument("--checkpoint-dir", default=None,
                   help="orbax checkpoint dir to restore model weights from "
                        "(single --model only; use name=zoo@dir per model)")
    p.add_argument("--lm", action="append", default=[],
                   help="generative LM entry: name=zoo_model[@ckpt_dir], "
                        "e.g. chat=gpt-125m")
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--param-dtype", default=None,
                   choices=["bfloat16", "float32", "int8", "int4"],
                   help="cast served LM parameters (bfloat16 halves the "
                        "weight HBM reads that dominate decode; int8 is "
                        "weight-only quantization, halving them again; "
                        "int4 packs two nibbles per byte for one more "
                        "halving at a looser error bound)")
    p.add_argument("--attention-window", type=int, default=0,
                   help="sliding-window attention width for served LMs "
                        "(0 = full causal)")
    p.add_argument("--rolling-kv-cache", action="store_true",
                   help="bound the decode KV cache to the attention "
                        "window (slot = position %% window): serving "
                        "memory and per-step cache bandwidth become "
                        "O(window) instead of O(max_seq); requires "
                        "--attention-window")
    p.add_argument("--kv-cache-dtype", default=None,
                   choices=["auto", "int8"],
                   help="int8 quantizes the decode KV cache (per-token-"
                        "head scales): the long-context decode lever")
    p.add_argument("--draft-model", default=None,
                   help="zoo model that drafts k tokens per round for "
                        "speculative decoding (greedy-exact; e.g. "
                        "gpt-125m drafting for llama-1b)")
    p.add_argument("--draft-k", type=int, default=4)
    p.add_argument("--draft-checkpoint-dir", default=None,
                   help="orbax checkpoint for the draft model — a "
                        "randomly initialized draft accepts ~nothing "
                        "and makes speculative serving SLOWER")
    p.add_argument("--max-inflight", type=int, default=0,
                   help="replica overload gate: cap concurrent predict "
                        "calls per LM; excess gets 429 + Retry-After "
                        "(0 = uncapped)")
    p.add_argument("--continuous-batching", action="store_true",
                   help="slot-based lockstep decode: requests join at any "
                        "step boundary and finish independently")
    p.add_argument("--decode-slots", type=int, default=8)
    p.add_argument("--kv-pages", type=int, default=0,
                   help="paged KV cache: total pool pages shared across "
                        "decode slots (page 0 is trash); admission is "
                        "gated on page availability and shared prompt "
                        "prefixes reuse pages. Requires "
                        "--continuous-batching and --kv-page-size")
    p.add_argument("--kv-page-size", type=int, default=0,
                   help="positions per KV-cache page")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable prompt-prefix page sharing (A/B lever; "
                        "pages are still pooled)")
    p.add_argument("--mesh", default=None,
                   help="shard served params over a mesh, e.g. "
                        "'model=4,fsdp=2' — required for models whose "
                        "state exceeds one chip's HBM")
    args = p.parse_args()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from kubeflow_tpu.utils import compile_cache

    log.info("compile cache: %s", compile_cache.configure())
    # take the device now: a server that cannot reach its chip must die
    # here, not answer 400s from inside the first request
    log.info("device: %(count)d x %(kind)s (%(platform)s)" % device_info())
    mesh_spec = None
    if args.mesh:
        try:
            mesh_spec = {k: int(v) for k, v in
                         (kv.split("=", 1) for kv in args.mesh.split(","))}
        except ValueError:
            p.error(f"--mesh must be axis=int[,axis=int...], got {args.mesh!r}")
    # default classifier only when nothing at all was requested
    models = args.model or ([] if args.lm else ["mnist=resnet18"])
    if args.checkpoint_dir and len(models) > 1:
        p.error("--checkpoint-dir applies to exactly one --model; "
                "use name=zoo@ckpt_dir syntax for multiple models")
    server = ModelServer()
    for spec in models:
        name, _, zoo = spec.partition("=")
        zoo, _, ckpt = zoo.partition("@")
        server.register(serve_flax_classifier(name, zoo or "resnet18",
                                              num_classes=10, mesh=mesh_spec,
                                              checkpoint_dir=ckpt or args.checkpoint_dir))
    for spec in args.lm:
        name, _, zoo = spec.partition("=")
        zoo, _, ckpt = zoo.partition("@")
        server.register(serve_lm_generator(
            name, zoo or "gpt-125m", prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens, mesh=mesh_spec,
            continuous_batching=args.continuous_batching,
            decode_slots=args.decode_slots,
            kv_pages=args.kv_pages, kv_page_size=args.kv_page_size,
            prefix_cache=not args.no_prefix_cache,
            param_dtype=args.param_dtype,
            max_inflight=args.max_inflight,
            checkpoint_dir=ckpt or None,
            draft_model=args.draft_model, draft_k=args.draft_k,
            draft_checkpoint_dir=args.draft_checkpoint_dir,
            **({"kv_cache_dtype": args.kv_cache_dtype}
               if args.kv_cache_dtype else {}),
            **({"attention_window": args.attention_window}
               if args.attention_window else {}),
            **({"rolling_kv_cache": True}
               if args.rolling_kv_cache else {})))
    svc = server.serve(port=args.port)
    log.info("serving on :%d", svc.port)
    # SIGTERM (pod termination, chip_smoke.py) stops the listener from
    # another thread — shutdown() blocks until serve_forever returns —
    # so the decoders close and the process gives the chip back cleanly
    import signal

    signal.signal(signal.SIGTERM, lambda *_: threading.Thread(
        target=svc.shutdown, name="sigterm-shutdown", daemon=True).start())
    try:
        svc.serve_forever()
    finally:
        server.close()
    log.info("stopped")


if __name__ == "__main__":  # pragma: no cover
    main()
