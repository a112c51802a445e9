"""Serving REST contract — mirrors testing/test_tf_serving.py:105-133:
POST /v1/models/<m>:predict with retries, numeric-tolerance compare."""

import numpy as np
import pytest
import requests

from kubeflow_tpu.serving.server import (
    ModelServer,
    ServedModel,
    _next_pow2,
    serve_flax_classifier,
)


def softmax_rows(x):
    x = np.asarray(x, np.float64)
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


@pytest.fixture(scope="module")
def server():
    srv = ModelServer()
    # a deterministic "mnist" stand-in: fixed linear map + softmax
    rng = np.random.default_rng(0)
    w = rng.normal(size=(784, 10)).astype(np.float32)

    srv.register(ServedModel(
        name="mnist",
        predict_fn=lambda batch: softmax_rows(
            np.asarray(batch, np.float32).reshape(len(batch), -1) @ w),
        signature={"inputs": "images"},
    ))
    svc = srv.serve(host="127.0.0.1", port=0)
    svc.serve_background()
    yield srv, f"http://127.0.0.1:{svc.port}"
    svc.shutdown()


class TestRestContract:
    def test_predict_with_retries_and_tolerance(self, server):
        """The exact loop shape of test_tf_serving.py:105-133: retry the
        POST, then almost_equal compare."""
        _, base = server
        x = np.random.default_rng(1).random((3, 28, 28)).tolist()
        result = None
        for _ in range(10):  # num_tries=10 (:108)
            r = requests.post(f"{base}/v1/models/mnist:predict",
                              json={"instances": x}, timeout=10)
            if r.status_code == 200:
                result = r.json()
                break
        assert result is not None
        preds = np.asarray(result["predictions"])
        assert preds.shape == (3, 10)
        np.testing.assert_allclose(preds.sum(axis=-1), 1.0, atol=1e-5)
        # golden determinism: same input -> same output within tolerance
        r2 = requests.post(f"{base}/v1/models/mnist:predict",
                           json={"instances": x}, timeout=10)
        np.testing.assert_allclose(np.asarray(r2.json()["predictions"]),
                                   preds, atol=1e-6)

    def test_status_endpoint(self, server):
        _, base = server
        r = requests.get(f"{base}/v1/models/mnist", timeout=5)
        st = r.json()["model_version_status"][0]
        assert st["state"] == "AVAILABLE"
        assert st["status"]["error_code"] == "OK"

    def test_metadata(self, server):
        _, base = server
        r = requests.get(f"{base}/v1/models/mnist/metadata", timeout=5)
        assert r.json()["model_spec"]["name"] == "mnist"

    def test_unknown_model_404(self, server):
        _, base = server
        r = requests.post(f"{base}/v1/models/nope:predict",
                          json={"instances": [[1]]}, timeout=5)
        assert r.status_code == 404

    def test_missing_instances_400(self, server):
        _, base = server
        r = requests.post(f"{base}/v1/models/mnist:predict",
                          json={"inputs": [1]}, timeout=5)
        assert r.status_code == 400

    def test_versioned_predict(self, server):
        srv, base = server
        srv.register(ServedModel(name="mnist", version=2,
                                 predict_fn=lambda b: np.zeros((len(b), 10))))
        r = requests.post(f"{base}/v1/models/mnist/versions/2:predict",
                          json={"instances": [[0.0] * 784]}, timeout=5)
        assert r.status_code == 200
        assert np.allclose(r.json()["predictions"], 0.0)
        # latest (highest) version now serves zeros too
        r2 = requests.post(f"{base}/v1/models/mnist:predict",
                           json={"instances": [[0.0] * 784]}, timeout=5)
        assert np.allclose(r2.json()["predictions"], 0.0)


class TestBatching:
    def test_pow2_padding(self):
        assert [_next_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]

    def test_padding_does_not_change_results(self):
        calls = []

        def fn(batch):
            calls.append(len(batch))
            return np.asarray(batch) * 2

        m = ServedModel(name="x", predict_fn=fn)
        out = m.predict([[1.0], [2.0], [3.0]])
        assert calls == [4]  # padded to pow2
        assert out == [[2.0], [4.0], [6.0]]  # but only 3 results returned

    def test_dict_instances(self):
        m = ServedModel(
            name="x",
            predict_fn=lambda b: {"score": b["a"] + b["b"]},
            pad_batches=False,
        )
        out = m.predict([{"a": 1, "b": 2}, {"a": 3, "b": 4}])
        assert out == [{"score": 3}, {"score": 7}]


class TestFlaxServing:
    def test_resnet_classifier_end_to_end(self, server):
        """A real jitted flax model behind the same contract (BERT-base
        path parity: jit once, stable outputs)."""
        srv, base = server
        srv.register(serve_flax_classifier("digits", "resnet18", num_classes=10))
        x = np.random.default_rng(2).random((2, 28, 28, 1)).tolist()
        r = requests.post(f"{base}/v1/models/digits:predict",
                          json={"instances": x}, timeout=120)
        assert r.status_code == 200, r.text
        preds = np.asarray(r.json()["predictions"])
        assert preds.shape == (2, 10)
        np.testing.assert_allclose(preds.sum(axis=-1), 1.0, atol=1e-4)


class TestMicroBatching:
    """Cross-request micro-batching: concurrent predicts coalesce into
    one padded device call (the TPU-native serving pattern — jit
    dispatch overhead amortizes, the MXU sees real batches)."""

    def test_concurrent_requests_coalesce(self):
        import threading

        calls = []

        def fn(instances):
            calls.append(len(instances))
            return [x * 2 for x in instances]

        from kubeflow_tpu.serving.server import MicroBatcher

        b = MicroBatcher(fn, max_batch=64, max_wait_ms=150.0)
        results = {}
        barrier = threading.Barrier(8)

        def worker(i):
            barrier.wait()
            results[i] = b.submit([i, i + 100])

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        b.close()
        for i in range(8):
            assert results[i] == [2 * i, 2 * (i + 100)]
        assert sum(calls) == 16
        assert len(calls) < 8, f"no coalescing happened: {calls}"

    def test_max_batch_bounds_group_size(self):
        import threading

        calls = []

        def fn(instances):
            calls.append(len(instances))
            return list(instances)

        from kubeflow_tpu.serving.server import MicroBatcher

        b = MicroBatcher(fn, max_batch=4, max_wait_ms=200.0)
        barrier = threading.Barrier(6)

        def worker(i):
            barrier.wait()
            b.submit([i])

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        b.close()
        assert sum(calls) == 6
        assert max(calls) <= 4

    def test_errors_propagate_to_all_callers(self):
        from kubeflow_tpu.serving.server import MicroBatcher

        def fn(instances):
            raise RuntimeError("boom")

        b = MicroBatcher(fn, max_batch=8, max_wait_ms=10.0)
        with pytest.raises(RuntimeError, match="boom"):
            b.submit([1])
        b.close()

    def test_http_concurrent_predicts_through_one_model_call(self):
        import threading

        calls = []

        def fn(batch):
            calls.append(len(batch))
            return softmax_rows(np.asarray(batch, np.float64))

        srv = ModelServer()
        srv.register(ServedModel(name="m", predict_fn=fn,
                                 batch_window_ms=150.0))
        svc = srv.serve(host="127.0.0.1", port=0)
        svc.serve_background()
        url = f"http://127.0.0.1:{svc.port}/v1/models/m:predict"
        outs = {}
        barrier = threading.Barrier(4)

        def worker(i):
            barrier.wait()
            outs[i] = requests.post(url, json={"instances": [[i, 0.0]]},
                                    timeout=30).json()

        try:
            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(4)]
            [t.start() for t in ts]
            [t.join() for t in ts]
        finally:
            svc.shutdown()
        for i in range(4):
            got = outs[i]["predictions"][0]
            want = softmax_rows(np.asarray([[i, 0.0]]))[0]
            np.testing.assert_allclose(got, want, rtol=1e-6)
        assert len(calls) < 4, f"requests were not coalesced: {calls}"


def test_microbatcher_never_overshoots_max_batch():
    from kubeflow_tpu.serving.server import MicroBatcher

    import threading

    calls = []

    def fn(instances):
        calls.append(len(instances))
        return list(instances)

    b = MicroBatcher(fn, max_batch=4, max_wait_ms=200.0)
    barrier = threading.Barrier(3)

    def worker(i):
        barrier.wait()
        b.submit([i] * 3)  # 3 instances each: 2 would overshoot cap 4

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    b.close()
    assert sum(calls) == 9
    assert max(calls) <= 4


def test_microbatcher_close_rejects_new_and_drains_pending():
    from kubeflow_tpu.serving.server import MicroBatcher

    def fn(instances):
        return list(instances)

    b = MicroBatcher(fn, max_batch=8, max_wait_ms=5.0)
    assert b.submit([1]) == [1]
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit([2])
    b.close()  # idempotent


class TestLmGeneration:
    """Generative LM serving: the transformer-era TF-Serving analogue
    (pre-tokenized prompts in, new tokens out, static shapes)."""

    @pytest.fixture(scope="class")
    def lm_server(self):
        from kubeflow_tpu.serving.server import serve_lm_generator

        srv = ModelServer()
        srv.register(serve_lm_generator(
            "tiny-lm", "transformer-test", prompt_len=8, max_new_tokens=4,
            vocab_size=64))
        svc = srv.serve(host="127.0.0.1", port=0)
        svc.serve_background()
        yield f"http://127.0.0.1:{svc.port}"
        svc.shutdown()
        srv.close()

    def test_generates_fixed_new_tokens(self, lm_server):
        r = requests.post(
            f"{lm_server}/v1/models/tiny-lm:predict",
            json={"instances": [{"tokens": [1, 2, 3]},
                                {"tokens": [4, 5, 6, 7, 8, 9]}]},
            timeout=120)
        assert r.status_code == 200, r.text
        preds = r.json()["predictions"]
        assert len(preds) == 2
        for p in preds:
            assert len(p) == 4  # max_new_tokens
            assert all(0 <= t < 64 for t in p)

    def test_ragged_and_overlong_prompts(self, lm_server):
        # an overlong prompt keeps its LAST prompt_len tokens
        long_prompt = list(range(1, 20))
        r = requests.post(
            f"{lm_server}/v1/models/tiny-lm:predict",
            json={"instances": [{"tokens": long_prompt},
                                {"tokens": [2]}]},
            timeout=120)
        assert r.status_code == 200, r.text
        assert len(r.json()["predictions"]) == 2

    def test_greedy_is_deterministic(self, lm_server):
        body = {"instances": [{"tokens": [3, 1, 4, 1, 5]}]}
        a = requests.post(f"{lm_server}/v1/models/tiny-lm:predict",
                          json=body, timeout=120).json()
        b = requests.post(f"{lm_server}/v1/models/tiny-lm:predict",
                          json=body, timeout=120).json()
        assert a["predictions"] == b["predictions"]

    def test_metadata_exposes_generation_signature(self, lm_server):
        meta = requests.get(
            f"{lm_server}/v1/models/tiny-lm/metadata", timeout=30).json()
        sig = meta["metadata"]["signature_def"]
        assert sig["method_name"] == "generate"
        assert sig["prompt_len"] == 8 and sig["max_new_tokens"] == 4


def test_prometheus_metrics_exported(server):
    """Per-model predict latency + device batch size + error counters at
    /metrics (every reference service exports prometheus; the serving
    hot path now does too)."""
    srv, url = server
    requests.post(f"{url}/v1/models/mnist:predict",
                  json={"instances": [[0.0] * 784]}, timeout=60)
    requests.post(f"{url}/v1/models/mnist:predict",
                  json={"instances": "bogus"}, timeout=60)
    text = requests.get(f"{url}/metrics", timeout=30).text
    assert 'serving_predict_seconds_count{model="mnist"}' in text
    assert 'serving_device_batch_size_bucket' in text
    assert 'serving_predict_errors_total{model="mnist"}' in text


def test_lm_generation_with_microbatching_coalesces_and_matches():
    """Generative serving + cross-request micro-batching: concurrent
    ragged prompts coalesce into one padded device call and each caller
    still gets exactly its solo-run greedy continuation."""
    import threading

    from kubeflow_tpu.serving.server import ModelServer, serve_lm_generator

    calls = []
    model = serve_lm_generator(
        "tiny-mb", "transformer-test", prompt_len=8, max_new_tokens=3,
        vocab_size=64, batch_window_ms=150.0)
    inner = model.predict_fn

    def counting(batch):
        calls.append(len(batch["tokens"]) if isinstance(batch, dict)
                     else len(batch))
        return inner(batch)

    model.predict_fn = counting
    srv = ModelServer()
    srv.register(model)
    svc = srv.serve(host="127.0.0.1", port=0)
    svc.serve_background()
    url = f"http://127.0.0.1:{svc.port}/v1/models/tiny-mb:predict"
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10, 11]]
    outs = {}
    barrier = threading.Barrier(len(prompts))

    def worker(i):
        barrier.wait()
        outs[i] = requests.post(
            url, json={"instances": [{"tokens": prompts[i]}]},
            timeout=300).json()

    try:
        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(len(prompts))]
        [t.start() for t in ts]
        [t.join() for t in ts]
        # solo runs for comparison (after: keeps the window clear)
        solos = [requests.post(url, json={"instances": [{"tokens": p}]},
                               timeout=300).json() for p in prompts]
    finally:
        svc.shutdown()
        srv.close()
    for i in range(len(prompts)):
        assert outs[i]["predictions"] == solos[i]["predictions"], i
    assert max(calls) >= 2, f"no coalescing observed: {calls}"


def test_list_models_inventory(server):
    srv, url = server
    out = requests.get(f"{url}/v1/models", timeout=30).json()
    [m] = [x for x in out["models"] if x["name"] == "mnist"]
    # module-scoped server: other tests may have registered more versions
    assert 1 in m["versions"] and m["versions"] == sorted(m["versions"])
    assert m["method"] == "predict"
    assert m["micro_batching"] is False


class TestMeshShardedServing:
    """A model whose params are sharded over the device mesh
    (2 fsdp x 4 model on the virtual 8-device CPU mesh) answers the same
    REST contract — predict AND generate — with GSPMD inserting the
    collectives. This is the only way a model too big for one chip's HBM
    (llama-1b f32 on v5e) is servable at all."""

    MESH = {"fsdp": 2, "model": 4}

    @pytest.fixture(scope="class")
    def sharded_lm(self):
        from kubeflow_tpu.serving.server import serve_lm_generator

        srv = ModelServer()
        srv.register(serve_lm_generator(
            "big-lm", "transformer-test", prompt_len=8, max_new_tokens=4,
            vocab_size=64, mesh=self.MESH))
        svc = srv.serve(host="127.0.0.1", port=0)
        svc.serve_background()
        yield f"http://127.0.0.1:{svc.port}"
        svc.shutdown()
        srv.close()

    def test_params_actually_sharded(self):
        from kubeflow_tpu.models.registry import get_model
        from kubeflow_tpu.serving.server import _ServingMesh

        import jax.numpy as jnp

        sm = _ServingMesh(self.MESH, seed=0, checkpoint_dir=None)
        model = get_model("transformer-test", vocab_size=64, max_seq_len=12)
        variables = sm.get_variables(model, jnp.ones((1, 1), jnp.int32))
        import jax

        leaves = jax.tree.leaves(variables)
        sharded = [l for l in leaves
                   if hasattr(l, "sharding")
                   and any(s is not None for s in l.sharding.spec)]
        assert sharded, "no parameter leaf is sharded over the mesh"
        # at least one leaf rides the tensor-parallel axis
        assert any("model" in str(l.sharding.spec) for l in sharded)

    def test_generate_over_sharded_mesh_http(self, sharded_lm):
        r = requests.post(
            f"{sharded_lm}/v1/models/big-lm:predict",
            json={"instances": [{"tokens": [1, 2, 3]},
                                {"tokens": [4, 5, 6, 7]}]},
            timeout=300)
        assert r.status_code == 200, r.text
        preds = r.json()["predictions"]
        assert len(preds) == 2
        for p in preds:
            assert len(p) == 4 and all(0 <= t < 64 for t in p)
        meta = requests.get(
            f"{sharded_lm}/v1/models/big-lm/metadata", timeout=30).json()
        assert meta["metadata"]["signature_def"]["mesh"] == self.MESH

    def test_sharded_matches_unsharded_greedy(self, sharded_lm):
        """Same seed, same prompt: the 8-way-sharded model must decode
        the same greedy tokens as the single-device one — sharding is a
        placement decision, not a numerics change (bf16 aside: this
        model runs f32 on CPU)."""
        from kubeflow_tpu.serving.server import serve_lm_generator

        plain = serve_lm_generator(
            "ref-lm", "transformer-test", prompt_len=8, max_new_tokens=4,
            vocab_size=64)
        body = [{"tokens": [3, 1, 4, 1, 5]}]
        want = plain.predict(body)
        r = requests.post(f"{sharded_lm}/v1/models/big-lm:predict",
                          json={"instances": body}, timeout=300)
        got = r.json()["predictions"]
        assert got == [list(map(int, w)) for w in want]

    def test_sharded_classifier_predict(self):
        from kubeflow_tpu.serving.server import serve_flax_classifier

        import numpy as np

        m = serve_flax_classifier(
            "cls", "resnet18", mesh=self.MESH, num_classes=10)
        # resnet has no TP annotations: the fsdp heuristic shards its
        # large kernels; the 32x32 input keeps the CPU compile cheap
        out = m.predict([np.zeros((32, 32, 3), np.float32)])
        assert len(out) == 1 and len(out[0]) == 10

    def test_sharded_restore_from_training_checkpoint(self, tmp_path):
        """Train 1 step (single-device trainer), then serve the orbax
        checkpoint SHARDED: restore -> device_put onto shards."""
        from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer
        from kubeflow_tpu.serving.server import serve_lm_generator

        cfg = TrainConfig.from_dict(dict(
            model="transformer-test", task="lm", global_batch=8,
            seq_len=12, vocab_size=64,
            model_kwargs={"vocab_size": 64},  # model head = data vocab
            total_steps=1, warmup_steps=1,
            checkpoint_dir=str(tmp_path), checkpoint_every=1))
        Trainer(cfg).fit(steps=1)
        m = serve_lm_generator(
            "ckpt-lm", "transformer-test", prompt_len=8, max_new_tokens=2,
            vocab_size=64, mesh=self.MESH, checkpoint_dir=str(tmp_path))
        out = m.predict([{"tokens": [1, 2, 3]}])
        assert len(out) == 1 and len(out[0]) == 2


def test_mesh_with_missing_checkpoint_fails_at_registration(tmp_path):
    """A bad --checkpoint-dir must crash at register time (readiness
    gates catch it), not 500 on the first routed request."""
    from kubeflow_tpu.serving.server import serve_lm_generator

    with pytest.raises(FileNotFoundError):
        serve_lm_generator(
            "bad", "transformer-test", prompt_len=8, max_new_tokens=2,
            vocab_size=64, mesh={"model": 4, "fsdp": 2},
            checkpoint_dir=str(tmp_path / "empty"))


class TestParamDtypeCasting:
    """Inference-time bf16 weight casting: decode is HBM-bound on weight
    reads, so halving weight bytes is the single-chip decode lever."""

    def test_served_params_are_cast_and_generation_valid(self, tmp_path):
        from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer
        from kubeflow_tpu.serving.server import serve_lm_generator

        cfg = TrainConfig.from_dict(dict(
            model="transformer-test", task="lm", global_batch=8,
            seq_len=12, vocab_size=64, model_kwargs={"vocab_size": 64},
            total_steps=1, warmup_steps=1,
            checkpoint_dir=str(tmp_path), checkpoint_every=1))
        Trainer(cfg).fit(steps=1)
        m = serve_lm_generator(
            "bf16-lm", "transformer-test", prompt_len=8, max_new_tokens=3,
            vocab_size=64, checkpoint_dir=str(tmp_path),
            param_dtype="bfloat16")
        out = m.predict([{"tokens": [1, 2, 3]}])
        assert len(out) == 1 and len(out[0]) == 3

    def test_cast_params_floats_only(self):
        import jax.numpy as jnp
        import numpy as np

        from kubeflow_tpu.serving.server import cast_params

        tree = {"w": jnp.ones((4,), jnp.float32),
                "ids": jnp.arange(4, dtype=jnp.int32)}
        out = cast_params(tree, "bfloat16")
        assert out["w"].dtype == jnp.bfloat16
        assert out["ids"].dtype == jnp.int32
        np.testing.assert_allclose(np.asarray(out["w"], np.float32),
                                   np.ones(4))

    def test_mesh_sharded_cast(self):
        import jax

        from kubeflow_tpu.models.registry import get_model
        from kubeflow_tpu.serving.server import _ServingMesh

        import jax.numpy as jnp

        sm = _ServingMesh({"fsdp": 2, "model": 4}, seed=0,
                          checkpoint_dir=None, param_dtype="bfloat16")
        model = get_model("transformer-test", vocab_size=64, max_seq_len=12)
        variables = sm.get_variables(model, jnp.ones((1, 1), jnp.int32))
        leaves = jax.tree.leaves(variables)
        floats = [l for l in leaves if jnp.issubdtype(l.dtype, jnp.floating)]
        assert floats and all(l.dtype == jnp.bfloat16 for l in floats)
        # still sharded over the mesh
        assert any(any(s is not None for s in l.sharding.spec)
                   for l in floats)
