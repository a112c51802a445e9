"""Trainer smoke tests on the virtual CPU mesh: the fake-backend
equivalent of the reference's real-cluster tf-cnn E2E (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.parallel.mesh import MeshSpec
from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer


def tiny_resnet_cfg(**over):
    cfg = dict(
        model="resnet18",
        task="classification",
        global_batch=16,
        image_size=32,
        num_classes=10,
        mesh=MeshSpec(data=8),
        total_steps=4,
        warmup_steps=1,
        log_every=2,
        learning_rate=0.01,
    )
    cfg.update(over)
    return TrainConfig.from_dict(cfg)


def test_resnet_dp_training_runs(devices8, caplog):
    trainer = Trainer(tiny_resnet_cfg())
    with caplog.at_level("INFO", logger="kubeflow_tpu.trainer"):
        state, summary = trainer.fit(steps=3)
    assert summary["steps"] == 3
    assert jnp.isfinite(summary["final"]["loss"])
    assert int(state.step) == 3
    # the device is named, set-up is apart from the steady step, and a
    # CPU has no peak: no MFU in the summary, the log or the gauges
    assert summary["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert summary["first_step_s"] > 0 and summary["step_time_s"] > 0
    assert summary["mfu"] is None
    assert "mfu" not in caplog.text
    # the optimizer state is spread like the params, not left on device 0,
    # and the state keeps its layout from step to step: one compile, not
    # a second one when step 1's outputs come back laid out otherwise
    assert all(len(leaf.sharding.device_set) == 8
               for leaf in jax.tree.leaves((state.opt_state, state.step)))
    assert trainer._train_step._cache_size() == 1


def test_resnet_loss_decreases_on_fixed_batch(devices8):
    # synthetic data repeats the same batch => loss must fall
    trainer = Trainer(tiny_resnet_cfg(total_steps=8, learning_rate=0.05))
    state = trainer.init_state()
    data = trainer.data_iter()
    batch = next(data)
    losses = []
    for _ in range(8):
        state, m = trainer.train_step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_fsdp_mesh_shards_params(devices8):
    trainer = Trainer(tiny_resnet_cfg(mesh=MeshSpec(data=2, fsdp=4)))
    state = trainer.init_state()
    # at least one large parameter should actually be sharded over fsdp
    sharded = [
        p for p in jax.tree.leaves(state.params)
        if p.size >= 2**14 and not p.sharding.is_fully_replicated
    ]
    assert sharded, "expected some fsdp-sharded parameters"
    # training still steps
    state, m = trainer.train_step(state, next(trainer.data_iter()))
    assert jnp.isfinite(m["loss"])


def test_eval_step(devices8):
    trainer = Trainer(tiny_resnet_cfg())
    state = trainer.init_state()
    m = trainer.eval_step(state, next(trainer.data_iter()))
    assert jnp.isfinite(m["loss"])


def test_config_rejects_unknown_keys():
    with pytest.raises(ValueError):
        TrainConfig.from_dict({"modell": "resnet50"})


def test_resnet_space_to_depth_stem_trains(devices8):
    # The MLPerf TPU stem variant must train the same as conv7.
    trainer = Trainer(tiny_resnet_cfg(
        model_kwargs={"stem": "space_to_depth"}, total_steps=3))
    state, summary = trainer.fit(steps=3)
    assert jnp.isfinite(summary["final"]["loss"])
    assert int(state.step) == 3


def test_space_to_depth_shape():
    import numpy as np

    from kubeflow_tpu.models.resnet import space_to_depth

    x = jnp.arange(2 * 4 * 4 * 3).reshape(2, 4, 4, 3).astype(jnp.float32)
    y = space_to_depth(x, 2)
    assert y.shape == (2, 2, 2, 12)
    # block (0,0) of image 0 = pixels (0,0),(0,1),(1,0),(1,1) channels-first
    np.testing.assert_array_equal(
        np.asarray(y[0, 0, 0]),
        np.concatenate([np.asarray(x[0, 0, 0]), np.asarray(x[0, 0, 1]),
                        np.asarray(x[0, 1, 0]), np.asarray(x[0, 1, 1])]))


def test_remat_dots_policy_trains_and_matches_no_remat(devices8):
    """remat_policy=dots (keep matmul outputs, recompute elementwise)
    computes the same loss as no-remat — it's a memory/compute trade,
    never a numerics change."""
    import jax
    import numpy as np

    from kubeflow_tpu.parallel.mesh import MeshSpec

    def cfg(**over):
        base = dict(
            model="transformer-test", task="lm", global_batch=8,
            seq_len=32, vocab_size=128, mesh=MeshSpec(data=8),
            optimizer="adamw", learning_rate=1e-3, total_steps=2,
            warmup_steps=1, log_every=10**9,
        )
        base.update(over)
        return TrainConfig.from_dict(base)

    t_plain = Trainer(cfg())
    t_dots = Trainer(cfg(remat=True, remat_policy="dots"))
    s1 = t_plain.init_state()
    s2 = t_dots.init_state()
    batch = next(t_plain.data_iter())
    _, m1 = t_plain.train_step(s1, batch)
    _, m2 = t_dots.train_step(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    # bad policy rejected at model level too
    import pytest as _pytest

    from kubeflow_tpu.models.transformer import TransformerConfig, _remat_policy
    with _pytest.raises(ValueError, match="remat_policy"):
        _remat_policy(TransformerConfig(remat_policy="bogus"))


def test_remat_slim_and_mlp_policies_match_no_remat(devices8):
    """The round-4 policies — slim (whitelist of named anchors) and the
    width-predicate mlp — are memory/compute trades only: same loss as
    no-remat on the same batch, and they must train under chunked CE
    (the production loss) too."""
    import numpy as np

    from kubeflow_tpu.parallel.mesh import MeshSpec

    def cfg(**over):
        base = dict(
            model="transformer-test", task="lm", global_batch=8,
            seq_len=32, vocab_size=128, mesh=MeshSpec(data=8),
            optimizer="adamw", learning_rate=1e-3, total_steps=2,
            warmup_steps=1, log_every=10**9, xent_chunks=4,
        )
        base.update(over)
        return TrainConfig.from_dict(base)

    t_plain = Trainer(cfg())
    batch = next(t_plain.data_iter())
    _, m_plain = t_plain.train_step(t_plain.init_state(), batch)
    for policy in ("slim", "mlp"):
        t_r = Trainer(cfg(remat=True, remat_policy=policy))
        _, m_r = t_r.train_step(t_r.init_state(), batch)
        np.testing.assert_allclose(
            float(m_plain["loss"]), float(m_r["loss"]), rtol=1e-5,
            err_msg=f"policy {policy}")


def test_periodic_eval_in_fit():
    """eval_every runs held-out eval during fit (train_and_evaluate
    parity): metrics land in the summary with LM perplexity = exp(loss),
    and the eval gauges reach the Prometheus registry."""
    import math

    from kubeflow_tpu.runtime import metrics as rt_metrics
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

    cfg = TrainConfig.from_dict(dict(
        model="transformer-test",
        task="lm",
        global_batch=8,
        seq_len=16,
        vocab_size=128,
        mesh=MeshSpec(data=8),
        optimizer="adafactor",
        learning_rate=1e-3,
        total_steps=4,
        warmup_steps=1,
        log_every=10**9,
        eval_every=2,
        eval_steps=2,
    ))
    _, summary = Trainer(cfg).fit()
    ev = summary["eval"]
    assert set(ev) >= {"loss", "accuracy", "perplexity"}
    assert math.isclose(ev["perplexity"], math.exp(ev["loss"]), rel_tol=1e-6)
    scrape = rt_metrics.REGISTRY.render()
    assert "jaxrt_eval_loss" in scrape and "jaxrt_eval_perplexity" in scrape


def test_flash_blocks_plumb_from_config(monkeypatch):
    """TrainConfig.flash_block_q/k must reach the flash kernel call —
    the measured-operating-point reproducibility guarantee (no env vars,
    no process-global state)."""
    import kubeflow_tpu.ops.flash_attention as fa
    from kubeflow_tpu.runtime.data import shard_batch
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

    seen = {}
    real = fa.flash_attention

    def spy(q, k, v, **kw):
        seen["block_q"] = kw.get("block_q")
        seen["block_k"] = kw.get("block_k")
        return real(q, k, v, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    cfg = TrainConfig.from_dict(dict(
        model="transformer-test",
        model_kwargs={"attention_impl": "flash"},
        task="lm",
        global_batch=8,
        seq_len=32,
        vocab_size=128,
        mesh=MeshSpec(data=8),
        optimizer="sgdm",
        learning_rate=1e-2,
        total_steps=1,
        warmup_steps=1,
        flash_block_q=32,
        flash_block_k=16,
    ))
    trainer = Trainer(cfg)
    state = trainer.init_state()
    batch = shard_batch(next(trainer.data_iter()),
                        next(iter(jax.tree.leaves(trainer.batch_shardings))))
    trainer.train_step(state, batch)
    assert seen == {"block_q": 32, "block_k": 16}
