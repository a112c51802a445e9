"""Analytic FLOPs accounting (the MFU meter's numerator).

Round-1 postmortem: the meter fed the literature "4.1 GFLOPs" resnet50
number into a peak that counts multiply and add separately — but that
number is MACs (fvcore convention), silently halving every reported MFU.
These tests pin the convention: model FLOPs = 2*MACs, cross-checked
against XLA's own HLO cost analysis.
"""

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.resnet import RESNET50_FWD_FLOPS_224, fwd_flops


class TestResNetAnalytic:
    def test_resnet50_is_twice_the_mac_count(self):
        # 4.09 GMACs x 2 = ~8.2e9; the old constant was the MAC count
        got = fwd_flops("resnet50")
        assert got == pytest.approx(2 * RESNET50_FWD_FLOPS_224, rel=0.02)

    def test_variants_scale_sensibly(self):
        r18 = fwd_flops("resnet18")
        r50 = fwd_flops("resnet50")
        r101 = fwd_flops("resnet101")
        assert r18 < r50 < r101
        # literature MACs: r18=1.82G, r101=7.8G (x2 for FLOPs)
        assert r18 == pytest.approx(2 * 1.82e9, rel=0.03)
        assert r101 == pytest.approx(2 * 7.8e9, rel=0.03)

    def test_image_size_scaling(self):
        # conv FLOPs scale ~quadratically in image size
        ratio = fwd_flops("resnet50", image_size=448) / fwd_flops("resnet50")
        assert ratio == pytest.approx(4.0, rel=0.05)

    def test_s2d_stem_costs_slightly_more(self):
        # 4x4x12 contraction vs 7x7x3: more MACs for a better MXU shape
        assert fwd_flops("resnet50", stem="space_to_depth") > fwd_flops("resnet50")

    def test_matches_xla_cost_analysis(self):
        """XLA's HLO flop count for a fwd pass agrees within 15% (XLA
        also counts BN/pool elementwise, so it sits slightly above)."""
        from kubeflow_tpu.models.registry import get_model

        model = get_model("resnet50", num_classes=1000)
        x = jnp.zeros((2, 224, 224, 3), jnp.float32)
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, train=False))
        lowered = jax.jit(
            lambda v, x: model.apply(v, x, train=False)).lower(variables, x)
        ca = lowered.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        xla = float(ca.get("flops", 0.0))
        if xla <= 0:
            pytest.skip("cost analysis unavailable on this backend")
        analytic = 2 * fwd_flops("resnet50")
        assert xla == pytest.approx(analytic, rel=0.15)

    def test_unknown_variant_rejected(self):
        with pytest.raises(ValueError):
            fwd_flops("resnet34")


class TestTransformerAnalytic:
    def test_attention_term_added_with_seq_len(self):
        from kubeflow_tpu.models.registry import get_model

        m = get_model("gpt-125m")
        base = m.flops_per_token()
        with_attn = m.flops_per_token(seq_len=2048)
        cfg = m.cfg
        # a causal query at position p sees p + 1 keys: 2049 / 2 on average
        want_attn = 12.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * 2049 / 2
        assert with_attn - base == pytest.approx(want_attn)

    def test_embedding_is_a_lookup_and_the_window_cuts_the_keys(self):
        from kubeflow_tpu.models.registry import get_model

        m = get_model("gpt-125m")
        cfg = m.cfg
        layer = cfg.d_model * cfg.head_dim * (2 * cfg.n_heads + 2 * cfg.n_kv_heads) \
            + 3 * cfg.d_model * cfg.d_ff
        assert m.flops_per_token() == pytest.approx(
            6.0 * (cfg.n_layers * layer + cfg.vocab_size * cfg.d_model))
        win = get_model("gpt-125m", attention_window=512)
        full = m.flops_per_token(seq_len=2048) - m.flops_per_token()
        cut = win.flops_per_token(seq_len=2048) - win.flops_per_token()
        # 512 queries see 1..512 keys, the other 1536 see 512 each
        assert cut / full == pytest.approx(
            (512 * 513 / 2 + 1536 * 512) / (2048 * 2049 / 2))
        # a window no shorter than the sequence changes nothing
        assert win.flops_per_token(seq_len=512) == m.flops_per_token(seq_len=512)

    def test_agrees_with_the_benchmarks_count_at_the_train_cell(self):
        """benchmarks/lib/opcount.py states what the work needs; the
        trainer's own MFU gauge must not disagree with model.mfu.train.
        The widths of mistral-7b-train: 8 layers, 8,192 tokens, window
        4,096."""
        from benchmarks.lib import opcount, spec
        from kubeflow_tpu.models.registry import get_model

        d = spec.cell("ft-8k-1chip").dims
        assert (d.layers, d.window) == (8, 4096)
        m = get_model("transformer-test", max_seq_len=8192, **d.model_kwargs())
        assert m.flops_per_token(seq_len=8192) == pytest.approx(
            opcount.train_flops_per_token(d, 8192), rel=0.01)

    def test_trainer_uses_seq_aware_flops(self):
        from kubeflow_tpu.parallel.mesh import MeshSpec
        from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

        from kubeflow_tpu.parallel.mesh import build_mesh

        cfg = TrainConfig.from_dict(dict(
            model="transformer-test", task="lm", global_batch=4, seq_len=32,
            vocab_size=256, mesh=MeshSpec(data=1), total_steps=1))
        tr = Trainer(cfg, mesh=build_mesh(cfg.mesh, devices=jax.devices()[:1]))
        per_token = tr.model.flops_per_token(seq_len=32)
        assert tr.flops_per_step() == pytest.approx(per_token * 4 * 32)

    def test_bert_flops_per_token(self):
        from kubeflow_tpu.models.registry import get_model

        m = get_model("bert-test")
        base = m.flops_per_token()
        with_attn = m.flops_per_token(seq_len=128)
        assert with_attn > base > 0


class TestMoEFlops:
    def test_moe_layers_count_topk_experts(self):
        from kubeflow_tpu.models.registry import get_model

        dense = get_model("transformer-test")
        moe = get_model("transformer-test", moe_every=2, n_experts=4,
                        expert_top_k=2)
        # half the layers run top_k=2 expert MLPs -> more FLOPs/token
        assert moe.flops_per_token() > dense.flops_per_token()
        cfg = moe.cfg
        mlp = 3 * cfg.d_model * cfg.d_ff
        extra = 6.0 * (cfg.n_layers // 2) * (
            (cfg.expert_top_k - 1) * mlp + cfg.d_model * cfg.n_experts)
        assert moe.flops_per_token() - dense.flops_per_token() == \
            pytest.approx(extra)
