"""The streamed grouped matmul (ops/grouped_matmul.py) against
`jax.lax.ragged_dot` on the rows that belong to a group.

Here (the conftest forces the CPU) the kernel runs in the Pallas
interpreter; the kernel Mosaic compiles is judged on the chip by
`tools/grouped_matmul_on_chip.py`, and compiled for a described v5e at
the cell's shape beside the paged kernels' compiles
(tests/test_paged_attention.py: one file loads the TPU's compiler).

Both sum a row's products in float32 and round once to the operands'
dtype; the orders of the sums differ, so bfloat16 results differ by a
rounding of the last bit, 2**-8 of the value, and the bound set
beforehand is two of them on the largest reference value. In float32
the two agree to the sums' last bits.
"""

import logging

import numpy as np
import pytest

# [k, n]: small stand-ins of the cell's [2048, 768] (gate, up) and
# [768, 2048] (down), lanes full
SHAPES = {"gate-up-shaped": (384, 128), "down-shaped": (128, 384)}


def cell_sizes():
    """128 groups over 2,048 rows as a pass of the block-diffusion cell
    has them: 16 in the mean, the fullest 38 (the first seed that gives
    that)."""
    for seed in range(1000):
        rng = np.random.default_rng(seed)
        sizes = rng.multinomial(2048, rng.dirichlet(np.full(128, 3.0)))
        if sizes.max() == 38:
            return tuple(int(s) for s in sizes)
    raise AssertionError("no seed under 1000 gives a fullest group of 38")


# (rows, group sizes): the sizes' sum may stay under the rows
GROUPS = {
    "all-equal": (512, (32,) * 16),
    "the-cells-128-groups-fullest-38": (2048, cell_sizes()),
    "empty-groups": (384, (0, 40, 0, 0, 90, 0, 254, 0)),
    "one-group-with-every-row": (384, (0, 0, 384, 0)),
    # 100 | 150 | 5 | 129: every group lies across a tile's edge but one
    "groups-straddle-row-tiles": (384, (100, 150, 5, 129)),
    # 170 rows live of 512: tile 1 is live in part, tiles 2 and 3 not at all
    "dead-rows-behind-the-last-group": (512, (60, 0, 110, 0)),
    "no-row-at-all": (256, (0, 0, 0)),
    "rows-no-multiple-of-the-tile": (200, (70, 0, 95)),
}


def operands(rows, sizes, k, n, dtype, seed=0):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(keys[0], (rows, k), jnp.float32).astype(dtype)
    rhs = (jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32)
           * k ** -0.5).astype(dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), int(sum(sizes))


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("case", list(GROUPS))
def test_kernel_matches_ragged_dot_on_the_groups_rows(case, shape):
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.grouped_matmul import grouped_matmul

    rows, sizes = GROUPS[case]
    lhs, rhs, counts, used = operands(rows, sizes, *SHAPES[shape],
                                      jnp.bfloat16)
    got = grouped_matmul(lhs, rhs, counts)
    want = jax.lax.ragged_dot(lhs, rhs, counts)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (np.asarray(a, np.float32)[:used] for a in (got, want))
    assert np.isfinite(got).all()
    if used:
        assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("case", [
    "the-cells-128-groups-fullest-38", "empty-groups",
    "groups-straddle-row-tiles", "dead-rows-behind-the-last-group"])
def test_gradient_is_the_references(case, shape):
    """The kernel's path is differentiable: a `custom_vjp` whose backward
    is `jax.vjp` of `ragged_dot`, so a mixture model trains on one device
    whatever the rule chose. float32, so that only the forward's last
    bits (which the cotangent here does not see: it is fixed) could
    differ."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.grouped_matmul import grouped_matmul

    rows, sizes = GROUPS[case]
    lhs, rhs, counts, used = operands(rows, sizes, *SHAPES[shape],
                                      jnp.float32, seed=1)
    weight = jax.random.normal(jax.random.PRNGKey(9),
                               (rows, SHAPES[shape][1]), jnp.float32)
    # dead rows are nobody's: the model masks them (`dropless_mlp`)
    weight = weight * (jnp.arange(rows) < used)[:, None]

    def loss(fn):
        return lambda a, b: jnp.sum(
            jnp.where(weight != 0, fn(a, b, counts), 0.0) * weight)

    got = jax.grad(loss(grouped_matmul), (0, 1))(lhs, rhs)
    want = jax.grad(loss(jax.lax.ragged_dot), (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert float(jnp.abs(g - w).max()) <= 1e-5 * float(jnp.abs(w).max())
    # and under jit, value and gradient in one program
    value, _ = jax.jit(jax.value_and_grad(loss(grouped_matmul), (0, 1)))(
        lhs, rhs)
    assert abs(float(value) - float(loss(jax.lax.ragged_dot)(lhs, rhs))) \
        <= 1e-4 * max(1.0, abs(float(value)))


def test_an_expert_no_pair_visits_is_never_read():
    """A group of no rows has no visit: its matrix may hold anything (a
    NaN read into a product would show in the rows of the tile it shared)
    and the steps behind the last visit name the last visited group."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops.grouped_matmul import _visits, grouped_matmul

    rows, sizes = GROUPS["empty-groups"]
    lhs, rhs, counts, used = operands(rows, sizes, 128, 128, jnp.bfloat16)
    empty = np.flatnonzero(np.asarray(sizes) == 0)
    poisoned = rhs.at[empty].set(jnp.nan)
    got = grouped_matmul(lhs, poisoned, counts)
    want = grouped_matmul(lhs, rhs, counts)
    assert np.isfinite(np.asarray(got, np.float32)[:used]).all()
    assert (np.asarray(got)[:used] == np.asarray(want)[:used]).all()
    group, tile, bounds, visits = (np.asarray(a)
                                   for a in _visits(counts, rows))
    assert not set(group) & set(empty)
    # 40 | 90 | 254 rows over three tiles: 1 + 2 + 2 visits, in order
    assert int(visits[0]) == 5
    assert list(zip(group[:5], tile[:5])) == [
        (1, 0), (4, 0), (4, 1), (6, 1), (6, 2)]
    assert (group[5:] == 6).all() and (tile[5:] == 2).all()
    assert list(bounds) == [0, 0, 40, 40, 40, 130, 130, 384, 384]


def toy_mixture(dtype):
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(d_model=128, d_ff=256, moe_d_ff=128, n_experts=8,
                            expert_top_k=2, dtype=dtype)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    t, e, k = 96, cfg.n_experts, cfg.expert_top_k
    x = jax.random.normal(keys[0], (t, 128), jnp.float32)
    gate_vals = jax.nn.softmax(jax.random.normal(keys[1], (t, k)), axis=-1)
    # experts 0..6 only: expert 7 is never visited
    gate_idx = jax.random.randint(keys[2], (t, k), 0, e - 1)
    w_gate, w_up = (jax.random.normal(key, (e, 128, 128)) * 0.09
                    for key in keys[3:5])
    w_down = jax.random.normal(keys[5], (e, 128, 128)) * 0.09
    live = jnp.arange(t) % 7 != 3          # padding rows among the tokens
    return cfg, (x, gate_vals, gate_idx, w_gate, w_up, w_down, live)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dropless_mlp_with_the_kernel_is_dropless_mlp_without_it(dtype):
    """The same sort, the same gates, the same rounding: a toy mixture
    layer with the grouped matmuls forced to the kernel against the same
    layer on `ragged_dot`, value, counts, the padding rows' zeros, and
    the gradient to the tokens and to an expert's matrix."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.moe import dropless_mlp

    cfg, args = toy_mixture(jnp.dtype(dtype))
    y0, c0 = dropless_mlp(cfg, *args)
    y1, c1 = dropless_mlp(cfg, *args, streamed=True)
    assert (np.asarray(c0) == np.asarray(c1)).all() and int(c0[7]) == 0
    live = np.asarray(args[-1])
    y0, y1 = (np.asarray(y, np.float32) for y in (y0, y1))
    assert not y1[~live].any()
    tol = 1e-5 if dtype == "float32" else 2.0 ** -6
    assert np.abs(y1 - y0).max() <= tol * np.abs(y0).max()
    if dtype == "float32":
        def loss(streamed):
            return lambda x, w: jnp.sum(dropless_mlp(
                cfg, x, *args[1:3], w, *args[4:], streamed=streamed)[0] ** 2)

        g0 = jax.grad(loss(False), (0, 1))(args[0], args[3])
        g1 = jax.grad(loss(True), (0, 1))(args[0], args[3])
        for a, b in zip(g1, g0):
            assert float(jnp.abs(a - b).max()) \
                <= 1e-4 * float(jnp.abs(b).max())


def test_the_layer_counts_the_pairs_the_kernel_took(monkeypatch):
    """`moe_kernel_pairs` is all of `moe_pairs` where the rule gave the
    layer's grouped matmuls to the kernel and 0 where it did not; the
    rule is asked once a call, with the layer's own shapes."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.ops import grouped_matmul
    from kubeflow_tpu.ops.moe import MoEBlock

    cfg = TransformerConfig(d_model=128, d_ff=256, moe_d_ff=128, n_experts=4,
                            expert_top_k=2, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 128), jnp.float32)
    block = MoEBlock(cfg)
    params = block.init(jax.random.PRNGKey(1), x)["params"]
    asked = []

    def rule(answer):
        def use_kernel(*shape):
            asked.append(shape)
            return answer
        return use_kernel

    out = {}
    for answer in (False, True):
        monkeypatch.setattr(grouped_matmul, "use_kernel", rule(answer))
        y, mut = block.apply({"params": params}, x, mutable=["diagnostics"])
        diag = {k: int(v[0]) for k, v in mut["diagnostics"].items()
                if k.startswith("moe_") and v[0].dtype == jnp.int32}
        assert diag["moe_pairs"] == 2 * 24 * 2
        assert diag["moe_kernel_pairs"] == (96 if answer else 0)
        out[answer] = np.asarray(y)
    assert asked == [(96, 128, 128, 4, jnp.float32)] * 2
    assert np.abs(out[True] - out[False]).max() <= 1e-5


def test_path_rule_follows_backend_mesh_dtype_alignment_and_rows(
        caplog, monkeypatch, devices8):
    """`ragged_dot` off the TPU, under a mesh of several devices, for
    operands that are not bfloat16, for k or n that do not fill the
    lanes, and for many rows a group; the kernel for the cell's pass and
    its prefill rungs on a TPU; which, and why, is logged."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from kubeflow_tpu.ops.grouped_matmul import MAX_MEAN_ROWS, use_kernel

    bf16 = jnp.bfloat16
    with caplog.at_level(logging.INFO, logger="kubeflow_tpu.grouped_matmul"):
        assert not use_kernel(2048, 2048, 768, 128, bf16)      # the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert use_kernel(2048, 2048, 768, 128, bf16)          # a pass
        assert use_kernel(2048, 768, 2048, 128, bf16)          # its down
        for rung in (256, 512, 768, 1024):                     # the prefill's
            assert use_kernel(rung * 8, 2048, 768, 128, bf16)
        with Mesh(np.array(devices8[:2]), ("data",)):
            assert not use_kernel(2048, 2048, 768, 128, bf16)
        with Mesh(np.array(devices8[:1]), ("data",)):
            assert use_kernel(2048, 2048, 768, 128, bf16)      # a mesh of one
        assert not use_kernel(2048, 2048, 768, 128, jnp.float32)
        assert not use_kernel(2048, 2048, 800, 128, bf16)
        assert not use_kernel(2048, 96, 768, 128, bf16)
        # the zoo's gpt-moe-8e: 8 experts, thousands of rows a group
        assert not use_kernel(8 * 2048 * 2, 1024, 4096, 8, bf16)
        assert use_kernel(MAX_MEAN_ROWS * 8, 1024, 4096, 8, bf16)
        assert not use_kernel(MAX_MEAN_ROWS * 8 + 1, 1024, 4096, 8, bf16)
    said = [r.getMessage() for r in caplog.records]
    assert all(m.startswith("grouped matmul: ") for m in said)
    assert "ragged_dot (default backend is 'cpu', not tpu)" in said[0]
    assert "kernel (tpu backend, bfloat16, 16 rows a group" in said[1]
    assert "ragged_dot (mesh of 2 devices)" in said[7]
    assert "kernel (" in said[8]
    assert "ragged_dot (float32 operands)" in said[9]
    assert "n 800 not multiples of 128" in said[10]
    assert "k 96" in said[11]
    assert "4096 rows a group in the mean, over" in said[12]
    assert len(said) == 15
