"""The kernel that adds a window of the experts' results into their
tokens' rows (ops/moe_combine.py), in the Pallas interpreter on the CPU,
against XLA's scatter-add: tokens that own no row, a token tile that owns
none, rows that are nobody's and hold NaN, a run of rows that straddles
row tiles, what y held before; and the rule that says which calls take
it. The compiled kernel is tools/moe_compact_on_chip.py's to judge."""

import numpy as np
import pytest


def case(t, m, d, k, held, seed=0, empty_tiles=()):
    """m sorted pairs of t tokens of k pairs, the first `held` of them
    somebody's (in the experts' order: tokens in no order), the rest
    nobody's and NaN; no row for the tokens of `empty_tiles`."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    free = np.array([p for p in range(t * k)
                     if p // k // 128 not in empty_tiles])
    pair = rng.permutation(free)[:m]
    token = np.where(np.arange(m) < held, pair // k, t).astype(np.int32)
    rows = rng.normal(size=(m, d)).astype(np.float32)
    rows[held:] = np.nan
    gates = rng.uniform(0.01, 2.5, m).astype(np.float32)
    y = rng.normal(size=(t, d)).astype(np.float32)
    return (jnp.asarray(y), jnp.asarray(rows, jnp.bfloat16),
            jnp.asarray(token), jnp.asarray(gates))


@pytest.mark.parametrize("t,m,d,k,held,empty", [
    (256, 256, 128, 4, 200, ()),
    (512, 256, 256, 8, 256, ()),          # every row somebody's
    (512, 512, 128, 2, 300, (1, 2)),      # token tiles that own no row
    (128, 512, 128, 8, 500, ()),          # one token tile, four row tiles
    (384, 128, 2048, 4, 1, ()),           # one row; two column tiles
    (256, 256, 128, 4, 0, ()),            # nobody's rows alone
], ids=["plain", "full", "empty-tiles", "one-token-tile", "one-row",
        "no-row"])
def test_kernel_adds_what_the_scatter_add_adds(t, m, d, k, held, empty):
    from kubeflow_tpu.ops import moe_combine

    args = case(t, m, d, k, held, empty_tiles=empty)
    want = np.asarray(moe_combine.scatter_add(*args))
    got = np.asarray(moe_combine.combine(*args))
    assert np.isfinite(got).all()
    # float32 sums of the same addends, in another order
    assert np.abs(got - want).max() <= 1e-5 * max(np.abs(want).max(), 1)
    if not held:
        assert (got == np.asarray(args[0])).all()


def test_the_gates_three_parts_are_the_gate():
    """The split that makes each product exact: three bfloat16 values
    that add up to the float32 gate, bit for bit."""
    import jax.numpy as jnp

    g = jnp.asarray(np.random.default_rng(0).uniform(1e-4, 3, 4096),
                    jnp.float32)
    g1 = g.astype(jnp.bfloat16).astype(jnp.float32)
    g2 = (g - g1).astype(jnp.bfloat16).astype(jnp.float32)
    g3 = g - g1 - g2
    assert (g3.astype(jnp.bfloat16).astype(jnp.float32) == g3).all()
    assert ((g1 + g2) + g3 == g).all()


def test_path_rule_follows_backend_mesh_and_tiles(caplog, monkeypatch,
                                                  devices8):
    """The scatter-add off the TPU, under a mesh of several devices and
    for shapes that are not whole tiles; the kernel for the rungs of the
    two configurations that hold a share; which, and why, is logged."""
    import logging

    import jax
    from jax.sharding import Mesh

    from kubeflow_tpu.ops.moe_combine import use_kernel

    with caplog.at_level(logging.INFO, logger="kubeflow_tpu.moe_combine"):
        assert not use_kernel(4096, 4096, 7168)                 # the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for rung in (2048, 4096, 6144, 8192):                   # ax-k1-serve
            assert use_kernel(rung, rung, 7168)
        assert use_kernel(16384, 16384, 3072)           # trinity-large-serve
        with Mesh(np.array(devices8[:2]), ("data",)):
            assert not use_kernel(4096, 4096, 7168)
        assert not use_kernel(4000, 4096, 7168)
        assert not use_kernel(4096, 4096, 7100)
    said = [r.getMessage() for r in caplog.records]
    assert all(m.startswith("gates' sum: ") for m in said)
    assert "scatter-add (default backend is 'cpu', not tpu)" in said[0]
    assert "kernel (tpu backend, 2048 rows into 2048 x 7168)" in said[1]
    assert "scatter-add (mesh of 2 devices)" in said[6]
    assert "t 4000" in said[7] and "d 7100" in said[8]


@pytest.mark.parametrize("overrun", [False, True], ids=["one-window", "three"])
def test_the_layer_takes_the_kernel_where_the_rule_says(monkeypatch, overrun):
    """`dropless_mlp` compacted, the gates' sum through the kernel
    (interpreted) and through the scatter-add: the same y, one window or
    several."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import moe, moe_combine

    t, d, f, k, e, e_all = 256, 128, 64, 4, 4, 32
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(keys[0], (t, d), jnp.float32)
    gate_vals, gate_idx = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(keys[1], (t, e_all))), k)
    if overrun:
        gate_idx = gate_idx % (2 * e)       # half of the pairs held
    w = [jax.random.normal(key, shape, jnp.float32) * 0.1
         for key, shape in zip(keys[2:], ((e, d, f), (e, d, f), (e, f, d)))]
    cfg = SimpleNamespace(dtype=jnp.bfloat16)
    got = {}
    for kernel in (False, True):
        monkeypatch.setattr(moe_combine, "use_kernel", lambda *a: kernel)
        y, counts = moe.dropless_mlp(cfg, x, gate_vals, gate_idx, *w, None,
                                     False, 0, 128)
        got[kernel] = np.asarray(y, np.float32)
    assert int(counts.sum()) > (256 if overrun else 0)
    assert np.abs(got[False]).max() > 0.05
    assert np.abs(got[True] - got[False]).max() \
        <= 2.0 ** -8 * np.abs(got[False]).max()
