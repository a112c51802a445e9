"""The paged decode kernel (ops/paged_attention.py) against the gather
path of `Attention._decode_paged` on the same pool.

Here (the conftest forces the CPU) the kernel runs in the Pallas
interpreter. On a TPU the same cases judge the kernel Mosaic compiles, at
the serving cells' own shapes where a case says so:

    chiprun -- python -m pytest --noconftest tests/test_paged_attention.py -q

Each case writes one new K/V row a slot and attends, as a decode tick
does: the reference is `_decode_paged` itself held to its gather path,
the kernel then reads the pool that call left. The two differ by the
pool dtype's rounding: bf16 keeps 8 bits of mantissa, the gather path
rounds normalised probabilities and the kernel unnormalised ones before
the product with V, and both round the output, so the bound set
beforehand is four roundings' worth of the largest output, 2**-6.
"""

import dataclasses
import functools
import json
import logging
import os

import numpy as np
import pytest

TOLERANCE = 2.0 ** -6
TRASH = 1.0e4     # the trash page and every unowned page: a leak shows


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class Case:
    pads: tuple            # left padding of each slot
    lasts: tuple           # each slot's decode position (the query's own)
    starts: tuple          # the first position each query sees, by hand
    heads: int = 8
    kv_heads: int = 2
    head_dim: int = 128
    page_size: int = 16
    max_pages: int = 12
    window: int = 0
    shared: int = 0        # leading logical pages slot 1 shares with slot 0
    cell_pool: bool = False   # on a TPU: the serving cells' 16,385 pages
    # a block model's step: `block` queries a slot that all see
    # starts..lasts, the block's own positions being lasts-block+1..lasts
    # (0 = one query a slot, at lasts)
    block: int = 0


def cell_case() -> Case:
    """The serving cells' shape: 32 slots, rows of 288 pages of 16, 32
    heads over 8 kv heads of 128, the 4,096 window, slot 7 idle. On a TPU
    left padding and positions as chat-saturated and doc-qa-paced have
    them; here shorter walks of the same rows, for the interpreter's sake."""
    rng = np.random.default_rng(27)
    pads = rng.integers(0 if on_tpu() else 3800, 4032, 32)
    lasts = rng.integers(4096, 4608, 32)
    if on_tpu():
        pads[3], lasts[3] = 0, 4300     # the window begins this one
    pads[7] = lasts[7] + 1              # idle
    starts = np.maximum(pads, lasts - 4096 + 1)
    return Case(tuple(pads), tuple(lasts), tuple(starts), heads=32,
                kv_heads=8, max_pages=288, window=4096, cell_pool=True)


CASES = {
    # pad 21 = page 1, offset 5: the first page is masked in part
    "pad-not-page-aligned": Case((21, 0), (100, 37), (21, 0)),
    # positions 35..44 lie in page 2 alone
    "start-and-last-in-one-page": Case((35,), (44,), (35,)),
    # last 150, window 64: 150 - 64 + 1 = 87 is past the pad of 20
    "window-begins-past-pad": Case((20, 90), (150, 150), (87, 90), window=64),
    # slot 1 idles as `_tick` says it: padding from past its position
    "idle-slot-beside-active": Case((0, 51, 7), (60, 50, 180), (0, 51, 7)),
    # slot 1's first three logical pages are slot 0's physical pages
    "two-slots-share-prefix-pages": Case((0, 0), (70, 55), (0, 0), shared=3),
    # the row's entries beyond last // PS are the trash page
    "trash-entries-beyond-last": Case((0,), (17,), (0,)),
    "gqa-group-4-head-128": Case((5, 0, 40), (130, 64, 47), (5, 0, 40),
                                 heads=16, kv_heads=4),
    # the other head size the kernel tiles (PAGED_HEAD_DIMS): 64 it does not
    "head-256": Case((5, 0), (130, 64), (5, 0), heads=4, kv_heads=2,
                     head_dim=256),
    "one-slot": Case((3,), (190,), (3,)),
    # blocks of 4 queries: across a page's edge (14..17), behind padding
    # that begins inside the block's page (pad 33, block 33..36), an idle
    # slot, and at the block-diffusion cell's heads (32 over 4 kv heads)
    "block-of-4-across-a-page-edge": Case((2, 5), (17, 100), (2, 5), block=4),
    "block-of-4-first-after-the-padding": Case(
        (33, 0, 91), (36, 63, 90), (33, 0, 91), block=4),
    "block-of-4-cell-heads": Case((7, 130, 0), (142, 161, 3), (7, 130, 0),
                                  heads=32, kv_heads=4, block=4),
    "thirty-two-slots-cell-shape": cell_case,    # sized by the backend
}


def build(case: Case, seed: int):
    """Pool, table, query and the new K/V rows of one decode tick."""
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig

    rng = np.random.default_rng(seed)
    b, ps = len(case.lasts), case.page_size
    # a slot owns the pages that hold pad..last; slot 1 may borrow the
    # first `shared` of slot 0's. Every other entry is the trash page.
    owned = [range(min(p, e) // ps, e // ps + 1) if p <= e else range(0)
             for p, e in zip(case.pads, case.lasts)]
    need = sum(len(r) for r in owned)
    pages = 16385 if case.cell_pool and on_tpu() else need + 1
    order = iter(rng.permutation(np.arange(1, pages))[:need])
    table = np.zeros((b, case.max_pages), np.int32)
    for s, r in enumerate(owned):
        for j in r:
            table[s, j] = next(order)
    table[1:2, :case.shared] = table[0, :case.shared]
    shape = (pages, ps, case.kv_heads, case.head_dim)
    live = np.unique(table[table > 0])
    pools = []
    for _ in range(2):
        pool = np.full(shape, TRASH, np.float32)
        pool[live] = rng.normal(size=(len(live),) + shape[1:])
        pools.append(jnp.asarray(pool, jnp.bfloat16))
    q, k, v = (jnp.asarray(rng.normal(size=(b, case.block or 1, h,
                                            case.head_dim)), jnp.bfloat16)
               for h in (case.heads, case.kv_heads, case.kv_heads))
    cfg = TransformerConfig(
        vocab_size=8, d_model=case.heads * case.head_dim, n_layers=1,
        n_heads=case.heads, n_kv_heads=case.kv_heads,
        head_dim=case.head_dim, d_ff=8, max_seq_len=case.max_pages * ps,
        attention_window=case.window, kv_pages=pages, kv_page_size=ps,
        gen_block=case.block)
    return cfg, pools, jnp.asarray(table), q, k, v


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_gather_path(name, monkeypatch):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import Attention
    from kubeflow_tpu.ops import paged_attention

    case = CASES[name]() if callable(CASES[name]) else CASES[name]
    cfg, (kp, vp), table, q, k, v = build(case, seed=len(name))
    pads = jnp.asarray(case.pads, jnp.int32)
    lasts = jnp.asarray(case.lasts, jnp.int32)
    starts = jnp.asarray(case.starts, jnp.int32)

    # the reference: the module's own gather path, on any backend (the
    # test steers the rule; the program has no switch)
    monkeypatch.setattr(paged_attention, "use_kernel", lambda *a, **kw: False)
    class DecodeStep(Attention):    # `_decode_paged` without the projections
        @nn.compact
        def __call__(self, *xs):
            return self._decode_paged(*xs)

    # the chunk's first position: the query's own, or its block's first
    first = lasts - max(case.block - 1, 0)
    want, mut = jax.jit(lambda *xs: DecodeStep(cfg).apply(
        {"cache": {"key_pages": kp, "value_pages": vp}}, *xs,
        bool(case.block), mutable=["cache"]))(q, k, v, first, pads, table)
    kp1, vp1 = mut["cache"]["key_pages"], mut["cache"]["value_pages"]
    got = jax.jit(paged_attention.paged_decode_attention)(
        q if case.block else q[:, 0], kp1, vp1, table, starts, lasts)

    got = np.asarray(got, np.float32)
    want = np.asarray(want if case.block else want[:, 0], np.float32)
    assert np.isfinite(got).all()
    idle = np.asarray(case.starts) > np.asarray(case.lasts)
    assert not got[idle].any(), "an idle slot gives zeros"
    assert idle.sum() < len(idle)
    worst = np.abs(got - want)[~idle].max() / np.abs(want[~idle]).max()
    assert worst <= TOLERANCE, worst
    # the trash value would show as thousands
    assert np.abs(got).max() < 10.0
    out = os.environ.get("PAGED_ATTENTION_REPORT")
    if out:   # the on-chip run's largest differences, for CHANGES.md
        with open(out, "a") as f:
            f.write(json.dumps({"case": name, "worst": float(worst),
                                "backend": jax.default_backend()}) + "\n")


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described (not attached) v5e host: the TPU's compiler
    is installed here, so Mosaic judges the kernel without a chip."""
    if on_tpu():
        pytest.skip("a TPU is attached: the cases above ran the compiled kernel")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else it logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def test_kernel_compiles_for_a_v5e_at_the_cells_shape(v5e_chip, monkeypatch):
    """What the interpreter cannot show: Mosaic takes the page DMAs, the
    merged (position, kv head) rows and the VMEM the kernel asks for, at
    32 slots, 288-page rows and the 16,385-page pool, and the pool goes
    into the call as it lies (no copy of 537 MB in front of it)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.ops.paged_attention import paged_decode_attention

    monkeypatch.setattr(flash_attention, "INTERPRET", False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    pool = arg((16385, 16, 8, 128), jnp.bfloat16)
    compiled = jax.jit(paged_decode_attention).lower(
        arg((32, 32, 128), jnp.bfloat16), pool, pool,
        arg((32, 288), jnp.int32), arg((32,), jnp.int32),
        arg((32,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_block_kernel_compiles_for_a_v5e_at_the_cells_shape(
        v5e_chip, monkeypatch):
    """The block-diffusion cell's step: 64 slots, 4 queries a slot of 32
    heads over 4 kv heads of 128 (128 query rows against each streamed
    block of pages), rows of 129 pages, the 8,193-page pool."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.ops.paged_attention import (BLOCK_KERNEL_NAME,
                                                  paged_decode_attention)

    monkeypatch.setattr(flash_attention, "INTERPRET", False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    pool = arg((8193, 16, 4, 128), jnp.bfloat16)
    compiled = jax.jit(paged_decode_attention).lower(
        arg((64, 4, 32, 128), jnp.bfloat16), pool, pool,
        arg((64, 129), jnp.int32), arg((64,), jnp.int32),
        arg((64,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the name the benchmark finds the kernel by in the device trace
    assert f"%{BLOCK_KERNEL_NAME}" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_latent_kernel_compiles_for_a_v5e_at_the_cells_shape(
        v5e_chip, monkeypatch):
    """`latent-saturated`'s tick: 64 slots, 64 heads' absorbed queries of
    640, rows of 560 pages, the 35,841-page latent pool, which goes into
    the call as it lies (no copy of 734 MB in front of it); the custom
    call carries the name the benchmark finds it by."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.ops.paged_latent_attention import (
        KERNEL_NAME, paged_latent_attention)

    monkeypatch.setattr(flash_attention, "INTERPRET", False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    compiled = jax.jit(functools.partial(
        paged_latent_attention, scale=0.13, rank=512)).lower(
        arg((64, 64, 640), jnp.bfloat16), arg((35841, 16, 640), jnp.bfloat16),
        arg((64, 560), jnp.int32), arg((64,), jnp.int32),
        arg((64,), jnp.int32)).compile()
    assert f"%{KERNEL_NAME}" in compiled.as_text()
    assert " = bf16[64,64,512]" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("rung", [2048, 8192])
def test_flash_forward_compiles_for_a_v5e_at_keys_192_and_values_128(
        v5e_chip, monkeypatch, rung):
    """A latent layer's rung in the up-projected form: Mosaic takes the
    forward kernel with keys of 192 and values of 128 as they are, with
    the padding's segment ids, through the nested jit whose name the
    kernels carry in the trace."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.ops.attention import local_attention

    monkeypatch.setattr(flash_attention, "INTERPRET", False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    qk = arg((1, rung, 64, 192), jnp.bfloat16)
    text = jax.jit(functools.partial(
        local_attention, causal=True, impl="flash", scale=0.13)).lower(
        qk, qk, arg((1, rung, 64, 128), jnp.bfloat16),
        segment_ids=arg((1, rung), jnp.int32)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%local_attention" in calls[0]
    assert f"bf16[64,{rung},128]" in calls[0]


@pytest.mark.parametrize("rows, k, n, groups", [
    (2048, 2048, 768, 128), (2048, 768, 2048, 128),
    (8192, 2048, 768, 128), (8192, 768, 2048, 128),
    # the widest experts the rule can give it: the zoo's gpt-moe-8e at a
    # served batch's few rows (8 MB an expert, two in VMEM)
    (256, 1024, 4096, 8), (256, 4096, 1024, 8),
    # a chip's share of Trinity-Large's experts (32 of 3,072 x 3,072: one
    # matrix is 18.9 MB, two of them in VMEM): a tick of 32 slots x 4 and
    # the prefill's rung of 4,096, the longest the rule gives the kernel
    (128, 3072, 3072, 32), (16384, 3072, 3072, 32),
    # a chip's share of A.X-K1's experts (12 of 7,168 x 2,048: 29.4 MB a
    # matrix, two of them in VMEM): a tick of 64 slots x 8
    (512, 7168, 2048, 12), (512, 2048, 7168, 12)],
    ids=["pass-gate-up", "pass-down", "top-rung-gate-up", "top-rung-down",
         "gpt-moe-8e-gate-up", "gpt-moe-8e-down", "share-of-32-tick",
         "share-of-32-rung-4096", "share-of-12-tick-gate-up",
         "share-of-12-tick-down"])
def test_grouped_matmul_compiles_for_a_v5e_at_the_cells_shape(
        v5e_chip, monkeypatch, rows, k, n, groups):
    """The experts' streamed grouped matmul (ops/grouped_matmul.py; its
    interpreter's tests are tests/test_grouped_matmul.py, its compile is
    here because one file loads the TPU's compiler): 128 experts of
    2,048 x 768, the rows of a pass and of the prefill's longest rung.
    Mosaic takes two whole experts in VMEM, and the custom call keeps the
    name, hyphens and all, by which the benchmark's
    `moe.expert_roofline.blockdiff` finds a pass's grouped matmuls: an
    array result `bf16[rows, n]`, no tuple."""
    import re

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.ops.grouped_matmul import KERNEL_NAME, grouped_matmul
    from kubeflow_tpu.ops.moe import EXPERT_MATMUL_TRACE_NAME

    monkeypatch.setattr(flash_attention, "INTERPRET", False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    text = jax.jit(grouped_matmul).lower(
        arg((rows, k), jnp.bfloat16), arg((groups, k, n), jnp.bfloat16),
        arg((groups,), jnp.int32)).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and calls[0].startswith(f"%{KERNEL_NAME}")
    # the reader's own expression (benchmarks/metrics/blockdiff.py)
    assert re.search(rf"^%?{EXPERT_MATMUL_TRACE_NAME}[\w.\-]* = \w+\[{rows},",
                     calls[0]), calls[0]
    assert f" = bf16[{rows},{n}]" in calls[0]


@pytest.mark.parametrize("t,m,d", [
    (8192, 8192, 7168), (2048, 2048, 7168), (16384, 16384, 3072)],
    ids=["share-of-12-top-rung", "share-of-12-first-rung",
         "share-of-32-top-rung"])
def test_gates_sum_kernel_compiles_for_a_v5e_at_the_cells_shape(
        v5e_chip, monkeypatch, t, m, d):
    """The kernel that adds a compacted rung's results into their tokens'
    rows (ops/moe_combine.py; its interpreter's tests are
    tests/test_moe_combine.py): Mosaic takes it at the rungs of the two
    configurations that hold a share, y is updated in place, and its
    name is no grouped matmul's (`moe.expert_roofline.*` reads those)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.ops.moe import EXPERT_MATMUL_TRACE_NAME
    from kubeflow_tpu.ops.moe_combine import KERNEL_NAME, combine

    monkeypatch.setattr(flash_attention, "INTERPRET", False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    text = jax.jit(combine, donate_argnums=(0,)).lower(
        arg((t, d), jnp.float32), arg((m, d), jnp.bfloat16),
        arg((m,), jnp.int32), arg((m,), jnp.float32)).compile().as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and calls[0].startswith(f"%{KERNEL_NAME}")
    assert not KERNEL_NAME.startswith(EXPERT_MATMUL_TRACE_NAME)
    assert f" = f32[{t},{d}]" in calls[0]
    assert "output_to_operand_aliasing" in calls[0]


def test_path_rule_follows_backend_and_chunk_length(caplog):
    """The gather path off the TPU and for chunks, the kernel for one
    query a slot on a TPU; which, and why, is logged."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops.paged_attention import use_kernel

    pool = (64, 16, 8, 128)
    with caplog.at_level(logging.INFO, logger="kubeflow_tpu.paged_attention"):
        one = use_kernel(1, pool, jnp.bfloat16)
        chunk = use_kernel(4096, pool, jnp.bfloat16)
        narrow = use_kernel(1, (64, 16, 8, 64), jnp.bfloat16)
        odd = use_kernel(1, (64, 16, 1, 128), jnp.bfloat16)
        block = use_kernel(4, (64, 16, 4, 128), jnp.bfloat16, one_range=True)
    assert one == on_tpu()
    assert not chunk and not narrow and not odd
    assert block == on_tpu()    # a chunk whose rows see one range
    said = [r.getMessage() for r in caplog.records]
    assert ("a block of 4 queries" if on_tpu() else "not tpu") in said.pop()
    assert len(said) == 4 and all("paged attention ->" in m for m in said)
    assert ("-> kernel" if on_tpu() else "not tpu") in said[0]
    assert "-> gather (a chunk of 4096 queries a slot)" in said[1]
    if on_tpu():
        assert "head_dim 64" in said[2] and "1 kv heads" in said[3]
