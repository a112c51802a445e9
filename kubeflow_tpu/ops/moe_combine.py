"""Pallas TPU kernel that adds a window of the experts' results into
their tokens' rows: the gates' weighted sum of a mixture layer that works
on its held pairs alone (ops/moe.py:`_compacted`).

`rows [m, d]` are the results of m sorted pairs, `token [m]` the token
each belongs to (`t` for a position that is no pair's), `gates [m]` its
gate: `y[token[i]] += gates[i] * rows[i]` in float32. XLA's scatter-add
does that a row at a time, 0.9-1.1 us a row of 7,168 (my chip run,
PR 36: 3.87 ms for 4,096 rows, 9.37 for 8,192, as long as the un-sort of
eight times the rows that it replaced).

How it walks. The rows are put in their tokens' order (one gather of m
rows), so a tile of 128 tokens owns a run of consecutive rows. A *visit*
is one (token tile, row tile) pair that share a row, or a token tile
that owns no row with any row tile: in the tokens' order, at most token
tiles + row tiles of them. The grid is one step a visit and a column
tile. A step builds the [128 tokens, 128 rows] matrix that holds a row's
gate where the row is the token's and 0 elsewhere, and multiplies the
row tile by it on the MXU; a token tile's block of y stays in VMEM from
its first visit, which reads what y held, to its last. The gate is split
into three bfloat16 parts that add up to it exactly, so each product is
exact in float32 and the sum is the float32 one: what the scatter-add
gives, in another order of addition.

`use_kernel` says from backend, mesh and shape which calls take it; the
scatter-add is its reference and every other call's path. Off the TPU the
kernel runs in Pallas interpret mode, for its own tests only.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.flash_attention import interpret_mode
from kubeflow_tpu.parallel.mesh import current_mesh

log = logging.getLogger("kubeflow_tpu.moe_combine")

TILE = 128          # tokens to a tile, and rows: the MXU's own
COLUMNS = 1024      # of d to a step where d is a multiple of it
# The custom call's name in the device trace. It does not start with
# ops/moe.py's EXPERT_MATMUL_TRACE_NAME: it is no grouped matmul.
KERNEL_NAME = "gate-combine"


def use_kernel(t: int, m: int, d: int) -> bool:
    """Whether `combine` over t tokens, m rows and d columns runs this
    kernel or XLA's scatter-add, logged with the reason: the kernel on a
    TPU backend with everything on one device and whole tiles."""
    backend = jax.default_backend()
    mesh = current_mesh()
    if backend != "tpu":
        choice, why = "scatter-add", f"default backend is {backend!r}, not tpu"
    elif mesh is not None and mesh.size > 1:
        choice, why = "scatter-add", f"mesh of {mesh.size} devices"
    elif t % TILE or m % TILE or d % TILE:
        choice, why = "scatter-add", (
            f"t {t}, m {m}, d {d} not multiples of {TILE}")
    else:
        choice, why = "kernel", f"tpu backend, {m} rows into {t} x {d}"
    log.info("gates' sum: %s (%s)", choice, why)
    return choice == "kernel"


def scatter_add(y, rows, token, gates):
    """The reference: y [t, d] float32 + each row times its gate, added
    into its token's row (a `token` of t or more: dropped)."""
    return y.at[token].add(
        rows.astype(jnp.float32) * gates[:, None], mode="drop")


def _kernel(q_ref, r_ref, visits_ref,                 # scalar prefetch
            token_ref, gate_ref, rows_ref, y_ref, o_ref):
    i = pl.program_id(1)

    # the steps behind the last visit repeat its blocks: nothing moves
    @pl.when(i < visits_ref[0])
    def _():
        q = q_ref[i]
        mine = token_ref[0:1, :] == q * TILE + jax.lax.broadcasted_iota(
            jnp.int32, (TILE, TILE), 0)               # [tokens, rows]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for part in range(3):
            weights = jnp.where(mine, gate_ref[part:part + 1, :], 0.0)
            acc += jnp.dot(weights.astype(jnp.bfloat16), rows_ref[...],
                           preferred_element_type=jnp.float32)
        first = (i == 0) | (q_ref[jnp.maximum(i - 1, 0)] != q)
        o_ref[...] = jnp.where(first, y_ref[...], o_ref[...]) + acc


def _visits(token, t: int):
    """The walk's metadata over rows sorted by token: for each of the
    grid's steps its token tile and its row tile, and how many steps are
    visits. A token tile that owns rows visits the row tiles they lie
    in, one that owns none visits one (and adds nothing)."""
    tiles, row_tiles = t // TILE, token.shape[0] // TILE
    edges = jnp.searchsorted(token, jnp.arange(tiles + 1) * TILE)
    starts, ends = edges[:-1], edges[1:]
    first = jnp.minimum(starts // TILE, row_tiles - 1)
    spans = jnp.where(ends > starts, (ends - 1) // TILE - first + 1, 1)
    upto = jnp.cumsum(spans)
    visits = upto[-1]
    step = jnp.minimum(jnp.arange(tiles + row_tiles), visits - 1)
    q = jnp.minimum(jnp.searchsorted(upto, step, side="right"), tiles - 1)
    r = first[q] + step - (upto - spans)[q]
    return (q.astype(jnp.int32), r.astype(jnp.int32),
            visits.astype(jnp.int32)[None])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _jitted(y, rows, token, gates, *, interpret: bool):
    t, d = y.shape
    m = rows.shape[0]
    order = jnp.argsort(token)                        # stable
    token, gates = token[order], gates[order]
    # a row that is nobody's holds anything, and 0 x NaN is no 0
    rows = jnp.where((token < t)[:, None], rows[order], 0)
    q, r, visits = _visits(token, t)
    # a row tile's tokens and the gates' three parts, along the lanes,
    # in blocks of whole (8, 128) tiles
    g1 = gates.astype(jnp.bfloat16).astype(jnp.float32)
    g2 = (gates - g1).astype(jnp.bfloat16).astype(jnp.float32)
    parts = jnp.stack([g1, g2, gates - g1 - g2])
    def lanes(a):       # [n, m] -> [row tiles, 8, TILE], zeros below n
        a = a.reshape(a.shape[0], m // TILE, TILE).swapaxes(0, 1)
        return jnp.pad(a, ((0, 0), (0, 8 - a.shape[1]), (0, 0)))

    cols = COLUMNS if d % COLUMNS == 0 else d
    block = 4 * TILE * cols
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // cols, q.shape[0]),
            in_specs=[
                pl.BlockSpec((None, 8, TILE), lambda j, i, q, r, v: (r[i], 0, 0)),
                pl.BlockSpec((None, 8, TILE), lambda j, i, q, r, v: (r[i], 0, 0)),
                pl.BlockSpec((TILE, cols), lambda j, i, q, r, v: (r[i], j)),
                pl.BlockSpec((TILE, cols), lambda j, i, q, r, v: (q[i], j)),
            ],
            out_specs=pl.BlockSpec(
                (TILE, cols), lambda j, i, q, r, v: (q[i], j))),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        # y is updated in place: a block is read at its token tile's
        # first visit and written after its last
        input_output_aliases={6: 0},
        # visits in the tokens' order: a token tile's block stays until
        # its last row tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(8 * block + (4 << 20), 16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * TILE * TILE * d * q.shape[0], transcendentals=0,
            bytes_accessed=2 * m * d + 8 * t * d),
        interpret=interpret,
        name=KERNEL_NAME,
    )(q, r, visits, lanes(token[None]), lanes(parts), rows, y)


def combine(y, rows, token, gates):
    """y [t, d] float32, rows [m, d], token [m] int32 (t or more: the
    row is nobody's), gates [m] float32. Returns y with each row times
    its gate added into its token's row, as `scatter_add` does; t, m and
    d are multiples of 128 (`use_kernel`)."""
    gates = jnp.where(token < y.shape[0], gates.astype(jnp.float32), 0.0)
    return _jitted(y, rows, jnp.minimum(token, y.shape[0]).astype(jnp.int32),
                   gates, interpret=interpret_mode())
