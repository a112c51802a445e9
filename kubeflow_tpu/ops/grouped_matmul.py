"""Pallas TPU grouped matmul for a few rows a group: each visited expert's
weight streams through VMEM once.

`lhs [m, k]` holds rows sorted by group, `rhs [groups, k, n]` one matrix
a group, `group_sizes [groups]` how many consecutive rows each group
owns: row i of group g gives `lhs[i] @ rhs[g]`, as `jax.lax.ragged_dot`
does. The dropless mixture layer (ops/moe.py) calls it three times a
layer, and a served pass has 16 rows a group in the mean: XLA's own
kernel then reloads the MXU with a 2,048 x 768 expert for 16 rows and
reads a third of the memory's bandwidth (PERF.md section 5, PR 29).

How it walks. The rows are cut into tiles of ROW_TILE. A *visit* is one
(row tile, group) pair that share a row, in the rows' order: groups are
contiguous, so a group's visits are consecutive, and so are a tile's.
The grid is one step a visit. A step's blocks are the tile's rows
`[ROW_TILE, k]`, the group's whole matrix `[k, n]` and the tile's
results `[ROW_TILE, n]`; Pallas's pipeline fetches the next step's
blocks while this step multiplies, keeps a block whose index does not
change (a group that straddles two tiles is read once, a tile's results
stay in VMEM until its last group has written its rows) and never
fetches a matrix no visit names: a group of no rows has no visit. A
step multiplies the whole tile by the group's matrix in float32 and
keeps the rows that are the group's (the MXU's time for 128 rows is the
time its weights take to load, so the rows of the neighbours cost
nothing that 16 rows would not). Rows behind the last group belong to
no visit: a tile of such rows alone is never written, and what those
rows hold is nobody's.

bfloat16 in, float32 accumulation over all of k, one rounding at the
end: what the call it replaces does. `use_kernel` says from backend,
mesh, dtype and shape which calls take it. Differentiable: the backward
is the reference's (`jax.vjp` of `jax.lax.ragged_dot`).

Off the TPU the kernel runs in Pallas interpret mode
(`flash_attention.interpret_mode()`), for its own tests only:
`use_kernel` keeps the model on `ragged_dot` there.
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

log = logging.getLogger("kubeflow_tpu.grouped_matmul")

# Rows to a tile: the MXU's own 128. A visit's matmul is bound by the
# 128 x 128 weight tiles it loads, not by the rows pushed through them,
# up to 128 rows; a larger tile pushes rows that are not the group's for
# longer than the weights take. Milliseconds a call at tiles of 64 / 128
# / 256 / 512 (my chip run, PR 32; 128 groups of [2048, 768]): 2,048 rows
# 0.625 / 0.625 / 0.651 / 1.147; 8,192 rows 0.779 / 0.751 / 0.769 /
# 1.243; 32,768 rows 1.284 / 1.238 / 1.223 / 1.631.
ROW_TILE = 128
# The custom call's name in the device trace (`%<name>.N = bf16[m,n]
# custom-call(`): it starts with ops/moe.py's EXPERT_MATMUL_TRACE_NAME,
# by which the benchmark finds a pass's grouped matmuls
# (tests/test_trace_names.py).
KERNEL_NAME = "ragged-dot-streamed"
# The most rows a group may have in the mean (m / groups) for the kernel
# to take the call: the largest mean it was measured at, not a crossover,
# for none was found. Milliseconds a call on a v5e, kernel / `ragged_dot`,
# 128 groups of [2048, 768] (of [768, 2048] within 7% of these), group
# sizes skewed as the cell's (tools/grouped_matmul_on_chip.py; my chip
# run, PR 32): 16 rows a group (a pass, and the prefill's rung of 256)
# 0.617 / 1.900; 32 0.663 / 1.982; 48 0.713 / 2.061; 64 (the rung of
# 1,024) 0.758 / 2.128; 128 0.925 / 2.365; 256 1.234 / 2.843; 512 1.801 /
# 3.764. A tile more costs the kernel 2.2 us and `ragged_dot` 3.6 us, so
# the two do not meet further out either; but thousands of rows a group
# (training: the zoo's gpt-moe-8e) are XLA's own case and stay with it
# until someone measures them.
MAX_MEAN_ROWS = 512


def use_kernel(m: int, k: int, n: int, groups: int, dtype) -> bool:
    """Whether a grouped matmul `[m, k] x [groups, k, n]` runs this
    kernel or `jax.lax.ragged_dot`, from what the code can observe,
    logged with the reason (as `paged_attention.use_kernel`): the kernel
    on a TPU backend, with everything on one device, bfloat16 operands,
    k and n that fill the lanes, and no more rows a group in the mean
    than it was measured at; XLA's kernel for everything else."""
    backend = jax.default_backend()
    mesh = current_mesh()
    if backend != "tpu":
        choice, why = "ragged_dot", f"default backend is {backend!r}, not tpu"
    elif mesh is not None and mesh.size > 1:
        choice, why = "ragged_dot", f"mesh of {mesh.size} devices"
    elif jnp.dtype(dtype) != jnp.bfloat16:
        choice, why = "ragged_dot", f"{jnp.dtype(dtype).name} operands"
    elif k % 128 or n % 128:
        choice, why = "ragged_dot", f"k {k}, n {n} not multiples of 128"
    elif m > MAX_MEAN_ROWS * groups:
        choice, why = "ragged_dot", (
            f"{m / groups:.0f} rows a group in the mean, over "
            f"{MAX_MEAN_ROWS}")
    else:
        choice, why = "kernel", (
            f"tpu backend, bfloat16, {m / groups:.0f} rows a group in the "
            f"mean of {m} x {k} x {n} over {groups} groups")
    log.info("grouped matmul: %s (%s)", choice, why)
    return choice == "kernel"


def _kernel(group_ref, tile_ref, bounds_ref, visits_ref,   # scalar prefetch
            x_ref, w_ref, o_ref):
    i = pl.program_id(0)

    # the steps behind the last visit repeat its blocks: nothing moves
    @pl.when(i < visits_ref[0])
    def _():
        g = group_ref[i]
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        row = tile_ref[i] * ROW_TILE + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        own = (row >= bounds_ref[g]) & (row < bounds_ref[g + 1])
        # the tile's other rows keep what their groups wrote (or, before
        # any has, whatever the buffer held: no visit leaves them so)
        o_ref[...] = jnp.where(
            own, acc, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _visits(group_sizes, m: int):
    """The walk's metadata: for each of the grid's steps its group and
    its row tile, the groups' row bounds, and how many steps are visits.
    At most tiles + groups - 1 (row tile, group) pairs share a row."""
    groups = group_sizes.shape[0]
    steps = pl.cdiv(m, ROW_TILE) + groups - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // ROW_TILE
    tiles = jnp.where(group_sizes > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    upto = jnp.cumsum(tiles)                 # visits up to and with group g
    visits = upto[-1]
    # a step behind the last visit repeats it, and fetches nothing
    step = jnp.clip(jnp.arange(steps), 0, jnp.maximum(visits - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(upto, step, side="right"), groups - 1)
    tile = first[group] + step - (upto - tiles)[group]
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (group.astype(jnp.int32), tile.astype(jnp.int32),
            bounds.astype(jnp.int32), visits.astype(jnp.int32)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _call(lhs, rhs, group_sizes, interpret: bool):
    m, k = lhs.shape
    groups, _, n = rhs.shape
    group, tile, bounds, visits = _visits(group_sizes, m)
    item = jnp.dtype(lhs.dtype).itemsize
    # two buffers a block, and the float32 product twice over for what
    # Mosaic keeps beside it
    vmem = 2 * item * (ROW_TILE * k + k * n + ROW_TILE * n) \
        + 2 * 4 * ROW_TILE * n
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(group.shape[0],),
            in_specs=[
                pl.BlockSpec((ROW_TILE, k), lambda i, g, t, *_: (t[i], 0)),
                pl.BlockSpec((None, k, n), lambda i, g, t, *_: (g[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (ROW_TILE, n), lambda i, g, t, *_: (t[i], 0))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        # visits in the rows' order: a tile's results stay until its
        # last group, a group's matrix until its last tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(vmem + (4 << 20), 16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=item * (m * k + m * n + min(groups, m) * k * n)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(group, tile, bounds, visits, lhs, rhs)


def _call_fwd(lhs, rhs, group_sizes, interpret):
    return _call(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _call_bwd(interpret, saved, g):
    # the reference's backward: training is not this kernel's case
    lhs, rhs, group_sizes = saved
    _, vjp = jax.vjp(
        lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), lhs, rhs)
    return (*vjp(g), None)


_call.defvjp(_call_fwd, _call_bwd)


# One jit for every layer's calls: a model's layers share the shapes, so
# the kernel is traced once a shape and a process, not once a call site
# (ops/paged_attention.py:_call, PR 27's lesson).
@functools.partial(jax.jit, static_argnames=("interpret",))
def _jitted(lhs, rhs, group_sizes, *, interpret: bool):
    return _call(lhs, rhs, group_sizes, interpret)


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs [m, k] sorted by group, rhs [groups, k, n], group_sizes
    [groups] int32 with sum <= m. Returns [m, n] in lhs's dtype: row i
    of group g is `lhs[i] @ rhs[g]`, what `jax.lax.ragged_dot` gives on
    the rows that belong to a group; the rows behind the last group hold
    anything."""
    return _jitted(lhs, rhs, group_sizes.astype(jnp.int32),
                   interpret=interpret_mode())
