"""Grouped matmuls whose float32 operands are cut into bfloat16 terms in
VMEM.

`models/moe.py` computes the experts as grouped matmuls at the precision
its caller traces under: at `high` an operand is two bfloat16 terms
(head = the operand rounded to bfloat16, tail = what is left, rounded)
and a product three passes of the MXU (head x head, head x tail, tail x
head) summed in float32; at `highest` three terms and six passes. JAX's
shipped megablox kernels take one bfloat16 operand a side, so such a
product was three (six) calls on terms cut by XLA ops in HBM, each
reading its operands and writing a whole float32 result that two more
passes then added (PERF.md, PR 50). `gmm` and `tgmm` here are forks of
the shipped pair (jax/experimental/pallas/ops/tpu/megablox/gmm.py: its
group metadata, tile visiting, store mask and `group_offset` are kept)
that read the FLOAT32 operands and cut every tile after it is loaded:
one call a product, the operands read once a tile visit, the result
written once, no term ever in HBM.

**Same arithmetic.** The terms are `ops/bf16_terms.py` `bf16_terms`'s
(`cut_in_kernel` beside it), the products the same `terms (terms + 1)
/ 2` (`product_in_kernel`), the smallest added first, every sum
float32. What differs is the order of summation: a tile's products are
added to one another before they are added to the running sum over the
contracted tiles, where three calls each summed alone.

**The cut is a cast there and back.** `lax.reduce_precision` has no
Mosaic lowering; `x.astype(bfloat16).astype(float32)`, which XLA folds
to the identity outside a kernel, is not folded inside one
(scripts/grouped_matmul_chip.py holds the kernels to a float64 product
on the chip: a folded cast would leave a tail of zeros and one pass's
error).

**Rows as their producer holds them.** `tgmm` takes both operands
`[m, .]` and contracts over the rows inside the kernel; the shipped one
is handed a transposed copy.

The contracted and the column tile divide their widths (`tiles`), so no
partial tile is masked; the row tile is the caller's.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from torchbeast_tpu.ops.bf16_terms import product_in_kernel

# What a call's tiles may take of the 16 MiB of VMEM a kernel is given
# unasked: the operands' and the result's double buffers, the running
# sum, and the terms a tile is cut into (`_tile_bytes`).
_VMEM_BUDGET = 12 * 2**20
_WIDEST_TILE = 1024


def _divisors(width):
    """The tiles a width can be cut into without a partial one: its
    divisors in whole lane tiles, the widest first; the width itself
    where there is none (a toy width)."""
    lanes = [
        tile for tile in range(_WIDEST_TILE, 0, -128) if width % tile == 0
    ]
    return lanes or [width]


def _tile_bytes(operands, result, terms):
    """VMEM a grid cell asks for, by the elements of its two operand
    tiles and of its result tile: float32 tiles double-buffered, the
    running sum, and an operand tile's terms beside what is left of
    it."""
    return (8 + 4 + 2 * terms) * operands + 12 * result


def tiles(tm, k, n, terms, over_rows=False):
    """(tk, tn) for rows tiled by `tm`: the widest column tile, then
    the deepest contracted one, that divide their widths and fit
    `_VMEM_BUDGET` (a wider column tile reads the rows fewer times).
    `over_rows`: `tgmm`'s cell, [tm, tk] and [tm, tn] into [tk, tn]."""
    for tn in _divisors(n):
        for tk in _divisors(k):
            if over_rows:
                operands, result = tm * (tk + tn), tk * tn
            else:
                operands, result = tk * (tm + tn), tm * tn
            if _tile_bytes(operands, result, terms) <= _VMEM_BUDGET:
                return tk, tn
    return _divisors(k)[-1], _divisors(n)[-1]


def _rows_of_group(grid_id, metadata, tm, width):
    """[tm, width] mask of the visited tile's rows that are the visited
    group's."""
    group_offsets, group_ids, m_tile_ids = metadata
    group = group_ids[grid_id]
    row = m_tile_ids[grid_id] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, width), 0
    )
    return (row >= group_offsets[group]) & (row < group_offsets[group + 1])


def _validate(lhs, rhs, rhs_rank, group_sizes):
    if lhs.ndim != 2 or rhs.ndim != rhs_rank:
        raise ValueError(
            f"Expected lhs of rank 2 and rhs of rank {rhs_rank}, got "
            f"{lhs.shape} and {rhs.shape}"
        )
    if lhs.dtype != jnp.float32 or rhs.dtype != jnp.float32:
        raise ValueError(
            f"The operands are cut from float32, got {lhs.dtype} and "
            f"{rhs.dtype}"
        )
    if group_sizes.dtype != jnp.int32:
        raise ValueError(f"Expected int32 group sizes: {group_sizes.dtype}")


def _offset(group_offset):
    if group_offset is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(group_offset, jnp.int32).reshape(1)


def _whole_tiles(width, tile, what):
    if width % tile:
        raise ValueError(
            f"{what} {width} is not whole tiles of {tile}: a partial tile "
            "is not masked here"
        )
    return width // tile


# Both grids: the column tiles apart, then the visits and the contracted
# (or `tgmm`'s row) tiles in order, a running sum between them.
_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary")
)


@functools.partial(
    jax.jit,
    static_argnames=("terms", "tm", "tiling", "transpose_rhs", "interpret"),
)
def gmm(lhs, rhs, group_sizes, *, terms, tm, tiling=None, group_offset=None,
        transpose_rhs=False, interpret=False):
    """lhs [m, k] in contiguous groups of `group_sizes` rows, rhs
    [C, k, n] (or [C, n, k] with `transpose_rhs`) -> [m, n] float32:
    rows of group `group_offset + c` times rhs[c], each operand `terms`
    bfloat16 terms. Rows of the groups rhs does not hold come out as
    zeros. `tm` tiles the rows and divides m; `tiling` (tk, tn) is
    chosen by `tiles` unless given."""
    _validate(lhs, rhs, 3, group_sizes)
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tk, tn = tiling or tiles(tm, k, n, terms)
    tiles_k = _whole_tiles(k, tk, "contracted width")
    tiles_n = _whole_tiles(n, tn, "column width")
    held, offset = rhs.shape[0], _offset(group_offset)
    metadata, visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=offset[0],
        num_nonzero_groups=held, visit_empty_groups=False,
    )
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def kernel(metadata, offset, lhs, rhs, out, acc):
        del offset
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += product_in_kernel(lhs[...], rhs[...], terms, dims)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            # A tile that two groups share is visited once for each:
            # the rows of the other stay as they are.
            mine = _rows_of_group(grid_id, metadata, tm, tn)
            out[...] = jax.lax.select(mine, acc[...], out[...])

    def lhs_index(n_i, grid_id, k_i, metadata, offset):
        del n_i, offset
        return metadata[2][grid_id], k_i

    def rhs_index(n_i, grid_id, k_i, metadata, offset):
        if transpose_rhs:
            k_i, n_i = n_i, k_i
        # The group ids count all the groups, rhs its own.
        return metadata[1][grid_id] - offset[0], k_i, n_i

    def out_index(n_i, grid_id, k_i, metadata, offset):
        del k_i, offset
        return metadata[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    most_visits = metadata[1].size
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec(rhs_block, rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        cost_estimate=pl.CostEstimate(
            flops=terms * (terms + 1) * m * k * n,
            bytes_accessed=4 * (
                m * k * tiles_n + k * n * most_visits + m * n
            ),
            transcendentals=0,
        ),
        interpret=interpret,
        name="gmm_cut_in_vmem",
        compiler_params=_GRID,
    )(metadata, offset, lhs, rhs)
    if held < group_sizes.shape[0]:
        # No visit wrote the rows of the groups that are not held.
        row = jnp.arange(m)
        theirs = (row >= metadata[0][offset[0]]) & (
            row < metadata[0][offset[0] + held]
        )
        out = jnp.where(theirs[:, None], out, 0)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("terms", "tm", "tiling", "num_actual_groups",
                     "interpret"),
)
def tgmm(lhs, rhs, group_sizes, *, terms, tm, tiling=None, group_offset=None,
         num_actual_groups=None, interpret=False):
    """lhs [m, k], rhs [m, n], both in contiguous groups of
    `group_sizes` rows -> [C, k, n] float32: for each of the
    `num_actual_groups` groups from `group_offset` on, its rows of lhs,
    transposed, times its rows of rhs; each operand `terms` bfloat16
    terms. A group without rows gives zeros. `tm` tiles the rows and
    divides m; `tiling` (tk, tn) is chosen by `tiles` unless given."""
    _validate(lhs, rhs, 2, group_sizes)
    m, k = lhs.shape
    n = rhs.shape[1]
    tk, tn = tiling or tiles(tm, k, n, terms, over_rows=True)
    tiles_k = _whole_tiles(k, tk, "lhs width")
    tiles_n = _whole_tiles(n, tn, "rhs width")
    held = num_actual_groups or group_sizes.shape[0]
    offset = _offset(group_offset)
    metadata, visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=offset[0],
        num_nonzero_groups=held, visit_empty_groups=True,
    )
    over_rows = (((0,), (0,)), ((), ()))

    def kernel(metadata, offset, lhs, rhs, out, acc):
        del offset
        group_offsets, group_ids, _ = metadata
        grid_id = pl.program_id(2)
        group = group_ids[grid_id]
        last = pl.num_programs(2) - 1

        @pl.when(
            (grid_id == 0) | (group_ids[jnp.maximum(grid_id - 1, 0)] != group)
        )
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        # An empty group is visited once, for its zeros alone.
        @pl.when(group_offsets[group + 1] > group_offsets[group])
        def _accumulate():
            acc[...] += product_in_kernel(
                jnp.where(
                    _rows_of_group(grid_id, metadata, tm, tk), lhs[...], 0.0
                ),
                jnp.where(
                    _rows_of_group(grid_id, metadata, tm, tn), rhs[...], 0.0
                ),
                terms, over_rows,
            )

        @pl.when(
            (grid_id == last)
            | (group_ids[jnp.minimum(grid_id + 1, last)] != group)
        )
        def _store():
            out[...] = acc[...]

    def lhs_index(n_i, k_i, grid_id, metadata, offset):
        del n_i, offset
        return metadata[2][grid_id], k_i

    def rhs_index(n_i, k_i, grid_id, metadata, offset):
        del k_i, offset
        return metadata[2][grid_id], n_i

    def out_index(n_i, k_i, grid_id, metadata, offset):
        return metadata[1][grid_id] - offset[0], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((held, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec((tm, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(tiles_n, tiles_k, visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        cost_estimate=pl.CostEstimate(
            flops=terms * (terms + 1) * m * k * n,
            bytes_accessed=4 * (
                m * k * tiles_n + m * n * tiles_k + held * k * n
            ),
            transcendentals=0,
        ),
        interpret=interpret,
        name="tgmm_cut_in_vmem",
        compiler_params=_GRID,
    )(metadata, offset, lhs, rhs)
