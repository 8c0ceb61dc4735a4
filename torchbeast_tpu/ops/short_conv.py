"""The short causal convolution over episode ends as two Mosaic kernels:
one pass over the array a direction.

models/nemotron3.py `conv_over_episodes` is a depthwise convolution of
K = 3 or 4 taps along an unroll, a tap read only where no episode ended
between its step and the step it is read at. With x[-j] the carried
tail's rows and `reach[t]` the steps back that step t may read (`reach`
below: the K masks are a prefix in the distance),

    conv[t] = bias + sum_k taps[k] x[t - j]   where j = K - 1 - k <= reach[t]

    dx[s]   = sum_j taps[K - 1 - j] dconv[s + j]   where j <= reach[s + j]
    dtaps[K - 1 - j] = sum_{b, t : j <= reach[t]} dconv[t] x[t - j]
    dbias   = sum_{b, t} dconv[t]

Written as K shifted, masked adds over [tail; inputs] and differentiated
by `jax.grad`, XLA pads, slices inside a sublane tile and reduces K
times over (**Measured**, below). Here a cell is one row of the batch
and a block of whole lane tiles; a turn of the cell's one rolled loop
takes a lane tile's `_STEPS` steps with the 8 steps before them (after
them, backward), shifts them along the sublanes in registers (`pltpu.
roll`) and writes once. The backward reads dconv and the inputs once and
writes dinputs; a row's sums for dtaps, dbias and the tail's gradient
leave as two [8, C] tiles a row, which XLA sums over the rows (0.3 MB).

**Same arithmetic.** Float32 throughout; the forward adds the taps in
`conv_over_episodes`' order, bias first, so it is that form to the bit
where the compiler contracts nothing. Backward the order of the sums
differs.

**Shapes.** `kernels_apply`: an unroll of whole sublane tiles, channels
of whole lane tiles, 2 to 8 taps. T = 1 (acting) and toy widths keep
the `jax.numpy` form in models/nemotron3.py, the kernels' reference.

**Measured** (PERF.md section 6, PR 67; TPU v5e; Qwen3-Next's [16, 256,
8192] x 4 | Granite's [8, 512, 4352] x 4). In the step 0.41 ms a
forward call and 0.60 a backward | 0.22 and 0.32: 268 / 402 MB | 143 /
214 MB at 650-670 GB/s, 80% of the chip's 819, the pace of this repo's
other streaming kernels (ops/stream_mix.py). The backward of the
`jax.numpy` form was three fusions, 3.08 | 1.52 ms a layer: autodiff's
transpose of `inputs[:, k : k + T]` is a `pad` a tap, and the K products
crossed HBM as K whole arrays. `deltanet_conv` 15.57 -> 8.12 ms of
Qwen3-Next's step, `mamba_conv` 21.68 -> 10.55 of Granite's; the rest of
both scopes is XLA's silu beside the kernels.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_TILE = 8  # steps of a float32 sublane tile
_MAX_TAPS = _TILE
# Steps a turn of a cell's loop: the most of these that divide the unroll.
_STEPS = (128, 64, 32, 16, 8)
# A cell's block of the array, at most: the lane tiles a cell are the
# most that divide the channels' under it (32 of Qwen3-Next's 64, 17 of
# Granite's 34, all of the other three cells'). Past 4 tiles a cell the
# kernels' times move under 1%; Granite's 2 tiles cost 3% / 4% on its 17
# (PERF.md section 6, PR 67).
_CELL_BYTES = 6 * 1024 * 1024
_VMEM_LIMIT = 100 * 1024 * 1024


def kernels_apply(steps: int, channels: int, taps: int) -> bool:
    """Whether `conv_over_episodes` of models/nemotron3.py runs as these
    kernels: an unroll (more than one step) of whole sublane tiles,
    channels in whole lane tiles, 2 to 8 taps, one lane tile of a row
    within a cell's bytes. A function of the shapes alone (the learners'
    [256, 8192] x 4, [512, 4352] x 4, [256, 2560] x 4, [256, 5120] x 4
    and [256, 2048] x 3 are; acting at T = 1 and tier-1's toy widths
    are not)."""
    return (
        steps > 1 and steps % _TILE == 0
        and channels > 0 and channels % _LANES == 0
        and 2 <= taps <= _MAX_TAPS
        and steps * _LANES * 4 <= _CELL_BYTES
    )


def reach(done, taps: int):
    """[B, T] int32 of done [B, T]: how many steps back step t may read,
    min(taps - 1, steps since the last episode end at or before t); 0
    where `done[b, t]`, taps - 1 where the unroll has had no end yet
    (the carried tail lies before all of them)."""
    steps = jnp.arange(done.shape[1], dtype=jnp.int32)
    last_end = jax.lax.cummax(jnp.where(done, steps, -taps), axis=1)
    return jnp.minimum(taps - 1, steps - last_end)


def _tiles_a_cell(steps, channels):
    of_row = channels // _LANES
    return next(
        n for n in range(of_row, 0, -1)
        if of_row % n == 0 and (
            n == 1 or steps * n * _LANES * 4 <= _CELL_BYTES
        )
    )


def _steps_a_turn(steps):
    return next(n for n in _STEPS if steps % n == 0)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _row(tile, k):
    return tile[k : k + 1]


def _before_the_unroll(tail):
    """tail [K - 1, 128] as the last rows of an [8, 128] tile: the steps
    before the unroll's first."""
    at = _iota((_TILE, _LANES), 0)
    before = jnp.zeros((_TILE, _LANES), jnp.float32)
    for i in range(tail.shape[0]):
        before = jnp.where(
            at == _TILE - tail.shape[0] + i, _row(tail, i), before
        )
    return before


def _back(before_and_x, j):
    """x[t - j] for the steps t of x, of [the 8 steps before; x]."""
    return pltpu.roll(before_and_x, j, axis=0)[_TILE:]


def _ahead(g_and_after, j):
    """g[s + j] for the steps s of g, of [g; the 8 steps after]."""
    n = g_and_after.shape[0]
    return pltpu.roll(g_and_after, n - j, axis=0)[: n - _TILE]


def _forward_kernel(x_ref, tail_ref, reach_ref, taps_ref, bias_ref, out_ref,
                    *, turn):
    steps, K = x_ref.shape[0], taps_ref.shape[0]

    def tile(i, carry):
        lanes = pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES)
        taps, bias = taps_ref[:, lanes], bias_ref[:, lanes]

        def chunk(r, before):
            rows = pl.ds(pl.multiple_of(r * turn, turn), turn)
            x, may = x_ref[rows, lanes], reach_ref[rows, :]
            before_and_x = jnp.concatenate([before, x], axis=0)
            conv = bias
            for k in range(K):
                j = K - 1 - k
                conv = conv + _row(taps, k) * (
                    jnp.where(may >= j, _back(before_and_x, j), 0.0)
                    if j else x
                )
            out_ref[rows, lanes] = conv
            return x[turn - _TILE :]

        jax.lax.fori_loop(
            0, steps // turn, chunk, _before_the_unroll(tail_ref[:, lanes])
        )
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // _LANES, tile, 0)


def _backward_kernel(g_ref, x_ref, tail_ref, reach_ref, taps_ref,
                     dx_ref, dtaps_ref, dedge_ref, *, turn):
    steps, K = x_ref.shape[0], taps_ref.shape[0]
    at = _iota((_TILE, _LANES), 0)
    none = jnp.zeros((_TILE, _LANES), jnp.float32)

    def tile(i, carry):
        lanes = pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES)
        taps = taps_ref[:, lanes]

        def chunk(r, carried):
            before, sums = carried
            first = pl.multiple_of(r * turn, turn)
            rows = pl.ds(first, turn)
            # The 8 steps after the turn's: zeros past the unroll's end.
            after = pl.ds(pl.multiple_of(
                jnp.minimum(first + turn, steps - _TILE), _TILE
            ), _TILE)
            x, g = x_ref[rows, lanes], g_ref[rows, lanes]
            g_and_after = jnp.concatenate([g, jnp.where(
                at + (first + turn) < steps, g_ref[after, lanes], 0.0
            )], axis=0)
            may = jnp.concatenate(
                [reach_ref[rows, :], reach_ref[after, :]], axis=0
            )
            before_and_x = jnp.concatenate([before, x], axis=0)
            dx = _row(taps, K - 1) * g
            sums = list(sums)
            sums[K] = sums[K] + jnp.sum(g, axis=0, keepdims=True)
            sums[K - 1] = sums[K - 1] + jnp.sum(g * x, axis=0, keepdims=True)
            for j in range(1, K):
                k = K - 1 - j
                read = jnp.where(may >= j, g_and_after, 0.0)
                dx = dx + _row(taps, k) * _ahead(read, j)
                sums[k] = sums[k] + jnp.sum(
                    read[:turn] * _back(before_and_x, j), axis=0, keepdims=True
                )
            dx_ref[rows, lanes] = dx
            return x[turn - _TILE :], tuple(sums)

        _, sums = jax.lax.fori_loop(
            0, steps // turn, chunk, (
                _before_the_unroll(tail_ref[:, lanes]),
                (jnp.zeros((1, _LANES), jnp.float32),) * (K + 1),
            ),
        )
        dtaps = none
        for k in range(K):
            dtaps = jnp.where(at == k, sums[k], dtaps)
        dtaps_ref[:, lanes] = dtaps
        # The tail's gradient in the tile's last K - 1 rows, as the
        # forward holds the tail; dbias in row 0, which no tail reaches.
        head = pl.ds(0, _TILE)
        g, may = g_ref[head, lanes], reach_ref[head, :]
        dedge = jnp.where(at == 0, sums[K], none)
        for j in range(1, K):
            read = jnp.concatenate(
                [none, jnp.where(may >= j, g, 0.0)], axis=0
            )
            dedge = dedge + _row(taps, K - 1 - j) * _ahead(read, j)
        dedge_ref[:, lanes] = dedge
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // _LANES, tile, 0)


def _compiler_params(interpret):
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        )
    }


def _specs(steps, channels, K):
    """(grid's lane blocks, block specs of the array, the tail, `reach`
    over the lanes, the taps or bias of `rows` rows, an [8, C] tile a
    row) for cells of (a row, a block of lane tiles)."""
    width = _tiles_a_cell(steps, channels) * _LANES

    def over(rows):
        return pl.BlockSpec((None, rows, width), lambda b, c: (b, 0, c))

    def shared(rows):
        return pl.BlockSpec((rows, width), lambda b, c: (0, c))

    return (
        channels // width, over(steps), over(K - 1),
        pl.BlockSpec((None, steps, _LANES), lambda b, c: (b, 0, 0)),
        shared, over(_TILE),
    )


# Jitted, as ops/ssd_scan.py's calls are: a step's layers, forward,
# rematerialised and backward, trace and lower a kernel's body once.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(x, tail, may, taps, bias, *, interpret):
    batch, steps, channels = x.shape
    K = taps.shape[0]
    blocks, array, tail_rows, over_lanes, shared, _ = _specs(
        steps, channels, K
    )
    return pl.pallas_call(
        functools.partial(_forward_kernel, turn=_steps_a_turn(steps)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grid=(batch, blocks),
        in_specs=[array, tail_rows, over_lanes, shared(K), shared(1)],
        out_specs=array,
        interpret=interpret,
        name="short_conv_forward",
        **_compiler_params(interpret),
    )(x, tail, may, taps, bias)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(g, x, tail, may, taps, *, interpret):
    batch, steps, channels = x.shape
    K = taps.shape[0]
    blocks, array, tail_rows, over_lanes, shared, a_tile = _specs(
        steps, channels, K
    )
    small = jax.ShapeDtypeStruct((batch, _TILE, channels), jnp.float32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, turn=_steps_a_turn(steps)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, jnp.float32), small, small),
        grid=(batch, blocks),
        in_specs=[array, array, tail_rows, over_lanes, shared(K)],
        out_specs=(array, a_tile, a_tile),
        interpret=interpret,
        name="short_conv_backward",
        **_compiler_params(interpret),
    )(g, x, tail, may, taps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _conv(x, tail, may, taps, bias, interpret):
    return _forward(x, tail, may, taps, bias, interpret=interpret)


def _conv_fwd(x, tail, may, taps, bias, interpret):
    return (
        _forward(x, tail, may, taps, bias, interpret=interpret),
        (x, tail, may, taps),
    )


def _conv_bwd(interpret, kept, g):
    x, tail, may, taps = kept
    K = taps.shape[0]
    dx, dtaps, dedge = _backward(
        g.astype(jnp.float32), x, tail, may, taps, interpret=interpret
    )
    return (
        dx, dedge[:, _TILE - (K - 1) :], None,
        jnp.sum(dtaps[:, :K], axis=0), jnp.sum(dedge[:, :1], axis=0),
    )


_conv.defvjp(_conv_fwd, _conv_bwd)


def operands(inputs, tail, reach, taps, bias):
    """What the kernels read, of `short_conv`'s arguments: the array,
    the tail batch-first [B, K - 1, C], `reach` over a lane tile [B, T,
    128] (a step's mask is then a plain load), the taps and the bias as
    a row [1, C], zeros where there is none; all float32 but `reach`."""
    f32 = jnp.float32
    return (
        inputs.astype(f32), tail.astype(f32).transpose(1, 0, 2),
        jnp.broadcast_to(reach[..., None], reach.shape + (_LANES,)),
        taps.astype(f32),
        (jnp.zeros(taps.shape[1:], f32) if bias is None
         else bias.astype(f32))[None],
    )


def short_conv(inputs, tail, reach, taps, bias):
    """The convolution by the kernels (the module's header): inputs [B,
    T, C]; tail [K - 1, B, C], the K - 1 inputs before the unroll;
    reach [B, T] int32 (`reach` above); taps [K, C], the last the
    step's own; bias [C] or None -> conv [B, T, C] in float32, before
    any activation; differentiable in all but `reach`. The shapes must
    be `kernels_apply`'s."""
    _, steps, channels = inputs.shape
    K = taps.shape[0]
    if not kernels_apply(steps, channels, K):
        raise ValueError(
            f"{steps} steps of {channels} channels under {K} taps are not "
            "the short convolution's kernels' shapes"
        )
    return _conv(
        *operands(inputs, tail, reach, taps, bias),
        jax.default_backend() != "tpu",
    )
