"""Mamba-1's selective scan as two Mosaic kernels, the state in VMEM.

    s_t = exp(dt_t A) keep_t s_{t-1} + (dt_t a_t) B_t^T,   y_t = s_t C_t

with a state s [N, D] a row whose decay differs by channel AND by state
column, so that no chunk of it is a matmul (models/phi4flash.py
`selective_scan`, whose `lax.scan` form this computes: the same
recurrence, resets as a multiplier on the decay). The steps run one
after another on the vector unit; what the kernels save is the state's
trips through HBM: XLA's loop reads and writes [B, N, D] (5.2 MB at the
Phi-4-mini-flash widths) every step, forward, again for the chunk made
anew and three times over for the backward step, where a cell here
holds its [N, 512] of one row's state in vector registers, streams a,
dt (and y's cotangent) in and y (and the gradients) out, and never
writes a state but at a block's boundary.

A cell is one row, `STEP_BLOCK` steps and `CHANNEL_BLOCK` channels; the
grid walks a row's step blocks in order (backward: in reverse) and,
inside a step block, its channel blocks, with the row's whole state
[N, D] (backward: its cotangent and A's gradient) in scratch between
them, 327 KB: the block of B_t and C_t, which every channel block of
a step block reads, is then fetched once and not once a channel block
(671 MB a call where it was; the forward kernel ran at its streams'
pace before, PERF.md section 6, PR 55). The channels lie
on the lanes and the state's columns on the sublanes; a step's a, dt
and keep are rows broadcast down the sublanes, its B_t and C_t columns
broadcast along the lanes, which the kernels read as [N, 128] tiles that
XLA lays out beforehand ([B, T, N, 128], 33.5 MB each at the cell's
sizes: a column of 16 values cannot be turned to lie along the sublanes
in a cell for less).

The forward kernel also writes the state at each step block's start
([B, T / 128, N, D]); the backward kernel makes a block's states again
from it into VMEM ([129, N, 512], 4.2 MB) and walks the block in
reverse. No [T, B, N, D] value exists anywhere. The gradients of B_t
and C_t are sums over the channels: a cell reduces its lanes and lays
step t's column in lane t of an [N, 128] tile (a compare and a select),
XLA sums the channel blocks' tiles; A's gradient is summed over the
steps in the cell and over the rows by XLA.

Neither kernel uses the MXU. Both loops fill the vector unit's issue
slots (about 23 and 63 operations on [16, 128] a step and 128 channels,
and one exponent each; half of the forward's are the recurrence's own,
the rest the sublane sums and the packing of rows into tiles); by what
the recurrence OWES (perfbench/flops_phi4flash.py `scan_counts`) the
forward kernel is held to its streams and the backward to the vector
unit (PERF.md section 6, PR 55, has their shares).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_TILE = 8  # steps of a float32 sublane tile
# Steps and channels of a cell. 128 steps: a gradient column of step t
# lies in lane t of a tile. 512 channels: the state is eight vector
# registers, its cotangent and A's gradient sixteen more.
STEP_BLOCK = 128
CHANNEL_BLOCK = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def kernels_apply(steps: int, channels: int, columns: int) -> bool:
    """Whether `selective_scan` of models/phi4flash.py runs as these
    kernels: an unroll (more than one step; `selective_scan_kernels`
    pads it to whole step blocks), whole channel blocks, state columns
    that fill sublane tiles. A function of the shapes alone (a learner's
    unroll of 5,120 channels and 16 columns is, whatever its length;
    acting at T=1 and tier-1's toy widths are not and run the
    `lax.scan`)."""
    return (
        steps > 1 and channels % CHANNEL_BLOCK == 0 and columns % _TILE == 0
    )


_BLOCKS = range(CHANNEL_BLOCK // _LANES)  # the lane tiles of a cell


def _lanes(block):
    return slice(block * _LANES, (block + 1) * _LANES)


def _tile_of(i, *refs):
    """(first step, the `pl.ds` of its tile of 8, that tile [8, .] of
    each of `refs`) for tile i of a cell's step block."""
    first = pl.multiple_of(i * _TILE, _TILE)
    steps = pl.ds(first, _TILE)
    return (first, steps) + tuple(ref[0, steps, :] for ref in refs)


def _advance(s, a, dt, keep, A, bx, j, block):
    """(the state after step j of the tile for lane tile `block`, the
    step's decay): rows j of a, dt, keep down the sublanes, the column
    tile bx along the lanes."""
    at = (slice(j, j + 1), _lanes(block))
    decay = jnp.exp(dt[at] * A[block]) * keep[j : j + 1]
    return decay * s + (dt[at] * a[at]) * bx, decay


def _forward_kernel(a_ref, dt_ref, keep_ref, bx_ref, cx_ref, A_ref, s0_ref,
                    y_ref, bound_ref, last_ref, state):
    c = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[c] = s0_ref[0]

    bound_ref[0, 0] = state[c]
    A = [A_ref[:, _lanes(block)] for block in _BLOCKS]

    def tile(i, s):
        first, steps, a, dt, keep = _tile_of(i, a_ref, dt_ref, keep_ref)
        s = list(s)
        rows = [[] for _ in s]
        for j in range(_TILE):
            bx, cx = bx_ref[0, first + j], cx_ref[0, first + j]
            for block in _BLOCKS:
                s[block], _ = _advance(
                    s[block], a, dt, keep, A, bx, j, block
                )
                rows[block].append(
                    jnp.sum(s[block] * cx, axis=0, keepdims=True)
                )
        for block in _BLOCKS:
            y_ref[0, steps, _lanes(block)] = jnp.concatenate(
                rows[block], axis=0
            )
        return tuple(s)

    last = jax.lax.fori_loop(
        0, STEP_BLOCK // _TILE, tile,
        tuple(state[c, :, _lanes(block)] for block in _BLOCKS),
    )
    for block in _BLOCKS:
        state[c, :, _lanes(block)] = last[block]
    # Left as it is after the last step block, the last to write it.
    last_ref[0] = state[c]


def _backward_kernel(a_ref, dt_ref, keep_ref, bx_ref, cx_ref, A_ref,
                     bound_ref, dy_ref, dlast_ref, da_ref, ddt_ref, dB_ref,
                     dC_ref, dA_ref, ds0_ref, states, cotangent, dA_sum):
    c = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        cotangent[c] = dlast_ref[0]
        dA_sum[c] = jnp.zeros(dA_sum.shape[1:], jnp.float32)

    blocks = _BLOCKS
    A = [A_ref[:, _lanes(block)] for block in blocks]
    tiles = STEP_BLOCK // _TILE

    # The block's states again, from the one at its start: states[t] is
    # the state BEFORE step t of the block, states[t + 1] after it.
    states[0] = bound_ref[0, 0]

    def again(i, s):
        first, _, a, dt, keep = _tile_of(i, a_ref, dt_ref, keep_ref)
        s = list(s)
        for j in range(_TILE):
            bx = bx_ref[0, first + j]
            for block in blocks:
                s[block], _ = _advance(
                    s[block], a, dt, keep, A, bx, j, block
                )
                states[first + j + 1, :, _lanes(block)] = s[block]
        return tuple(s)

    jax.lax.fori_loop(
        0, tiles, again,
        tuple(states[0, :, _lanes(block)] for block in blocks),
    )

    columns = A_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (columns, _LANES), 1)
    zeros = jnp.zeros((columns, _LANES), jnp.float32)

    def back(r, carry):
        first, steps, a, dt, keep, dy = _tile_of(
            tiles - 1 - r, a_ref, dt_ref, keep_ref, dy_ref
        )
        g, dA, dB, dC = (list(part) if isinstance(part, tuple) else part
                         for part in carry)
        da_rows = [[None] * _TILE for _ in blocks]
        ddt_rows = [[None] * _TILE for _ in blocks]
        for j in reversed(range(_TILE)):
            t = first + j
            bx, cx = bx_ref[0, t], cx_ref[0, t]
            dB_t, dC_t = zeros, zeros
            for block in blocks:
                at = (slice(j, j + 1), _lanes(block))
                after = states[t + 1, :, _lanes(block)]
                before = states[t, :, _lanes(block)]
                g_t = g[block] + cx * dy[at]
                dC_t = dC_t + after * dy[at]
                dB_t = dB_t + g_t * (dt[at] * a[at])
                du = jnp.sum(g_t * bx, axis=0, keepdims=True)
                decay = jnp.exp(dt[at] * A[block]) * keep[j : j + 1]
                # d / d(dt A): the decay's own derivative is the decay.
                d_exponent = g_t * before * decay
                ddt_rows[block][j] = jnp.sum(
                    d_exponent * A[block], axis=0, keepdims=True
                ) + du * a[at]
                da_rows[block][j] = du * dt[at]
                dA[block] = dA[block] + d_exponent * dt[at]
                g[block] = g_t * decay
            # Step t's columns into lane t of the block's tiles.
            here = lane == t
            dB = jnp.where(here, jnp.sum(dB_t, axis=1, keepdims=True), dB)
            dC = jnp.where(here, jnp.sum(dC_t, axis=1, keepdims=True), dC)
        for block in blocks:
            da_ref[0, steps, _lanes(block)] = jnp.concatenate(
                da_rows[block], axis=0
            )
            ddt_ref[0, steps, _lanes(block)] = jnp.concatenate(
                ddt_rows[block], axis=0
            )
        return tuple(g), tuple(dA), dB, dC

    g, dA, dB, dC = jax.lax.fori_loop(
        0, tiles, back,
        (
            tuple(cotangent[c, :, _lanes(block)] for block in blocks),
            tuple(zeros for _ in blocks), zeros, zeros,
        ),
    )
    for block in blocks:
        cotangent[c, :, _lanes(block)] = g[block]
        dA_sum[c, :, _lanes(block)] += dA[block]
    dB_ref[0, 0] = dB
    dC_ref[0, 0] = dC
    # Left as they are after the first step block, the last to write them.
    dA_ref[0] = dA_sum[c]
    ds0_ref[0] = cotangent[c]


def _compiler_params(interpret):
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        )
    }


def _specs(columns, step_blocks, reverse):
    """Block specs of a cell (row b, step block t, channel block c of
    the grid, the channel blocks innermost: a step block's keep, B_t and
    C_t are fetched once for all of them; backward the step blocks are
    walked from the last)."""
    def at(t):
        return step_blocks - 1 - t if reverse else t

    return dict(
        stream=pl.BlockSpec(
            (1, STEP_BLOCK, CHANNEL_BLOCK), lambda b, t, c: (b, at(t), c)
        ),
        keep=pl.BlockSpec(
            (1, STEP_BLOCK, _LANES), lambda b, t, c: (b, at(t), 0)
        ),
        column=pl.BlockSpec(
            (1, STEP_BLOCK, columns, _LANES),
            lambda b, t, c: (b, at(t), 0, 0),
        ),
        A=pl.BlockSpec((columns, CHANNEL_BLOCK), lambda b, t, c: (0, c)),
        state=pl.BlockSpec(
            (1, columns, CHANNEL_BLOCK), lambda b, t, c: (b, 0, c)
        ),
        bound=pl.BlockSpec(
            (1, 1, columns, CHANNEL_BLOCK), lambda b, t, c: (b, at(t), 0, c)
        ),
        # A gradient column of step t in lane t: [B, channel blocks, N, T].
        column_grad=pl.BlockSpec(
            (1, 1, columns, STEP_BLOCK), lambda b, t, c: (b, c, 0, at(t))
        ),
    )


def _operands(a, dt, B_in, C_in, done):
    """What the kernels stream, float32: a, dt as they are; keep [B, T,
    128] and B_t, C_t [B, T, N, 128] broadcast along the lanes."""
    f32 = jnp.float32
    keep = jnp.broadcast_to(
        (1.0 - done.astype(f32))[..., None], done.shape + (_LANES,)
    )

    def along_lanes(x):
        return jnp.broadcast_to(
            x.astype(f32)[..., None], x.shape + (_LANES,)
        )

    return a.astype(f32), dt.astype(f32), keep, along_lanes(B_in), along_lanes(C_in)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan(a, dt, A, B_in, C_in, state, done, interpret):
    return _scan_fwd(a, dt, A, B_in, C_in, state, done, interpret)[0]


def _scan_fwd(a, dt, A, B_in, C_in, state, done, interpret):
    rows, steps, channels = a.shape
    columns = A.shape[0]
    step_blocks = steps // STEP_BLOCK
    channel_blocks = channels // CHANNEL_BLOCK
    spec = _specs(columns, step_blocks, reverse=False)
    streamed = _operands(a, dt, B_in, C_in, done)
    f32 = jnp.float32
    y, bound, last = pl.pallas_call(
        _forward_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(a.shape, f32),
            jax.ShapeDtypeStruct(
                (rows, step_blocks, columns, channels), f32
            ),
            jax.ShapeDtypeStruct((rows, columns, channels), f32),
        ),
        grid=(rows, step_blocks, channel_blocks),
        in_specs=[
            spec["stream"], spec["stream"], spec["keep"], spec["column"],
            spec["column"], spec["A"], spec["state"],
        ],
        out_specs=(spec["stream"], spec["bound"], spec["state"]),
        scratch_shapes=[
            pltpu.VMEM((channel_blocks, columns, CHANNEL_BLOCK), f32),
        ],
        interpret=interpret,
        name="selective_scan_forward",
        **_compiler_params(interpret),
    )(*streamed, A.astype(f32), state.astype(f32))
    return (y, last), (a, dt, A, B_in, C_in, done, bound)


def _scan_bwd(interpret, residuals, cotangents):
    a, dt, A, B_in, C_in, done, bound = residuals
    dy, dlast = cotangents
    rows, steps, channels = a.shape
    columns = A.shape[0]
    step_blocks = steps // STEP_BLOCK
    channel_blocks = channels // CHANNEL_BLOCK
    spec = _specs(columns, step_blocks, reverse=True)
    f32 = jnp.float32
    like_state = jax.ShapeDtypeStruct((rows, columns, channels), f32)
    by_step = jax.ShapeDtypeStruct(
        (rows, channel_blocks, columns, steps), f32
    )
    da, ddt, dB, dC, dA, ds0 = pl.pallas_call(
        _backward_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(a.shape, f32),
            jax.ShapeDtypeStruct(a.shape, f32),
            by_step, by_step, like_state, like_state,
        ),
        grid=(rows, step_blocks, channel_blocks),
        in_specs=[
            spec["stream"], spec["stream"], spec["keep"], spec["column"],
            spec["column"], spec["A"], spec["bound"], spec["stream"],
            spec["state"],
        ],
        out_specs=(
            spec["stream"], spec["stream"], spec["column_grad"],
            spec["column_grad"], spec["state"], spec["state"],
        ),
        scratch_shapes=[
            pltpu.VMEM((STEP_BLOCK + 1, columns, CHANNEL_BLOCK), f32),
            pltpu.VMEM((channel_blocks, columns, CHANNEL_BLOCK), f32),
            pltpu.VMEM((channel_blocks, columns, CHANNEL_BLOCK), f32),
        ],
        interpret=interpret,
        name="selective_scan_backward",
        **_compiler_params(interpret),
    )(
        *_operands(a, dt, B_in, C_in, done), A.astype(f32), bound,
        dy.astype(f32), dlast.astype(f32),
    )

    def by_column(grad, like):  # [B, blocks, N, T] -> [B, T, N]
        return jnp.sum(grad, axis=1).transpose(0, 2, 1).astype(like.dtype)

    return (
        da.astype(a.dtype), ddt.astype(dt.dtype),
        jnp.sum(dA, axis=0).astype(A.dtype), by_column(dB, B_in),
        by_column(dC, C_in), ds0, None,
    )


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan_kernels(a, dt, A, B_in, C_in, state, done):
    """models/phi4flash.py `selective_scan`'s operands and results, by
    the kernels (see the module's header): a, dt [B, T, D]; A [N, D];
    B_in, C_in [B, T, N]; state [B, N, D]; done [B, T] -> (y [B, T, D],
    the state after the last step [B, N, D]), float32; differentiable
    in all but `done`. The shapes must be `kernels_apply`'s. An unroll
    that is no whole step blocks is padded to them: a padded step has
    dt = 0 and keeps, so it passes the state on as it is."""
    rows, steps, channels = a.shape
    if not kernels_apply(steps, channels, A.shape[0]):
        raise ValueError(
            f"{steps} steps of {channels} channels and {A.shape[0]} state "
            f"columns are no unroll over whole blocks of the scan's kernels"
        )
    pad = -steps % STEP_BLOCK

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    y, last = _scan(
        *(padded(x) for x in (a, dt)), A, padded(B_in), padded(C_in), state,
        padded(done), jax.default_backend() != "tpu",
    )
    return y[:, :steps], last
