"""The Mamba-2 scan's chunks as two Mosaic kernels, the carried state in
VMEM and x, B, C, y where the mixer has them.

models/nemotron3.py `ssd_scan` computes the recurrence in chunks of Q
steps (its header has the masks). For a row, a chunk and a head h of
group g, with S [P, N] the state that enters the chunk, cs [Q] the
cumulative sum of dt A inside it and `ends` the count of episode ends
up to each step:

    scores = C_g B_g^T                                   [Q, Q], a group
    L_ij = exp(cs_i - cs_j) dt_j     where j <= i and ends_i == ends_j
    y = (scores . L) x + f . (C_g S^T)                   f = `from_start`
    S_next = f_Q S + (e . x)^T B_g                       e = `to_end`

and backward, the chunks in reverse with dS carried (dy, dS_next in):

    dW = dy x^T          dx = (scores . L)^T dy + e . (B_g dS_next^T)
    dscores = sum_h dW . L        dC_g = dscores B_g + sum_h (f . dy) S
    dB_g = dscores^T C_g + sum_h (e . x) dS_next
    dL = dW . scores: d dt_j = sum_i dL_ij E_ij (E = L / dt), d cs_i =
    sum_j dL_ij L_ij, d cs_j = -sum_i dL_ij L_ij
    df = rowsum(dy . (C_g S^T)), df_Q += <dS_next, S>
    de = rowsum((B_g dS_next^T) . x)     dS = f_Q dS_next + (f . dy)^T C_g

The `jax.numpy` form lays x and dt out again as [B, c, G, per, Q] and
[B, c, Q, G, per, P], transposes them for each einsum, writes every
chunk's `left` and `entering` ([B, c, H, P, N]) and scans over them:
59.8 ms of `granite4_policy.learner`'s 540 ms step for 12 ms of
products (PERF.md section 5, PR 64's account). Here a cell is one row,
one chunk and a block of heads of one group; the grid is (row, chunk,
head block), the chunks of a row in order with EVERY head's state in
scratch ([H P, N] float32: 2 MB at Granite's 64 heads), so that

- no state but the unroll's first and last crosses HBM;
- x and y are column blocks of [B, T, H P] as the mixer's convolution
  leaves them and its gate reads them, whole lane tiles (two heads of 64
  are one tile, and a tile is what a turn of a cell's one rolled loop
  works on: a head's own products take the tile with the other heads'
  lanes zeroed, which costs the MXU nothing, a product 64 wide being
  padded to 128; the products over the state take the tile whole);
- B and C are [B, T, G N] blocks that stay in VMEM while the grid walks
  a group's head blocks (the head block is the innermost axis: a block
  whose index does not change is not fetched again), `scores` is made
  once a (row, chunk, group) into scratch, the mask once a (row, chunk),
  and dB, dC are summed over a group's heads in the cell: the head
  block is the grid's reduction axis for them.

The per-step scalars are XLA's (`scan`, below: 1 MB arrays): cs, dt as
rows [B, c, H, 2, Q] (steps on the lanes) and cs, f, e as columns
[B, c, 3, Q, H'] (steps on the sublanes, heads on the lanes, H' whole
lane tiles: a head's column is a masked lane sum); `ends` both ways.
f_Q is `handed_on`; L's last row is e and is not read as such, a column
being what scales x's rows.

The backward kernel's grid has 2c - 1 turns a row, each over all head
blocks: c - 1 make the entering states of chunks 1..c-1 again from the
first (kept in scratch, c x [H P, N]), then c walk the chunks in reverse
with dS in scratch. While the states are made, the blocks that only the
walk touches stay on the walk's first cell's, so nothing is fetched or
written twice.

**Same arithmetic.** Every product is made from float32 tiles cut into
bfloat16 terms after they are loaded (ops/bf16_terms.py) at the count
the caller traces under; decays are exponentials of float32 differences
of cs, and where the mask says no the exponent is -1e30, added before
the exponential (zeros in L, f, e: multiplied by, no branch).

**Measured** (PERF.md section 6, PR 65; TPU v5e; Granite's cell: B 8, T
512 = 2 chunks of 256, H 64, P 64, G 1, N 128 | Nemotron-3's: B 16, T
256 = 2 chunks of 128, H 32, G 2; three passes). In the step 0.53 ms a
forward call and 1.15 a backward | 0.27 and 0.62, where the recurrence
owes (a head at its own 64 columns) 0.27 / 0.63 ms of products | 0.15 /
0.26 of bytes: 50% / 55% | 57% / 43% of the roofline. The MXU's issue
slots pace both kernels (64% full in the forward's loop, 76% in the
backward's walk; the vector unit 49% / 41%), and half of what they
issue are RESULT POPS: this Mosaic accumulates nothing in the MXU
across a product's K tiles or passes, so every (M / 8) x (N / 128) x (K
/ 128) x passes tile is popped and added on the vector unit, and a
head's (scores . L) x, 64 columns padded to 128 under 256 rows, pops as
much as a full-width product (its transpose, 64 rows as M, would pop
half: not done). The cell's size moves little past 4 lane tiles (0.67
ms at one tile, 0.58 at eight); L's all-zero block of a chunk of 256
was worth 9% / 12%. `ssd_scan` inclusive 54.96 -> 30.09 ms of Granite's
step, 14.90 -> 8.79 of Nemotron-3's.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbeast_tpu.ops.bf16_terms import cut_in_kernel, product_of_terms

_LANES = 128
_ROWS = 16  # steps of a bfloat16 sublane tile: a chunk is whole ones
_MAX_CHUNK = 256
_VMEM_LIMIT = 100 * 1024 * 1024
# What the backward kernel's entering states (every chunk's, every
# head's) may take of VMEM.
_STATE_BUDGET = 32 * 1024 * 1024
# Lane tiles a cell, at most (16 heads of 64): 1, 2, 4, 8, 32 ran the
# kernels at 0.67, 0.62, 0.59, 0.58, 0.58 ms forward and 1.45, 1.31,
# 1.24, 1.20, 1.16 backward at Granite's shapes (PERF.md section 6, PR
# 65).
_TILES = 8
_NEVER = -1e30  # an exponent where a step does not reach another

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b

_cut = cut_in_kernel


def kernels_apply(steps: int, Q: int, H: int, P: int, G: int, N: int) -> bool:
    """Whether `ssd_scan` of models/nemotron3.py runs as these kernels:
    an unroll (more than one step) in chunks of Q steps that are whole
    sublane tiles and no more than 256, a state of whole lane tiles,
    heads that fill lane tiles (P divides 128) with a group's heads
    whole tiles, and no more chunks than the backward kernel can hold
    the entering states of. A function of the shapes alone (the
    learners' [512, 8] at 64 / 64 / 1 / 128 and [256, 16] at 32 / 64 /
    2 / 128 are; acting at T = 1 and tier-1's toy widths are not and
    run the `jax.numpy` form)."""
    if min(steps, Q, H, P, G, N) < 1 or H % G or P > _LANES:
        return False
    chunks = -(-steps // Q)
    return (
        steps > 1 and Q % _ROWS == 0 and Q <= _MAX_CHUNK
        and N % _LANES == 0 and _LANES % P == 0
        and (H // G * P) % _LANES == 0
        and chunks * H * P * N * 4 <= _STATE_BUDGET
    )


def _tiles_a_cell(per_group, P):
    """Lane tiles a cell: the most, `_TILES` at most, that divide a
    group's."""
    of_group = per_group * P // _LANES
    return next(n for n in range(min(_TILES, of_group), 0, -1)
                if of_group % n == 0)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(values, head):
    """Head `head`'s column [Q, 1] of values [Q, H'] (heads on the
    lanes): a masked lane sum."""
    return jnp.sum(
        jnp.where(_iota(values.shape, 1) == head, values, 0.0),
        axis=1, keepdims=True,
    )


def _into_lane(ref, k, head, column):
    """Add column [Q, 1] into lane `head` of ref[k] [Q, H']."""
    ref[k] = ref[k] + jnp.where(
        _iota(ref.shape[1:], 1) == head, column, 0.0
    )


def _total(a):
    return jnp.sum(jnp.sum(a, axis=1, keepdims=True), axis=0, keepdims=True)


def _within(shape, axis, j, P):
    """Where lane (or sublane) tile positions are head j's of the tile."""
    at = _iota(shape, axis)
    return (at >= j * P) & (at < (j + 1) * P)


def _reach_exponent(ends_row_ref, ends_col_ref):
    """[Q, Q]: 0 where step j (a lane) reaches step i (a sublane), j <=
    i and no episode end in (j, i]; `_NEVER` elsewhere."""
    Q = ends_row_ref.shape[1]
    reach = (_iota((Q, Q), 0) >= _iota((Q, Q), 1)) & (
        ends_col_ref[...] == ends_row_ref[...]
    )
    return jnp.where(reach, 0.0, _NEVER)


def _blocks(Q):
    """(steps, sources) of L's blocks that are not all zeros: the steps
    in blocks of a lane tile, each with the source steps up to its last
    (a chunk of 256: the upper right [128, 128] of L is never made);
    one block where a chunk is not whole lane tiles."""
    step = _LANES if Q % _LANES == 0 else Q
    return [
        (slice(first, first + step), slice(0, first + step))
        for first in range(0, Q, step)
    ]


def _decays(rows, cs_column, exponent, steps, sources):
    """(E, L) of a head on the block [steps, sources]: rows [2, Q] its
    cs and dt along the lanes, cs_column [Q, 1], exponent the [Q, Q]
    scratch."""
    E = jnp.exp(
        cs_column[steps] - rows[0:1, sources] + exponent[steps, sources]
    )
    return E, E * rows[1:2, sources]


def _add_pieces(pieces, value, axis):
    """Add value, which covers the first so many of `pieces` along
    `axis` (each the extent of a block of `_blocks`), piece by piece."""
    extent = value.shape[axis] if len(pieces) == 1 else _LANES
    for at in range(value.shape[axis] // extent):
        piece = jax.lax.slice_in_dim(
            value, at * extent, (at + 1) * extent, axis=axis
        )
        pieces[at] = piece if pieces[at] is None else pieces[at] + piece


def _columns(cols_ref, k, first_head, heads):
    """Scalar k of a tile's heads, a column [Q, 1] each."""
    return [_column(cols_ref[k], first_head + j) for j in range(heads)]


def _over_lanes(columns, P):
    """A tile's heads' columns over the tile's lanes, [Q, 128]."""
    lanes = jnp.zeros((columns[0].shape[0], _LANES), jnp.float32)
    for j, column in enumerate(columns):
        lanes = jnp.where(_within((1, _LANES), 1, j, P), column, lanes)
    return lanes


def _last_down_rows(columns, P):
    """f_Q of a tile's heads down the tile's state rows [128, 1]."""
    Q = columns[0].shape[0]
    rows = jnp.zeros((_LANES, 1), jnp.float32)
    for j, column in enumerate(columns):
        rows = jnp.where(
            _within((_LANES, 1), 0, j, P), column[Q - 1 : Q], rows
        )
    return rows


def _leaving(S, x, cols_ref, first_head, heads, P, b_terms, terms):
    """A tile's states after the chunk, f_Q S + (e . x)^T B: S [128, N]
    entering, x [Q, 128], the chunk's columns."""
    e_lanes = _over_lanes(_columns(cols_ref, 2, first_head, heads), P)
    f_last = _last_down_rows(_columns(cols_ref, 1, first_head, heads), P)
    return f_last * S + product_of_terms(
        _cut(e_lanes * x, terms), b_terms, _TN
    )


def _forward_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, ends_row_ref,
                    ends_col_ref, s0_ref, y_ref, last_ref, state, scores,
                    exponent, *, terms, P, blocks_a_group):
    chunk, block = pl.program_id(1), pl.program_id(2)
    Q = x_ref.shape[0]
    tiles = x_ref.shape[1] // _LANES
    heads = _LANES // P  # a lane tile's

    @pl.when(block == 0)
    def _():
        exponent[...] = _reach_exponent(ends_row_ref, ends_col_ref)

    c_terms = _cut(c_ref[...], terms)
    b_terms = _cut(b_ref[...], terms)

    @pl.when(block % blocks_a_group == 0)
    def _():
        scores[...] = product_of_terms(c_terms, b_terms, _NT)

    def tile(i, carry):
        lanes = pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES)
        at = block * tiles + i  # the tile among the row's

        @pl.when(chunk == 0)
        def _():
            state[at] = s0_ref[lanes, :]

        x, S = x_ref[:, lanes], state[at]
        f_lanes = _over_lanes(_columns(cols_ref, 1, at * heads, heads), P)
        read = f_lanes * product_of_terms(c_terms, _cut(S, terms), _NT)
        y = [read[steps] for steps, _ in _blocks(Q)]
        for j in range(heads):
            rows = rows_ref[i * heads + j]
            cs_column = _column(cols_ref[0], at * heads + j)
            mine = _cut(
                jnp.where(_within((1, _LANES), 1, j, P), x, 0.0), terms
            )
            for k, (steps, sources) in enumerate(_blocks(Q)):
                _, L = _decays(rows, cs_column, exponent, steps, sources)
                y[k] = y[k] + product_of_terms(
                    _cut(scores[steps, sources] * L, terms),
                    [term[sources] for term in mine], _NN,
                )
        for (steps, _), piece in zip(_blocks(Q), y):
            y_ref[steps, lanes] = piece
        leaving = _leaving(
            S, x, cols_ref, at * heads, heads, P, b_terms, terms
        )
        state[at] = leaving

        @pl.when(chunk == pl.num_programs(1) - 1)
        def _():
            last_ref[lanes, :] = leaving

        return carry

    jax.lax.fori_loop(0, tiles, tile, 0)


def _backward_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, ends_row_ref,
                     ends_col_ref, s0_ref, dy_ref, dlast_ref,
                     dx_ref, db_ref, dc_ref, drows_ref, dcols_ref, ds0_ref,
                     entering, cotangent, scores, exponent, dscores,
                     *, terms, P, blocks_a_group, chunks):
    turn, block = pl.program_id(1), pl.program_id(2)
    Q = x_ref.shape[0]
    tiles = x_ref.shape[1] // _LANES
    heads = _LANES // P
    of_row = pl.num_programs(2) * tiles  # a row's lane tiles
    made = chunks - 1  # turns that make the entering states again

    def lanes_of(i):
        return pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES)

    @pl.when(turn == 0)
    def _():
        def tile(i, carry):
            entering[block * tiles + i] = s0_ref[lanes_of(i), :]
            return carry

        jax.lax.fori_loop(0, tiles, tile, 0)

    @pl.when(turn < made)
    def _():
        # The states again: chunk `turn` makes what enters the next.
        b_terms = _cut(b_ref[...], terms)

        def tile(i, carry):
            at = block * tiles + i
            entering[(turn + 1) * of_row + at] = _leaving(
                entering[turn * of_row + at], x_ref[:, lanes_of(i)],
                cols_ref, at * heads, heads, P, b_terms, terms,
            )
            return carry

        jax.lax.fori_loop(0, tiles, tile, 0)

    @pl.when(turn >= made)
    def _():
        chunk = 2 * made - turn
        first_of_group = block % blocks_a_group == 0

        @pl.when(block == 0)
        def _():
            exponent[...] = _reach_exponent(ends_row_ref, ends_col_ref)
            dcols_ref[...] = jnp.zeros_like(dcols_ref)

        c_terms = _cut(c_ref[...], terms)
        b_terms = _cut(b_ref[...], terms)

        @pl.when(first_of_group)
        def _():
            scores[...] = product_of_terms(c_terms, b_terms, _NT)
            dscores[...] = jnp.zeros_like(dscores)
            db_ref[...] = jnp.zeros_like(db_ref)
            dc_ref[...] = jnp.zeros_like(dc_ref)

        def tile(i, carry):
            lanes = lanes_of(i)
            at = block * tiles + i

            @pl.when(turn == made)
            def _():
                cotangent[at] = dlast_ref[lanes, :]

            x, dy = x_ref[:, lanes], dy_ref[:, lanes]
            S, dS_next = entering[chunk * of_row + at], cotangent[at]
            S_terms, dS_terms = _cut(S, terms), _cut(dS_next, terms)
            x_terms = _cut(x, terms)
            f_columns = _columns(cols_ref, 1, at * heads, heads)
            f_lanes = _over_lanes(f_columns, P)
            e_lanes = _over_lanes(
                _columns(cols_ref, 2, at * heads, heads), P
            )
            read = product_of_terms(c_terms, S_terms, _NT)  # C S^T
            back = product_of_terms(b_terms, dS_terms, _NT)  # B dS_next^T
            blocks = _blocks(Q)
            dx = [None] * len(blocks)
            _add_pieces(dx, e_lanes * back, 0)
            for j in range(heads):
                head = at * heads + j
                rows = rows_ref[i * heads + j]
                cs_column = _column(cols_ref[0], head)
                mine = _within((1, _LANES), 1, j, P)
                dy_mine = jnp.where(mine, dy, 0.0)
                dy_terms = _cut(dy_mine, terms)
                # By block: d cs down the steps, d cs and d dt along
                # the sources.
                d_steps, d_cs, d_dt = ([None] * len(blocks) for _ in "123")
                for k, (steps, sources) in enumerate(blocks):
                    E, L = _decays(rows, cs_column, exponent, steps, sources)
                    mine_dy = [term[steps] for term in dy_terms]
                    dW = product_of_terms(
                        mine_dy, [term[sources] for term in x_terms], _NT
                    )
                    _add_pieces(dx, product_of_terms(
                        _cut(scores[steps, sources] * L, terms), mine_dy, _TN
                    ), 0)
                    dscores[steps, sources] = (
                        dscores[steps, sources] + dW * L
                    )
                    through = dW * scores[steps, sources] * E  # dL . E
                    weighed = through * rows[1:2, sources]  # dL . L
                    d_steps[k] = jnp.sum(weighed, axis=1, keepdims=True)
                    _add_pieces(
                        d_cs, -jnp.sum(weighed, axis=0, keepdims=True), 1
                    )
                    _add_pieces(
                        d_dt, jnp.sum(through, axis=0, keepdims=True), 1
                    )
                drows_ref[i * heads + j] = jnp.concatenate([
                    jnp.concatenate(d_cs, axis=1),
                    jnp.concatenate(d_dt, axis=1),
                ], axis=0)
                _into_lane(
                    dcols_ref, 0, head, jnp.concatenate(d_steps, axis=0)
                )
                handed = _total(jnp.where(
                    _within((_LANES, 1), 0, j, P), dS_next * S, 0.0
                ))
                _into_lane(
                    dcols_ref, 1, head,
                    jnp.sum(dy_mine * read, axis=1, keepdims=True)
                    + jnp.where(_iota((Q, 1), 0) == Q - 1, handed, 0.0),
                )
                _into_lane(
                    dcols_ref, 2, head, jnp.sum(
                        jnp.where(mine, back * x, 0.0), axis=1, keepdims=True
                    ),
                )
            for (steps, _), piece in zip(blocks, dx):
                dx_ref[steps, lanes] = piece
            seen = _cut(f_lanes * dy, terms)
            dc_ref[...] = dc_ref[...] + product_of_terms(seen, S_terms, _NN)
            db_ref[...] = db_ref[...] + product_of_terms(
                _cut(e_lanes * x, terms), dS_terms, _NN
            )
            entered = _last_down_rows(f_columns, P) * dS_next + (
                product_of_terms(seen, c_terms, _TN)
            )
            cotangent[at] = entered

            @pl.when(chunk == 0)
            def _():
                ds0_ref[lanes, :] = entered

            return carry

        jax.lax.fori_loop(0, tiles, tile, 0)

        @pl.when(block % blocks_a_group == blocks_a_group - 1)
        def _():
            through = _cut(dscores[...], terms)
            dc_ref[...] = dc_ref[...] + product_of_terms(
                through, b_terms, _NN
            )
            db_ref[...] = db_ref[...] + product_of_terms(
                through, c_terms, _TN
            )


def _compiler_params(interpret):
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        )
    }


def _plan(x, b, rows, P, N):
    """(rows of the batch, chunks, Q, a cell's lanes, a cell's heads, a
    row's head blocks, a group's) of the kernels' operands."""
    chunks, H, Q = rows.shape[1], rows.shape[2], rows.shape[4]
    G = b.shape[2] // N
    width = _tiles_a_cell(H // G, P) * _LANES
    blocks = x.shape[2] // width
    return x.shape[0], chunks, Q, width, width // P, blocks, blocks // G


# Jitted, as ops/delta_rule.py's calls are: a step's mixer layers,
# forward, rematerialised and backward, trace and lower a kernel's body
# once.
@functools.partial(
    jax.jit, static_argnames=("terms", "P", "N", "interpret")
)
def _forward(x, b, c, rows, cols, ends_row, ends_col, s0, *, terms, P, N,
             interpret):
    batch, chunks, Q, width, heads, blocks, blocks_a_group = _plan(
        x, b, rows, P, N
    )
    inner, last = x.shape[2], chunks - 1
    f32 = jnp.float32

    def group(h):
        return h // blocks_a_group

    return pl.pallas_call(
        functools.partial(
            _forward_kernel, terms=terms, P=P, blocks_a_group=blocks_a_group
        ),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, f32),
            jax.ShapeDtypeStruct(s0.shape, f32),
        ),
        grid=(batch, chunks, blocks),
        in_specs=[
            pl.BlockSpec((None, Q, width), lambda r, t, h: (r, t, h)),
            pl.BlockSpec((None, Q, N), lambda r, t, h: (r, t, group(h))),
            pl.BlockSpec((None, Q, N), lambda r, t, h: (r, t, group(h))),
            pl.BlockSpec(
                (None, None, heads, 2, Q), lambda r, t, h: (r, t, h, 0, 0)
            ),
            pl.BlockSpec(
                (None, None) + cols.shape[2:], lambda r, t, h: (r, t, 0, 0, 0)
            ),
            pl.BlockSpec((None, None, 1, Q), lambda r, t, h: (r, t, 0, 0)),
            pl.BlockSpec((None, None, Q, 1), lambda r, t, h: (r, t, 0, 0)),
            # Read at the first chunk alone, written at the last.
            pl.BlockSpec(
                (None, width, N),
                lambda r, t, h: (r, jnp.where(t == 0, h, blocks - 1), 0),
            ),
        ],
        out_specs=(
            pl.BlockSpec((None, Q, width), lambda r, t, h: (r, t, h)),
            pl.BlockSpec(
                (None, width, N),
                lambda r, t, h: (r, jnp.where(t == last, h, 0), 0),
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((inner // _LANES, _LANES, N), f32),
            pltpu.VMEM((Q, Q), f32),
            pltpu.VMEM((Q, Q), f32),
        ],
        interpret=interpret,
        name="ssd_scan_forward",
        **_compiler_params(interpret),
    )(x, b, c, rows, cols, ends_row, ends_col, s0)


@functools.partial(
    jax.jit, static_argnames=("terms", "P", "N", "interpret")
)
def _backward(x, b, c, rows, cols, ends_row, ends_col, s0, dy, dlast, *,
              terms, P, N, interpret):
    batch, chunks, Q, width, heads, blocks, blocks_a_group = _plan(
        x, b, rows, P, N
    )
    inner, made = x.shape[2], chunks - 1
    f32 = jnp.float32

    def group(h):
        return h // blocks_a_group

    def chunk_of(t):  # the chunk a turn works on
        return jnp.where(t < made, t, 2 * made - t)

    def late(t):  # that of what only the walk touches
        return 2 * made - jnp.maximum(t, made)

    def late_block(t, h):
        return jnp.where(t < made, 0, h)

    # Blocks the walk alone touches stay, while the states are made, on
    # those of the walk's first cell (the last chunk, head block 0).
    def walked_block(r, t, h):
        return (r, late(t), late_block(t, h))

    def walked_group(r, t, h):
        return (r, late(t), group(late_block(t, h)))

    def over_row(when):  # a [B, H P, N] block, touched at turn `when`
        return pl.BlockSpec(
            (None, width, N),
            lambda r, t, h: (
                r, jnp.where(t < when, 0, jnp.where(t == when, h, blocks - 1)),
                0,
            ),
        )

    steps_x = pl.BlockSpec((None, Q, width), lambda r, t, h: (r, chunk_of(t), h))
    steps_b = pl.BlockSpec(
        (None, Q, N), lambda r, t, h: (r, chunk_of(t), group(h))
    )
    late_x = pl.BlockSpec((None, Q, width), walked_block)
    late_b = pl.BlockSpec((None, Q, N), walked_group)
    late_rows = pl.BlockSpec(
        (None, None, heads, 2, Q), lambda r, t, h: walked_block(r, t, h) + (0, 0)
    )
    steps_cols = pl.BlockSpec(
        (None, None) + cols.shape[2:], lambda r, t, h: (r, chunk_of(t), 0, 0, 0)
    )
    late_cols = pl.BlockSpec(
        (None, None) + cols.shape[2:], lambda r, t, h: (r, late(t), 0, 0, 0)
    )
    late_ends_row = pl.BlockSpec(
        (None, None, 1, Q), lambda r, t, h: (r, late(t), 0, 0)
    )
    late_ends_col = pl.BlockSpec(
        (None, None, Q, 1), lambda r, t, h: (r, late(t), 0, 0)
    )
    return pl.pallas_call(
        functools.partial(
            _backward_kernel, terms=terms, P=P,
            blocks_a_group=blocks_a_group, chunks=chunks,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, f32),
            jax.ShapeDtypeStruct(b.shape, f32),
            jax.ShapeDtypeStruct(c.shape, f32),
            jax.ShapeDtypeStruct(rows.shape, f32),
            jax.ShapeDtypeStruct(cols.shape, f32),
            jax.ShapeDtypeStruct(s0.shape, f32),
        ),
        grid=(batch, 2 * chunks - 1, blocks),
        in_specs=[
            steps_x, steps_b, late_b, late_rows, steps_cols, late_ends_row,
            late_ends_col, over_row(0), late_x, over_row(made),
        ],
        out_specs=(
            late_x, late_b, late_b, late_rows, late_cols, over_row(2 * made),
        ),
        scratch_shapes=[
            pltpu.VMEM((chunks * inner // _LANES, _LANES, N), f32),
            pltpu.VMEM((inner // _LANES, _LANES, N), f32),
            pltpu.VMEM((Q, Q), f32),
            pltpu.VMEM((Q, Q), f32),
            pltpu.VMEM((Q, Q), f32),
        ],
        interpret=interpret,
        name="ssd_scan_backward",
        **_compiler_params(interpret),
    )(x, b, c, rows, cols, ends_row, ends_col, s0, dy, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _chunks(x, b, c, rows, cols, ends_row, ends_col, s0, terms, P, N,
            interpret):
    return _chunks_fwd(
        x, b, c, rows, cols, ends_row, ends_col, s0, terms, P, N, interpret
    )[0]


def _chunks_fwd(x, b, c, rows, cols, ends_row, ends_col, s0, terms, P, N,
                interpret):
    operands = (x, b, c, rows, cols, ends_row, ends_col, s0)
    return _forward(
        *operands, terms=terms, P=P, N=N, interpret=interpret
    ), operands


def _chunks_bwd(terms, P, N, interpret, operands, cotangents):
    dy, dlast = cotangents
    dx, db, dc, drows, dcols, ds0 = _backward(
        *operands, dy.astype(jnp.float32), dlast.astype(jnp.float32),
        terms=terms, P=P, N=N, interpret=interpret,
    )
    ends_row, ends_col = operands[5:7]
    return (
        dx, db, dc, drows, dcols, jnp.zeros_like(ends_row),
        jnp.zeros_like(ends_col), ds0,
    )


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def operands(x, dt, A, B_in, C_in, state, done, Q):
    """What the kernels read, of `scan`'s arguments: x [B, T', H P], B,
    C [B, T', G N] (T' whole chunks), the per-step scalars as rows [B,
    c, H, 2, Q] (cs, dt) and as columns [B, c, 3, Q, H'] (cs,
    `from_start`, `to_end`), `ends` as a row and as a column, the state
    [B, H P, N]; all float32."""
    batch, steps, H, P = x.shape
    N = B_in.shape[3]
    pad = -steps % Q
    chunks = (steps + pad) // Q
    f32 = jnp.float32

    def padded(a, dtype=f32):
        a = a.reshape(a.shape[:2] + (-1,)).astype(dtype)
        return jnp.pad(a, ((0, 0), (0, pad), (0, 0)))

    # [B, c, Q, H]: the steps on the sublanes, as dt comes.
    dt = padded(dt).reshape(batch, chunks, Q, H)
    ends = jnp.cumsum(
        padded(done[..., None], jnp.int32).reshape(batch, chunks, Q), axis=2
    )
    cs = jnp.cumsum(dt * A, axis=2)
    from_start = jnp.where((ends == 0)[..., None], jnp.exp(cs), 0.0)
    to_end = jnp.exp(jnp.where(
        (ends[:, :, -1:] == ends)[..., None], cs[:, :, -1:] - cs, -jnp.inf
    )) * dt
    cols = jnp.pad(
        jnp.stack([cs, from_start, to_end], axis=2),
        ((0, 0),) * 4 + ((0, -H % _LANES),),
    )
    rows = jnp.stack([cs, dt], axis=2).transpose(0, 1, 4, 2, 3)
    ends = ends.astype(f32)
    return (
        padded(x), padded(B_in), padded(C_in), rows, cols,
        ends[:, :, None, :], ends[..., None],
        state.reshape(batch, H * P, N).astype(f32),
    )


def scan(x, dt, A, B_in, C_in, state, done, Q, terms):
    """`ssd_scan` of models/nemotron3.py by the kernels (the module's
    header), its arguments and results: x [B, T, H, P]; dt [B, T, H]
    (after the softplus); A [H]; B_in, C_in [B, T, G, N]; state [B, H,
    P, N]; done [B, T] bool -> (y [B, T, H, P] without the D x skip, the
    state after the last step); differentiable in all but `done`. Q:
    the steps of a chunk (`chunk_plan`'s); the last chunk is padded with
    steps of dt = 0. `terms`: the bfloat16 terms a side of every product
    (`ops/bf16_terms.terms_traced_under()` where the caller is traced:
    the backward kernel is traced after it and makes the same). The
    shapes must be `kernels_apply`'s."""
    batch, steps, H, P = x.shape
    G, N = B_in.shape[2:]
    if not kernels_apply(steps, Q, H, P, G, N):
        raise ValueError(
            f"{steps} steps in chunks of {Q}, {H} heads of {P} on {G} "
            f"groups of {N} are not the Mamba-2 scan's kernels' shapes"
        )
    y, last = _chunks(
        *operands(x, dt, A, B_in, C_in, state, done, Q), terms, P, N,
        jax.default_backend() != "tpu",
    )
    return (
        y[:, :steps].reshape(batch, steps, H, P),
        last.reshape(batch, H, P, N),
    )
