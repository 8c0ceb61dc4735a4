"""The gated delta rule in chunks as Mosaic kernels: the chunk-to-chunk
pass, the carried state in VMEM (PR 61), and what a chunk owes before
its state enters it, every [Q, Q] matrix in VMEM (PR 69: the second
half of this header).

models/qwen3next.py `delta_scan` computes the recurrence in chunks of Q
steps (its header has the algebra). For a (row, value head) and a chunk,
with S [Dk, Dv] the state that enters it, f [Q] what each step still
sees of S (`from_start`), e [Q] what the chunk's end still sees of each
step (`to_end`), A [Q, Q] = (q k^T) . decay (`weights`), U [Q, Dv]
(`values`) and Kd [Q, Dk] (`keys_seen`) the solve's two right-hand
sides, Kl = e . k:

    V' = U - Kd S        O = f . (q S) + A V'        S_next = f_Q S + Kl^T V'

and backward, the chunks in reverse with dS carried:

    dV' = A^T dO + Kl dS_next      dS = f_Q dS_next + q^T (f . dO) - Kd^T dV'
    dA = dO V'^T   dq = (f . dO) S^T   dU = dV'   dKd = -dV' S^T
    dKl = V' dS_next^T   df = rowsum(dO . (q S)),  df_Q += <dS_next, S>

The `jax.numpy` form makes S_next's two parts for every chunk at once
(`handed_on`, `left`: [Dk, Dk] and [Dk, Dv] a cell), scans over them and
writes every entering state: five [128, 128] float32 arrays a (row,
chunk, value head) through HBM, forward, rematerialised and twice over
backward, 35.4 ms of `qwen3next_policy.learner`'s 300 ms step for 2 ms
of arithmetic (PERF.md section 5, PR 56's account). Here a cell is one
row, a block of key heads (with their value heads) and one chunk; the
grid walks a row's chunks in order with the block's states in scratch,
so S never leaves VMEM between chunks and no [Dk, Dv] array but the
unroll's first and last state crosses HBM. The backward kernel's grid
has 2c - 1 turns a (row, block): c - 1 make the entering states of
chunks 1..c-1 again from the first (kept in scratch, c x 64 KB a value
head: cheaper than a residual, which would be the `entering` array
again, written and read), then c walk the chunks in reverse with dS in
scratch. While the states are made the blocks that only the reverse
walk touches stay on the last chunk's, so nothing is fetched or written
twice.

**A hand-on a key channel** (`chunk_pass(..., hand_on=)`, PR 68).
models/ling3.py's Kimi Delta Attention decays every key channel of a
head by its own factor: the decay sits INSIDE the key contraction, f
and e become [Q, Dk] and are folded into q and Kl by the caller (they
come here as ones), and what is left for the pass is the hand-on,
S_next = Diag(d) S + Kl^T V' with d [Dk] a chunk and value head, a row
scale where f_Q is one number. Given `hand_on`, the kernels read d as a
row [per, Dk] a key head, turn it to a column as they turn f and e,
multiply S (forward, the states made again, and dS = d . dS_next + ...
backward) by it and return dd = rowsum(dS_next . S) in place of f_Q's
<dS_next, S>. Without it (models/qwen3next.py: ONE decay a head and
step, a scalar outside the contraction, which is all its row
publishes and the cheaper form: a [Q, Q] decay matrix laid over K K^T,
no sub-blocks) the kernels trace to what they were, op for op.

**Same arithmetic.** Every product is made from float32 tiles cut into
bfloat16 terms after they are loaded (ops/bf16_terms.py), at the count
the caller traces under: three passes at the family's `high`, six at
`highest`; sums, S, f, e in float32. Where two products share an
operand their other operands are stacked: [Kd; q] S is one product with
128 rows, [f . dO; -dV'] S^T gives dq and dKd, and [q; Kd]^T [f . dO;
-dV'] is dS's q^T (f . dO) - Kd^T dV' at a full 128-wide contraction.
Episode ends are zeros in f, e and A: multiplied by, no branch.

**Layouts.** q, k [B, c, Hk, Q, Dk] (heads before steps: XLA writes
them so from the fusion that l2-normalises them, and reads them so for
`delta_intra`'s own products); the per-step scalars come as rows [B, c,
Hk, 2 per, Q] (f then e, a value head a row) and are turned to columns
in the cell by a masked lane sum; a key head's value heads are walked
inside the cell so that q, k and their cuts are loaded once, and
`_TOGETHER` key heads stand side by side in a turn of the cell's one
rolled loop (a head's products wait on one another; the host traces
one body a kernel).

**Forecast and measured** (PERF.md section 6, PR 61; TPU v5e, at the
cell's B 16, T 256 = 4 chunks of 64, Hk 16, Hv 32, Dk = Dv 128: 2,048
(row, chunk, value head) cells a layer). Bytes a call by the compiled
calls' own account: forward 405 MB (q, k 33.5 each; U, Kd, O 67 each; A
67, a [64, 64] tile being padded to 128 lanes in HBM; the first and
last state 33.5 each), backward 838 MB (the forward's reads, dO, the
last state's cotangent, seven results, and k, U, Kd of three chunks of
four a second time): 0.49 and 1.02 ms at 819 GB/s. Arithmetic: 3
products a value head and chunk forward, 2 + 7 backward, 15.0 and 45.1
GFLOP a call, 0.23 and 0.69 ms at three bf16 passes. Measured in the
step 0.80 ms a forward call and 1.78 a backward (alone 0.84 / 1.84;
XLA's form of the same pass 2.92 forward, 10.06 with its backward):
**62% and 57% of the roofline, which is the bytes'**; neither unit is
the limit: the cells' instruction streams are (1,109 bundles a key
head forward for 438 with an MXU push; the products are 64 to 128 rows
over a [128, 128] operand, so a push waits on a latch as often as not).
The cell size moves nothing (2 to 16 key heads a cell: 1.01 to 0.96
ms); heads side by side move the forward 12% and the backward 2%.

WHAT A CHUNK OWES BEFORE ITS STATE ENTERS IT (PR 69). For a (row,
chunk, value head) under one decay a head and step: L [Q, Q] strictly
lower, L_ij = beta_i (k_i . k_j) D_ij; W = (I + L)^-1; U = (W . beta)
v; Kd = (W . beta) (f . k); A = (q k^T) . D (beta a row over W's
columns, f what a step still sees of the entering state). XLA made
them for all chunks at once in ~30 ops a layer and direction, every
level of the solve's doubling through HBM as a [64, 64] array padded
to 128 lanes: 22.9 ms of `qwen3next_policy.learner`'s 264
(`delta_solve` + `delta_intra`) for ~2 ms of arithmetic. Here they are
three cells over the grid (row, chunk, block of key heads), every axis
parallel:

  `delta_sides_solve`     (k, beta, G, ends)              -> W
  `delta_sides_apply`     (W, q, k, v, beta, G, ends)     -> U, Kd, A
  `delta_sides_backward`  the operands, W, dU, dKd, dA    -> dq, dk, dv,
                                                             dbeta, dG

one `jax.custom_vjp` over the three (`sides_before_the_state`), chosen
by shape (`sides_apply`). The forward is TWO cells and not one because
of what a rematerialised block keeps: W, named `SOLVED` (4 Hv Q^2
bytes a row and chunk). Its second forward must make U, Kd and A again
(the pass's backward kernel reads them), and as one cell with the solve
it would solve again, 1.73 ms a layer; as two the second forward calls
the apply alone and W crosses HBM once more (33.5 MB, 0.04 ms).

**Two systems side by side.** Chunks of 64 steps: a key head's two
value heads stand in ONE lane tile, [X0 | X1] [64, 128]. A product with
a system's own matrix is the side-by-side tile times blockdiag(Y0, Y1)
[128, 128] (`_blocks`: the tile over itself, the off-diagonal blocks
masked), 64 rows through a FULL [128, 128] operand where a [64, 64] x
[64, 64] product fills a quarter of the array for the same 64 result
pops: the doubling's ten products pop half the rows they would a head
at a time, and the block-diagonal zeros stay exact zeros at every
level. K K^T and q K^T come out side by side from ONE product against
[k; k]. W is kept so, [B, c, Hk, 64, 128] dense, where XLA's [.., 64,
64] was padded to twice the bytes. Row scales come as rows [1, 128] and
are turned to columns by a masked sum (`_columns`); sums over a
system's own columns go back the same way (`_row_sums`); no lane is
shifted but A's second head, one slice forward and one concatenate
backward. v is read, and its cotangent written, where the mixer has
them, steps before heads: a key head's two value heads are 256 lanes
of the block (`_steps_first`), so no relayout of v stands beside the
cells (as first written, heads first, one did: 1.2 ms a step).

**Same arithmetic.** The doubling is models/qwen3next.py `_block_
doubling`'s, level for level (`_inverse_side_by_side`), at three
bfloat16 terms a side (six passes) whatever the caller traces under, as
the backward's -(W^T dW W^T); K K^T, q K^T, U, Kd and their cotangents
at the caller's terms; D's exponentials, masks and sums float32. An
entry of L that is exactly zero leaves W's exactly zero. Against
float64 on the chip at the cell's shapes the cells sit CLOSER than
XLA's form in every result and gradient (PERF.md section 6, PR 69:
0.5 to 1.4e-5 of the largest entry against 1.6 to 4.1e-5).

**Forecast and measured** (PERF.md section 6, PR 69; TPU v5e; Qwen3-
Next's layer: B 16, 4 chunks, 16 key heads x 2: 1,024 pairs a call).
Bytes: solve 67 MB (k in, W out), apply 436 (W, q, k 33.5 each; v, U,
Kd 67 each; A 134, padded to 128 lanes), backward 570: 0.08, 0.53 and
0.70 ms at 819 GB/s. MXU: the solve's sixty passes of [64, 128] x [128,
128] are 129 GFLOP a call, 0.65 ms at 197 TFLOP/s. The static bundles
of a described v5e (two key heads a turn of the rolled loop: 3,320 /
773 / 2,659 bundles, MXU slots 49-60% full, VALU 39-52%: neither unit
alone) forecast 1.13 / 0.27 / 0.90 ms; measured alone **1.73 / 0.59 /
1.19** and in the step 1.71 / 0.57 / 1.16: the apply runs at 90% of its
bytes' pace, the solve at 38% of the MXU's and the backward at 59% of
its bytes': their instruction streams pace them, as the pass's (the
bundles do not show a push's wait on its latch). XLA's form of the
same op, alone: 4.15 ms forward, 7.58 with its backward; the cells
2.53 and 3.95. Tried and left out: the short operand's terms stacked
over each term of the other, a latch a term (solve 2.11 ms); four key
heads a turn (1.67 / 0.60 / 1.13: 0.4 ms a step for twice the body).

**Not for a decay a key channel** (models/ling3.py `kda_scan`, whose L
is its own sub-blocks'): the same solve and apply entered at `lower`
were built and measured there, and LOST (PERF.md section 6, PR 69:
`ling3_policy.learner` 7,354 -> 7,298 learn frames/s). XLA's doubling
runs at 0.78 us a system in that cell (1.36 in Qwen3-Next's), which is
this solve's own pace, and its U and Kd einsums fuse f . k and the
masks that a cell's operands make arrays of; the entry was taken out.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbeast_tpu.ops.bf16_terms import cut_in_kernel, product_of_terms
from torchbeast_tpu.telemetry import device_scope

_LANES = 128
_ROWS = 16  # steps of a bfloat16 sublane tile: a chunk is whole ones
_VMEM_LIMIT = 100 * 1024 * 1024
# What the backward kernel's entering states may take of VMEM, and a
# cell's streamed blocks (double-buffered) beside them.
_STATE_BUDGET = 8 * 1024 * 1024
_HEADS = 8  # key heads a cell, at most
# Key heads a turn of a cell's loop (their bodies side by side in one
# rolled loop): 1, 2, 4 ran the forward kernel at 0.96, 0.89, 0.84 ms and
# the backward at 1.89, 1.87, 1.84 (PERF.md section 6, PR 61).
_TOGETHER = 4

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def kernels_apply(steps: int, Q: int, Dk: int, Dv: int) -> bool:
    """Whether `delta_scan`'s chunk-to-chunk pass runs as these kernels:
    an unroll (more than one step) in chunks of Q steps that are whole
    sublane tiles, key and value widths that are whole lane tiles, and
    no more chunks than the backward kernel can hold the entering states
    of for one key head with two value heads. A function of the shapes
    alone (the learner's [256, B] unroll at the published 128 x 128 is;
    acting at T = 1 and tier-1's toy widths are not and run the
    `jax.numpy` form)."""
    chunks = -(-steps // max(Q, 1))
    return (
        steps > 1 and Q % _ROWS == 0 and Q <= _LANES
        and Dk % _LANES == 0 and Dv % _LANES == 0
        and 2 * chunks * Dk * Dv * 4 <= _STATE_BUDGET
    )


def _heads_a_cell(Hk, per, chunks, Dk, Dv):
    """Key heads a cell: the most, `_HEADS` at most, that divide Hk and
    whose entering states fit the budget."""
    return next(
        hb for hb in range(min(_HEADS, Hk), 0, -1)
        if Hk % hb == 0
        and (hb == 1 or hb * per * chunks * Dk * Dv * 4 <= _STATE_BUDGET)
    )


_cut = cut_in_kernel


def _over_heads(heads, body, together=_TOGETHER):
    """`body(h)` for every key head of a cell, `together` of them a
    turn of ONE rolled loop: a head's products wait on one another (S,
    then V', then what both feed), and a second head beside it fills
    the gaps."""
    together = next(n for n in range(together, 0, -1) if heads % n == 0)

    def turn(i, carry):
        for j in range(together):
            body(i * together + j)
        return carry

    jax.lax.fori_loop(0, heads // together, turn, 0)


def _turned(x, axis):
    """Per-step scalars from a row [1, Q] to a column [Q, 1] (`axis` 1)
    or back (`axis` 0): the diagonal of their broadcast, summed over
    `axis`."""
    Q = max(x.shape)
    diagonal = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )
    return jnp.sum(jnp.where(diagonal, x, 0.0), axis=axis, keepdims=True)


def _column(row):
    return _turned(row, 1)


def _row(column):
    return _turned(column, 0)


def _last(row):
    """The last entry of a row [1, Q], [1, 1]."""
    Q = row.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    return jnp.sum(jnp.where(lane == Q - 1, row, 0.0), axis=1, keepdims=True)


def _advance(S, k, e_column, f_last, u, kd, terms, q_terms=None):
    """(V' in terms, q S or None, the state after the chunk) for one
    value head: S [Dk, Dv] entering, k [Q, Dk], u [Q, Dv], kd [Q, Dk];
    `f_last` what the state is handed on under, [1, 1] (one decay a
    head) or a column [Dk, 1] (one a key channel: `hand_on`)."""
    Q = k.shape[0]
    S_terms = _cut(S, terms)
    if q_terms is None:
        seen, read = product_of_terms(_cut(kd, terms), S_terms, _NN), None
    else:
        # [Kd; q] S: one product of 2 Q rows over the state.
        stacked = [
            jnp.concatenate([a, b], axis=0)
            for a, b in zip(_cut(kd, terms), q_terms)
        ]
        both = product_of_terms(stacked, S_terms, _NN)
        seen, read = both[:Q], both[Q:]
    corrected = _cut(u - seen, terms)
    leaving = f_last * S + product_of_terms(
        _cut(e_column * k, terms), corrected, _TN
    )
    return corrected, read, leaving


def _forward_kernel(q_ref, k_ref, steps_ref, a_ref, u_ref, kd_ref, s0_ref,
                    *rest, terms):
    # `rest`: with a hand-on a key channel its rows [heads, per, Dk]
    # first; then the two results and the scratch.
    hand_ref = rest[0] if len(rest) == 4 else None
    o_ref, last_ref, state = rest[-3:]
    heads, per = state.shape[:2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = s0_ref[...]

    def head(h):
        k = k_ref[h]
        q_terms = _cut(q_ref[h], terms)
        steps = steps_ref[h]
        for p in range(per):
            f = steps[p : p + 1]
            corrected, read, leaving = _advance(
                state[h, p], k, _column(steps[per + p : per + p + 1]),
                _last(f) if hand_ref is None
                else _column(hand_ref[h][p : p + 1]),
                u_ref[h, p], kd_ref[h, p], terms, q_terms,
            )
            o_ref[h, p] = _column(f) * read + product_of_terms(
                _cut(a_ref[h, p], terms), corrected, _NN
            )
            state[h, p] = leaving

    _over_heads(heads, head)
    # Left as it is after the last chunk, the last to write it.
    last_ref[...] = state[...]


def _backward_kernel(q_ref, k_ref, steps_ref, a_ref, u_ref, kd_ref, s0_ref,
                     *rest, terms):
    # `rest`: with a hand-on a key channel its rows first and, last of
    # the results, their cotangent's.
    per_channel = len(rest) == 13
    hand_ref = rest[0] if per_channel else None
    (do_ref, dlast_ref, dq_ref, dk_ref, dsteps_ref, da_ref, du_ref, dkd_ref,
     ds0_ref) = rest[per_channel : per_channel + 9]
    dhand_ref = rest[-3] if per_channel else None
    entering, cotangent = rest[-2:]
    chunks, heads, per = entering.shape[:3]
    turn = pl.program_id(2)

    def handed_on_under(h, p, steps):
        if hand_ref is None:
            return _last(steps[p : p + 1])
        return _column(hand_ref[h][p : p + 1])

    @pl.when(turn == 0)
    def _():
        entering[0] = s0_ref[...]
        cotangent[...] = dlast_ref[...]

    @pl.when(turn < chunks - 1)
    def _():
        # The states again: chunk `turn` makes what enters the next.
        def head(h):
            k = k_ref[h]
            steps = steps_ref[h]
            for p in range(per):
                _, _, leaving = _advance(
                    entering[turn, h, p], k,
                    _column(steps[per + p : per + p + 1]),
                    handed_on_under(h, p, steps), u_ref[h, p], kd_ref[h, p],
                    terms,
                )
                entering[turn + 1, h, p] = leaving

        _over_heads(heads, head)

    @pl.when(turn >= chunks - 1)
    def _():
        chunk = 2 * (chunks - 1) - turn

        def head(h):
            q, k = q_ref[h], k_ref[h]
            Q = q.shape[0]
            q_terms = _cut(q, terms)
            steps = steps_ref[h]
            dq = dk = None
            rows = [None] * (2 * per)
            hand_rows = [None] * per
            for p in range(per):
                f, e = steps[p : p + 1], steps[per + p : per + p + 1]
                f_column, e_column = _column(f), _column(e)
                f_last = handed_on_under(h, p, steps)
                S, dS_next = entering[chunk, h, p], cotangent[h, p]
                kd, dO = kd_ref[h, p], do_ref[h, p]
                S_terms = _cut(S, terms)
                kd_terms = _cut(kd, terms)
                over_state = [
                    jnp.concatenate([a, b], axis=0)
                    for a, b in zip(q_terms, kd_terms)
                ]  # [q; Kd]
                both = product_of_terms(over_state, S_terms, _NN)
                read, corrected = both[:Q], u_ref[h, p] - both[Q:]
                dS_terms = _cut(dS_next, terms)
                dO_terms = _cut(dO, terms)
                d_corrected = product_of_terms(
                    _cut(a_ref[h, p], terms), dO_terms, _TN
                ) + product_of_terms(_cut(e_column * k, terms), dS_terms, _NN)
                du_ref[h, p] = d_corrected
                corrected_terms = _cut(corrected, terms)
                da_ref[h, p] = product_of_terms(
                    dO_terms, corrected_terms, _NT
                )
                # [f . dO; -dV']: against S^T it is [dq; dKd], under
                # [q; Kd]^T the state's cotangent but for f_Q dS_next.
                through = _cut(
                    jnp.concatenate([f_column * dO, -d_corrected], axis=0),
                    terms,
                )
                grads = product_of_terms(through, S_terms, _NT)
                dkd_ref[h, p] = grads[Q:]
                dq = grads[:Q] if dq is None else dq + grads[:Q]
                d_left = product_of_terms(corrected_terms, dS_terms, _NT)
                dk_p = e_column * d_left
                dk = dk_p if dk is None else dk + dk_p
                # What the hand-on's factor is owed: <dS_next, S>, whole
                # (the chunk's last f) or a key channel.
                through_hand_on = jnp.sum(dS_next * S, axis=1, keepdims=True)
                rows[p] = _row(jnp.sum(dO * read, axis=1, keepdims=True))
                if hand_ref is None:
                    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
                    rows[p] = rows[p] + jnp.where(
                        lane == Q - 1,
                        jnp.sum(through_hand_on, axis=0, keepdims=True), 0.0,
                    )
                else:
                    hand_rows[p] = _row(through_hand_on)
                rows[per + p] = _row(
                    jnp.sum(d_left * k, axis=1, keepdims=True)
                )
                cotangent[h, p] = f_last * dS_next + product_of_terms(
                    over_state, through, _TN
                )
            dq_ref[h] = dq
            dk_ref[h] = dk
            dsteps_ref[h] = jnp.concatenate(rows, axis=0)
            if hand_ref is not None:
                dhand_ref[h] = jnp.concatenate(hand_rows, axis=0)

        _over_heads(heads, head)

    # Left as it is after chunk 0, the last to write it.
    ds0_ref[...] = cotangent[...]


def _compiler_params(interpret, last="arbitrary"):
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", last),
            vmem_limit_bytes=_VMEM_LIMIT,
        )
    }


def _specs(shapes, heads, chunk_of, late):
    """Block specs of a cell (row b, head block g, turn t of the grid):
    `chunk_of(t)` the chunk whose k, scalars, U and Kd the turn reads,
    `late(t)` that of the blocks only the walk itself touches."""
    Q, Dk, Dv, per = shapes

    def by_chunk(at, *tail):
        return pl.BlockSpec(
            (None, None, heads) + tail,
            lambda b, g, t: (b, at(t), g) + (0,) * len(tail),
        )

    return dict(
        q=by_chunk(late, Q, Dk),
        k=by_chunk(chunk_of, Q, Dk),
        steps=by_chunk(chunk_of, 2 * per, Q),
        a=by_chunk(late, per, Q, Q),
        u=by_chunk(chunk_of, per, Q, Dv),
        kd=by_chunk(chunk_of, per, Q, Dk),
        o=by_chunk(late, per, Q, Dv),
        late_k=by_chunk(late, Q, Dk),
        late_steps=by_chunk(late, 2 * per, Q),
        late_kd=by_chunk(late, per, Q, Dk),
        hand=by_chunk(chunk_of, per, Dk),
        late_hand=by_chunk(late, per, Dk),
        state=pl.BlockSpec(
            (None, heads, per, Dk, Dv), lambda b, g, t: (b, g, 0, 0, 0)
        ),
    )


# Jitted, as ops/stream_mix.py's calls are and for its reason: a step's
# three layers, forward, rematerialised and backward, trace and lower a
# kernel's body once.
@functools.partial(jax.jit, static_argnames=("terms", "interpret"))
def _forward(q, k, steps, a, u, kd, s0, hand=None, *, terms, interpret):
    rows, chunks, Hk, Q, Dk = q.shape
    per, Dv = u.shape[3], u.shape[5]
    heads = _heads_a_cell(Hk, per, chunks, Dk, Dv)
    spec = _specs((Q, Dk, Dv, per), heads, lambda t: t, lambda t: t)
    f32 = jnp.float32
    handed = () if hand is None else (hand,)
    return pl.pallas_call(
        functools.partial(_forward_kernel, terms=terms),
        out_shape=(
            jax.ShapeDtypeStruct(u.shape, f32),
            jax.ShapeDtypeStruct(s0.shape, f32),
        ),
        grid=(rows, Hk // heads, chunks),
        in_specs=[
            spec["q"], spec["k"], spec["steps"], spec["a"], spec["u"],
            spec["kd"], spec["state"],
        ] + [spec["hand"]] * len(handed),
        out_specs=(spec["o"], spec["state"]),
        scratch_shapes=[pltpu.VMEM((heads, per, Dk, Dv), f32)],
        interpret=interpret,
        name="delta_rule_forward",
        **_compiler_params(interpret),
    )(q, k, steps, a, u, kd, s0, *handed)


@functools.partial(jax.jit, static_argnames=("terms", "interpret"))
def _backward(q, k, steps, a, u, kd, s0, dO, dlast, hand=None, *, terms,
              interpret):
    rows, chunks, Hk, Q, Dk = q.shape
    per, Dv = u.shape[3], u.shape[5]
    heads = _heads_a_cell(Hk, per, chunks, Dk, Dv)
    made = chunks - 1  # turns that make the entering states again

    def chunk_of(t):
        return jnp.where(t < made, t, 2 * made - t)

    def late(t):
        return 2 * made - jnp.maximum(t, made)

    spec = _specs((Q, Dk, Dv, per), heads, chunk_of, late)
    f32 = jnp.float32
    handed = () if hand is None else (hand,)
    return pl.pallas_call(
        functools.partial(_backward_kernel, terms=terms),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, f32),
            jax.ShapeDtypeStruct(k.shape, f32),
            jax.ShapeDtypeStruct(steps.shape, f32),
            jax.ShapeDtypeStruct(a.shape, f32),
            jax.ShapeDtypeStruct(u.shape, f32),
            jax.ShapeDtypeStruct(kd.shape, f32),
            jax.ShapeDtypeStruct(s0.shape, f32),
        ) + tuple(jax.ShapeDtypeStruct(x.shape, f32) for x in handed),
        grid=(rows, Hk // heads, 2 * chunks - 1),
        in_specs=[
            spec["q"], spec["k"], spec["steps"], spec["a"], spec["u"],
            spec["kd"], spec["state"],
        ] + [spec["hand"]] * len(handed) + [spec["o"], spec["state"]],
        out_specs=(
            spec["q"], spec["late_k"], spec["late_steps"], spec["a"],
            spec["o"], spec["late_kd"], spec["state"],
        ) + (spec["late_hand"],) * len(handed),
        scratch_shapes=[
            pltpu.VMEM((chunks, heads, per, Dk, Dv), f32),
            pltpu.VMEM((heads, per, Dk, Dv), f32),
        ],
        interpret=interpret,
        name="delta_rule_backward",
        **_compiler_params(interpret),
    )(q, k, steps, a, u, kd, s0, *handed, dO, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _pass(q, k, steps, a, u, kd, s0, hand, terms, interpret):
    return _pass_fwd(q, k, steps, a, u, kd, s0, hand, terms, interpret)[0]


def _pass_fwd(q, k, steps, a, u, kd, s0, hand, terms, interpret):
    operands = (q, k, steps, a, u, kd, s0, hand)
    return _forward(*operands, terms=terms, interpret=interpret), operands


def _pass_bwd(terms, interpret, residuals, cotangents):
    dO, dlast = cotangents
    grads = _backward(
        *residuals[:-1], dO.astype(jnp.float32), dlast.astype(jnp.float32),
        residuals[-1], terms=terms, interpret=interpret,
    )
    # One a decay a head: no hand-on was given, and none is owed.
    return tuple(grads) + (None,) * (residuals[-1] is None)


_pass.defvjp(_pass_fwd, _pass_bwd)


def chunk_pass(q, k, from_start, to_end, weights, values, keys_seen, state,
               terms, hand_on=None):
    """`delta_scan`'s chunk-to-chunk pass by the kernels (the module's
    header): q, k [B, c, Hk, Q, Dk]; from_start, to_end [B, c, Hk, per,
    Q]; weights [B, c, Hk, per, Q, Q]; values [B, c, Hk, per, Q, Dv];
    keys_seen [B, c, Hk, per, Q, Dk]; state [B, Hk, per, Dk, Dv], float32
    -> (o [B, c, Hk, per, Q, Dv], the state after the last chunk);
    differentiable in all of them. `terms`: the bfloat16 terms a side of
    every product (`ops/bf16_terms.terms_traced_under()` where the caller
    is traced: the backward kernel is traced after it and makes the
    same). The shapes must be `kernels_apply`'s.

    `hand_on` [B, c, Hk, per, Dk], if given, is what the state that
    entered a chunk is handed on under, a KEY CHANNEL each (S_next =
    Diag(hand_on) S + Kl^T V': models/ling3.py `kda_scan`, whose decays
    sit inside the key contraction, so its f and e are folded into q
    and k and come here as ones) in place of the chunk's last f, one
    number a head; differentiable too. None (models/qwen3next.py)
    traces the kernels as they were."""
    rows, chunks, Hk, Q, Dk = q.shape
    Dv = values.shape[-1]
    if not kernels_apply(chunks * Q, Q, Dk, Dv):
        raise ValueError(
            f"{chunks} chunks of {Q} steps at widths {Dk} x {Dv} are not "
            "the delta rule's kernels' shapes"
        )
    return _pass(
        q, k, jnp.concatenate([from_start, to_end], axis=3), weights, values,
        keys_seen, state, hand_on, terms, jax.default_backend() != "tpu",
    )


# --- what a chunk owes before its state enters it -------------------------

# What a rematerialised block keeps of a layer's forward pass: the
# solve's result, which is all the backward cell reads of it.
SOLVED = "delta_solved"
# bfloat16 terms a side of the solve's products, six passes, whatever
# the caller traces under.
_SOLVE_TERMS = 3
# Key heads (pairs of systems) a turn of a cell's rolled loop.
_PAIRS_TOGETHER = 2


def sides_apply(steps: int, Q: int, Dk: int, Dv: int, per: int) -> bool:
    """Whether what a chunk owes before its state enters it (W, U, Kd,
    A) is made by the cells below: `kernels_apply`'s shapes, chunks of
    half a lane tile's steps and two value heads a key head, so that
    the two [Q, Q] systems stand side by side in one lane tile. A
    function of the shapes alone."""
    return kernels_apply(steps, Q, Dk, Dv) and 2 * Q == _LANES and per == 2


def _planes(Q):
    """Of a [Q, 2 Q] tile of two systems side by side: an entry's row,
    its column within its own system, and whether it is the second
    system's."""
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 1)
    return row, lane & (Q - 1), lane >= Q


def _blocks(x):
    """[X0 | X1] [Q, 2 Q] as blockdiag(X0, X1) [2 Q, 2 Q]: the operand a
    side-by-side tile is multiplied by, a system by its own."""
    Q = x.shape[0]
    lower = jax.lax.broadcasted_iota(jnp.int32, (2 * Q, 2 * Q), 0) >= Q
    right = jax.lax.broadcasted_iota(jnp.int32, (2 * Q, 2 * Q), 1) >= Q
    return jnp.where(lower == right, jnp.concatenate([x, x], axis=0), 0.0)


def _diagonal_blocks(x):
    """The two diagonal [Q, Q] blocks of x [2 Q, 2 Q], side by side."""
    Q = x.shape[0] // 2
    return jnp.where(_planes(Q)[2], x[Q:], x[:Q])


def _columns(row):
    """A row [1, 2 Q] of per-step scalars, a system after the other, as
    the plane [Q, 2 Q] whose entry (i, system p) is the row's p Q + i:
    what scales a system's ROWS."""
    column = _column(row)
    Q = column.shape[0] // 2
    return jnp.where(_planes(Q)[2], column[Q:], column[:Q])


def _row_sums(x):
    """A side-by-side tile's sums over each system's own columns, as a
    row [1, 2 Q], a system after the other."""
    Q = x.shape[0]
    row, column, second = _planes(Q)
    sums = jnp.where(
        second,
        jnp.sum(jnp.where(second, x, 0.0), axis=1, keepdims=True),
        jnp.sum(jnp.where(second, 0.0, x), axis=1, keepdims=True),
    )
    return jnp.sum(jnp.where(row == column, sums, 0.0), axis=0, keepdims=True)


def _inverse_side_by_side(L):
    """(I + L)^-1 of two systems side by side, L [Q, 2 Q] of which the
    parts strictly below the diagonals are read: models/qwen3next.py
    `_block_doubling`'s five levels and ten products, each ONE product
    of the side-by-side tile [Q, 2 Q] with a block-diagonal [2 Q, 2 Q]
    (the two systems never mix: the off-diagonal blocks are exact
    zeros at every level), at `_SOLVE_TERMS` a side. An entry of L that
    is exactly zero leaves the inverse's exactly zero."""
    Q = L.shape[0]
    row, column, _ = _planes(Q)

    def below(shift):
        # L's blocks below the diagonal blocks of 2^shift steps, inside
        # those of twice the size.
        return jnp.where(
            ((row >> (shift + 1)) == (column >> (shift + 1)))
            & ((row >> shift) > (column >> shift)),
            L, 0.0,
        )

    inverse = jnp.where(row == column, 1.0, 0.0) - below(0)
    for shift in range(1, Q.bit_length() - 1):
        terms = _cut(inverse, _SOLVE_TERMS)
        through = product_of_terms(
            terms, _cut(_blocks(below(shift)), _SOLVE_TERMS), _NN
        )
        inverse = inverse - product_of_terms(
            _cut(through, _SOLVE_TERMS),
            _cut(_blocks(inverse), _SOLVE_TERMS), _NN,
        )
    return inverse


def _decays(rows_ref, ends_ref, h):
    """Of a key head's two value heads side by side: (beta, a row
    [1, 2 Q]; what a step still sees of the entering state, a row; D
    [Q, 2 Q], exp(G_i - G_j) where j reaches i and zero elsewhere, its
    diagonal ones)."""
    rows = rows_ref[h]
    beta, G = rows[0:1], rows[1:2]
    ends = ends_ref[...]
    Q = G.shape[1] // 2
    row, column, _ = _planes(Q)
    reach = (column <= row) & (_columns(ends) == ends)
    decay = jnp.exp(jnp.where(reach, _columns(G) - G, -jnp.inf))
    return beta, jnp.where(ends == 0.0, jnp.exp(G), 0.0), decay


def _over_keys(lhs, k, terms):
    """lhs [M, Dk] against [k; k]^T: [lhs k^T | lhs k^T], a value head's
    columns beside the other's, at the caller's terms."""
    return product_of_terms(
        _cut(lhs, terms), _cut(jnp.concatenate([k, k], axis=0), terms), _NT
    )


def _beside(a, b, terms):
    """[a | b] in terms: two operands that meet the same third."""
    return [
        jnp.concatenate([x, y], axis=1)
        for x, y in zip(_cut(a, terms), _cut(b, terms))
    ]


def _solve_kernel(k_ref, rows_ref, ends_ref, w_ref, *, terms):
    """W of every key head of a cell: L = beta . (k k^T) . D is made
    here from k and the rows, and never leaves VMEM."""
    def pair(h):
        k = k_ref[h]
        beta, _, decay = _decays(rows_ref, ends_ref, h)
        w_ref[h] = _inverse_side_by_side(
            _columns(beta) * _over_keys(k, k, terms) * decay
        )

    _over_heads(w_ref.shape[0], pair, _PAIRS_TOGETHER)


def _steps_first(ref, h, pairs):
    """Where key head h's two value heads lie in a block [Q, pairs x 2
    Dv] that has steps before heads, as the mixer leaves v."""
    width = ref.shape[-1] // pairs
    return pl.ds(pl.multiple_of(h * width, width), width)


def _sides(w, beta, v_ref, h, pairs, terms, k, from_start):
    """(blockdiag(W . beta) in terms, [v; v'] and [f . k; f' . k] of
    the key head's two value heads [2 Q, D]): the operands of U and Kd
    and of their cotangents'."""
    both = v_ref[:, _steps_first(v_ref, h, pairs)]  # [v | v']
    Dv = both.shape[1] // 2
    values = jnp.concatenate([both[:, :Dv], both[:, Dv:]], axis=0)
    keys = _column(from_start) * jnp.concatenate([k, k], axis=0)
    return _cut(_blocks(w * beta), terms), values, keys


def _apply_kernel(w_ref, q_ref, k_ref, v_ref, rows_ref, ends_ref,
                  u_ref, kd_ref, a_ref, *, terms):
    """U = (W . beta) v and Kd = (W . beta) (f . k) of every key head of
    a cell, one product over [v | f . k], and A = (q k^T) . D."""
    pairs, Q = w_ref.shape[:2]
    Dv = u_ref.shape[-1]

    def pair(h):
        k = k_ref[h]
        beta, from_start, decay = _decays(rows_ref, ends_ref, h)
        by_beta, values, keys = _sides(
            w_ref[h], beta, v_ref, h, pairs, terms, k, from_start
        )
        weights = _over_keys(q_ref[h], k, terms) * decay  # [A | A']
        a_ref[h, 0] = weights[:, :Q]
        a_ref[h, 1] = weights[:, Q:]
        both = product_of_terms(
            by_beta, _beside(values, keys, terms), _NN
        )  # [U | Kd]
        u_ref[h] = both[:, :Dv].reshape(u_ref.shape[1:])
        kd_ref[h] = both[:, Dv:].reshape(kd_ref.shape[1:])

    _over_heads(pairs, pair, _PAIRS_TOGETHER)


def _sides_backward_kernel(w_ref, q_ref, k_ref, v_ref, rows_ref, ends_ref,
                           du_ref, dkd_ref, da_ref,
                           dq_ref, dk_ref, dv_ref, drows_ref, *, terms):
    """The cotangents of everything the two forward cells read, from
    those of U, Kd and A, a key head at a time: d(W . beta) = the
    diagonal blocks of [dU | dKd] [v | f . k]^T; [dv | d(f . k)] = (W .
    beta)^T [dU | dKd]; dW = d(W . beta) . beta; dL = -(W^T dW W^T)
    strictly below the diagonal, at the solve's terms; then dq, dk and
    the rows' (beta's, and G's through D and f) from dL and dA."""
    pairs, Q = w_ref.shape[:2]
    Dv = du_ref.shape[-1]
    row, column, _ = _planes(Q)

    def pair(h):
        w, q, k = w_ref[h], q_ref[h], k_ref[h]
        beta, from_start, decay = _decays(rows_ref, ends_ref, h)
        by_beta, values, keys = _sides(
            w, beta, v_ref, h, pairs, terms, k, from_start
        )
        cotangents = _beside(
            du_ref[h].reshape(2 * Q, Dv), dkd_ref[h].reshape(keys.shape),
            terms,
        )  # [dU | dKd]
        back = product_of_terms(by_beta, cotangents, _TN)
        d_values, d_keys = back[:, :Dv], back[:, Dv:]
        d_by_beta = _diagonal_blocks(product_of_terms(
            cotangents, _beside(values, keys, terms), _NT
        ))
        d_beta = jnp.sum(w * d_by_beta, axis=0, keepdims=True)
        solved = _cut(_blocks(w), _SOLVE_TERMS)
        through = product_of_terms(
            _cut(d_by_beta * beta, _SOLVE_TERMS), solved, _NT
        )  # dW W^T
        d_lower = jnp.where(column < row, -_diagonal_blocks(product_of_terms(
            solved, _cut(_blocks(through), _SOLVE_TERMS), _TN
        )), 0.0)
        dv_ref[:, _steps_first(dv_ref, h, pairs)] = jnp.concatenate(
            [d_values[:Q], d_values[Q:]], axis=1
        )
        # f . k was made here: its cotangent is k's and f's.
        twice = jnp.concatenate([k, k], axis=0)
        d_from_start = _row(jnp.sum(d_keys * twice, axis=1, keepdims=True))
        d_k = _column(from_start) * d_keys
        d_k = d_k[:Q] + d_k[Q:]
        # [k; q] [k; k]^T again: [k k^T | k k^T] over [q k^T | q k^T].
        stacked = [
            jnp.concatenate([a, b], axis=0)
            for a, b in zip(_cut(k, terms), _cut(q, terms))
        ]
        twice_terms = _cut(twice, terms)
        products = product_of_terms(stacked, twice_terms, _NT)
        between_keys, reads = products[:Q], products[Q:]
        d_weights = jnp.concatenate([da_ref[h, 0], da_ref[h, 1]], axis=1)
        beta_rows = _columns(beta)
        # L = beta . (k k^T) . D and A = (q k^T) . D.
        d_beta = d_beta + _row_sums(d_lower * between_keys * decay)
        through_decay = (
            d_lower * beta_rows * between_keys + d_weights * reads
        ) * decay
        d_G = (
            d_from_start * from_start + _row_sums(through_decay)
            - jnp.sum(through_decay, axis=0, keepdims=True)
        )
        d_products = _cut(jnp.concatenate(
            [d_lower * beta_rows * decay, d_weights * decay], axis=0
        ), terms)  # over [k; q]'s rows
        d_rows = product_of_terms(d_products, twice_terms, _NN)
        d_columns = product_of_terms(d_products, stacked, _TN)
        dq_ref[h] = d_rows[Q:]
        dk_ref[h] = d_k + d_rows[:Q] + d_columns[:Q] + d_columns[Q:]
        drows_ref[h] = jnp.concatenate([d_beta, d_G], axis=0)

    _over_heads(pairs, pair, _PAIRS_TOGETHER)


def _sides_call(kernel, name, operands, results, *, terms, interpret):
    """One of the three cells over the grid (row, chunk, block of key
    heads), every axis parallel: `operands` {name: array} and `results`
    {name: shape} by the names of their blocks. All are [B, c, Hk, ...]
    but a row and chunk's ends [B, c, 1, 2 Q], every head's alike, and
    v and its cotangent [B, c, Q, Hk x 2 Dv], steps before heads, of
    which a block of heads is a slice of lanes."""
    rows, chunks, Hk, Q, Dk = operands["k"].shape
    Dv = operands["v"].shape[-1] // (2 * Hk) if "v" in operands else 0
    block = next(n for n in range(min(_HEADS, Hk), 0, -1) if Hk % n == 0)

    def by_head(*tail):
        return pl.BlockSpec(
            (None, None, block) + tail,
            lambda b, c, g: (b, c, g) + (0,) * len(tail),
        )

    spec = dict(
        w=by_head(Q, 2 * Q), q=by_head(Q, Dk), k=by_head(Q, Dk),
        rows=by_head(2, 2 * Q),
        ends=pl.BlockSpec((None, None, 1, 2 * Q), lambda b, c, g: (b, c, 0, 0)),
        v=pl.BlockSpec(
            (None, None, Q, block * 2 * Dv), lambda b, c, g: (b, c, 0, g)
        ),
        u=by_head(2, Q, Dv), kd=by_head(2, Q, Dk), a=by_head(2, Q, Q),
    )
    return pl.pallas_call(
        functools.partial(kernel, terms=terms),
        out_shape=tuple(
            jax.ShapeDtypeStruct(shape, jnp.float32)
            for shape in results.values()
        ),
        grid=(rows, chunks, Hk // block),
        # A cotangent `d<x>` has x's block.
        in_specs=[spec[key.removeprefix("d")] for key in operands],
        out_specs=tuple(spec[key.removeprefix("d")] for key in results),
        interpret=interpret,
        name=name,
        **_compiler_params(interpret, "parallel"),
    )(*operands.values())


# Jitted for `_forward`'s reason.
@functools.partial(jax.jit, static_argnames=("terms", "interpret"))
def _solve(k, rows, ends, *, terms, interpret):
    Q = k.shape[3]
    return _sides_call(
        _solve_kernel, "delta_sides_solve", dict(k=k, rows=rows, ends=ends),
        dict(w=k.shape[:3] + (Q, 2 * Q)), terms=terms, interpret=interpret,
    )[0]


@functools.partial(jax.jit, static_argnames=("terms", "interpret"))
def _apply(solved, q, k, v, rows, ends, *, terms, interpret):
    Hk, Q, Dk = k.shape[2:]
    lead = k.shape[:3] + (2, Q)
    return _sides_call(
        _apply_kernel, "delta_sides_apply",
        dict(w=solved, q=q, k=k, v=v, rows=rows, ends=ends),
        dict(u=lead + (v.shape[-1] // (2 * Hk),), kd=lead + (Dk,),
             a=lead + (Q,)),
        terms=terms, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("terms", "interpret"))
def _sides_backward(solved, operands, cotangents, *, terms, interpret):
    q, k, v, rows, ends = operands
    du, dkd, da = cotangents
    return _sides_call(
        _sides_backward_kernel, "delta_sides_backward",
        dict(w=solved, q=q, k=k, v=v, rows=rows, ends=ends, du=du, dkd=dkd,
             da=da),
        dict(dq=q.shape, dk=k.shape, dv=v.shape, drows=rows.shape),
        terms=terms, interpret=interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sides_op(terms, interpret, *operands):
    return _sides_forward(terms, interpret, *operands)[0]


def _sides_forward(terms, interpret, *operands):
    static = dict(terms=terms, interpret=interpret)
    _, k, _, rows, ends = operands
    # The scope the `jax.numpy` form's solve has in models/qwen3next.py.
    with device_scope("delta_solve"):
        solved = checkpoint_name(_solve(k, rows, ends, **static), SOLVED)
    return _apply(solved, *operands, **static), (solved, operands)


def _sides_backward_rule(terms, interpret, residuals, cotangents):
    solved, operands = residuals
    grads = _sides_backward(
        solved, operands, tuple(x.astype(jnp.float32) for x in cotangents),
        terms=terms, interpret=interpret,
    )
    # A row and chunk's ends are counts: nothing is owed them.
    return tuple(grads) + (jnp.zeros_like(operands[-1]),)


_sides_op.defvjp(_sides_forward, _sides_backward_rule)


def sides_before_the_state(q, k, v, beta, G, ends, terms):
    """What a chunk owes before its state enters it (models/qwen3next.py
    `delta_scan`; the module's header): q, k [B, c, Hk, Q, Dk], heads
    before steps; v [B, c, Q, Hk, 2, Dv], steps before heads as the
    mixer leaves it (a cell reads a key head's two value heads out of
    its block's lanes, and writes v's cotangent so); beta and G (the
    log-decays' cumulative sum inside a chunk) [B, c, Hk, 2, Q]; ends
    [B, c, Q], the episodes ended in the chunk up to and including a
    step -> (weights A [B, c, Hk, 2, Q, Q], values U [B, c, Hk, 2, Q,
    Dv], keys_seen Kd [B, c, Hk, 2, Q, Dk]) as `chunk_pass` reads them;
    differentiable in all but `ends`. L, K K^T and D exist in VMEM
    alone; W, two value heads' side by side [B, c, Hk, Q, 2 Q], is the
    one residual besides the operands, named `SOLVED`. `terms` as
    `chunk_pass`'s (the solve's own are six passes whatever it says).
    The solve's cell is traced under the `device_scope` `delta_solve`.
    The shapes must be `sides_apply`'s."""
    rows, chunks, Hk, Q, Dk = q.shape
    per, Dv = v.shape[4:]
    if not sides_apply(chunks * Q, Q, Dk, Dv, per):
        raise ValueError(
            f"chunks of {Q} steps at widths {Dk} x {Dv}, {per} value heads "
            "a key head, are not the delta rule's cells' shapes"
        )
    side_by_side = (rows, chunks, Hk, 2 * Q)
    values, keys_seen, weights = _sides_op(
        terms, jax.default_backend() != "tpu", q, k,
        v.reshape(rows, chunks, Q, -1),
        jnp.stack(
            [beta.reshape(side_by_side), G.reshape(side_by_side)], axis=3
        ),
        jnp.tile(ends.astype(jnp.float32), (1, 1, 2))[:, :, None],
    )
    return weights, values, keys_seen
