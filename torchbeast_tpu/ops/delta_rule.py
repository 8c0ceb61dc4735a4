"""The gated delta rule's chunk-to-chunk pass as two Mosaic kernels, the
carried state in VMEM.

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
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbeast_tpu.ops.bf16_terms import cut_in_kernel, product_of_terms

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


def _over_heads(heads, body):
    """`body(h)` for every key head of a cell, `_TOGETHER` of them a
    turn of ONE rolled loop: a head's products wait on one another (S,
    then V', then what both feed), and a second head beside it fills
    the gaps."""
    together = next(n for n in range(_TOGETHER, 0, -1) if heads % n == 0)

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


def _compiler_params(interpret):
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
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
