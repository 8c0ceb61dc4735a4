"""Attention over `[cache; unroll]` whose scores live in VMEM.

`ops/attention.dense_transformer_attend` computes softmax(mask(q k^T /
sqrt(D))) v by building the f32 scores `[B, Hkv, G, T, K]` in HBM; at
the Mellum2 cell's widths that tensor is 1.385 GB in the layer that
reads its whole cache and every pass over it is a round trip through
HBM (PERF.md, PR 37). `fused_attend` is the same function as a
blockwise pass over the keys with a running maximum and denominator,
forward and backward under one `jax.custom_vjp`: a grid cell is one
batch row, one key/value head and one block of keys, its scores a
`[G * T, block]` tile that never leaves the chip.

**Same mathematics, same precision.** On the chip the operands of the
matmuls are bfloat16 and their sums float32 — what XLA makes of a
float32 einsum at JAX's default precision, so `q k^T`, `p v` and the
backward pass's four products are computed as the dense body's are (and
as models/moe.py `_gmm_call` states for the experts). Scores, the mask
(`BIG_NEG`), maximum, exponent and denominator are float32; `p` is cast
to the matmul's operand type for the combine. What differs from the
dense body is the order of summation over key blocks, and that `p` is
rounded before it is divided by the denominator, not after. Every key
the mask admits is summed; no block is skipped, whatever the mask says
(the mask is data: a program that skipped on it would be another
program for a full cache than for an empty one). Off the chip the
kernels are interpreted, in float32.

**Or the caller's precision** (`terms`; PR 42, PR 54). The dense body's
einsums follow the precision their caller traces under; so does this
pass. A float32 matmul on the MXU is passes over bfloat16 terms of its
operands (ops/bf16_terms.py): one term a side and one pass at the
default, two and three under `high`, three and six under `highest`.
`dense_transformer_attend` hands over the number of terms its caller's
trace states (`bf16_terms.terms_traced_under`), and at more than one
(models/nemotron3.py, models/qwen3next.py, models/lfm2.py trace at
`high`: what their attention layer writes feeds routers whose last
choice among close scores decides) the kernels read FLOAT32 tiles and
make every product themselves, from terms cut in VMEM (`cut_in_kernel`,
`product_of_terms`: ops/grouped_matmul.py's way): three passes under
`high`, as XLA's dense body would make (a dot at Mosaic's own
`highest`, which this pass asked for until PR 54, is six passes on
three terms cut again inside every dot). Each operand is cut ONCE: a
block's keys, values, `p` and `ds` when they are made, and the row
operands, which are the same over a cell's key blocks (`q`; backward
`dout` too), at the cell's first block into VMEM scratch. `p` is then
two (three) terms as any float32 operand under that precision is. A
caller at the default gets the program it always got.

**Grouped heads as rows.** A key/value head's G query heads x T steps
are the rows of one matmul against a `[block, D]` tile of keys: K and V
are read once a key/value head and never repeated. T is padded to the
f32 sublane tile (8) so that a `[G * Tp, block]` tile of scores splits
into `[G, Tp, block]` for the `[Tp, block]` slab of the mask without a
relayout. Padded rows admit no key and are dropped; every real row
must admit one (the transformer families always admit a query's own
step): a row that admits none comes out as an average over the last
block's padding too, not as the dense body's average over the K.

**Keys where the state holds them.** The kernels read k_all and v_all
time-major, `[K, B * Hkv * D]`, a cell's keys rows j of column block
b * Hkv + h: the layout of a cache in the agent state (`[M, B, Hkv,
D]`), so `[cache; k]` reaches a kernel by one pass over the cache (the
cast to the operand type, a family's rotation fused into it) and one
relayout of the result, with no transposed copy of the cache and no
copy to pad it (first built batch-major and padded: 8 ms a step more in
the Mellum2 cell, PERF.md section 6, PR 37). The keys are not padded to
a whole number of blocks: the last block's tail is zeroed in the cell,
and the mask, which is small, is padded with False instead.

**Backward.** One kernel, one pass over the key blocks: all of a
key/value head's query rows are in the cell, so a block's `dk` and `dv`
are complete when the cell ends and only `dq` is carried across blocks.
Saved from the forward pass: the operands as the kernel reads them, the
output and the rows' log-sum-exp. A REMATERIALISED caller keeps the
last two and nothing else (`KEPT_FORWARD`, the names `_fused_attend_fwd`
gives them; models/transformer.py `rematerialised` hands `nn.remat` the
policy that keeps them): its second forward then rebuilds the operands,
which the backward kernel reads, and does not call the forward kernel,
whose two results are all a block's backward pass wants of it and small
beside what making them costs (46 MB a layer in the Trinity cell
against 4.5 to 8.1 ms a call; PERF.md section 6, PR 63). The
log-sum-exp is kept NARROW, a float a row `[B, Hkv, G * Tp]`: the
kernel writes it lane-replicated, `[.., 128]`, as large as the output
at heads of 128, and the backward pass broadcasts the column to the
lanes again where it hands it to its kernel. Told
that the first n keys take no gradient (`no_grad_keys`: a cache that is
the learner's data), the kernel makes `dk`, `dv` from the block that
holds key n on and writes no row before it.

**The latent leg** (PR 41). `fused_latent_leg` is the same pass for the
cache leg of latent attention (`ops/attention.latent_cached_attend`,
the Kanana-2 cell): a group of heads' absorbed queries against ONE
joined key a slot, whose first columns are also the values; it returns
the leg's output and the rows' log-sum-exp, both differentiable, for
the caller to join with the unroll leg. Its own section, below, says
what differs: no `dk`, `dv` at all, rows that admit no slot, operands
head-major as the einsums around it make and read them.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbeast_tpu.ops.bf16_terms import cut_in_kernel, product_of_terms

BIG_NEG = -1e30

# What a rematerialised caller keeps of `fused_attend`'s forward pass
# (the module's header, "Backward"): the output as the backward kernel
# reads it, and the rows' log-sum-exp, one float a row.
KEPT_FORWARD = ("fused_attend_out", "fused_attend_lse")

# The most keys a grid cell takes. A forward cell pays for its running
# maximum and denominator (two reductions along the lanes, the rescaled
# accumulator) once a block, so it takes long blocks: at the Mellum2
# widths 1,408 keys a cell ran the full layer's forward in 4.9 ms where
# 384 took 6.2 and 128 took 11.5 (PERF.md section 6, PR 37). A backward
# cell has no reduction and four [rows, block] intermediates, and with
# `no_grad_keys` it makes dk, dv for whole blocks: 384 keys a cell.
_FORWARD_KEYS = 1536
_BACKWARD_KEYS = 512
# With float32 operands cut into terms in the cell (1,024 to 2,048
# rows, 4,351 keys, `high`; PERF.md section 6, PR 54, has the sweep): a
# forward cell of 512 keys, or of 384 where that pads them less. At
# heads of 128 the once-a-block work on [rows, 1] and [rows, D] costs a
# cell what 250 keys do, so blocks of 256, which pad 4,351 keys the
# least, ran 7.8 ms where 512 ran 6.2 and 768 to 1,536 6.3 to 7.1; at
# heads of 256 the MXU paces the cell and the padding is all that
# shows (5.2 at 256, 5.4 at 512). The backward cell, which makes dk,
# dv for whole blocks from `no_grad_keys` on, 256: 8.5 / 7.7 ms where
# 512 ran 9.4 / 8.6.
_CUT_FEWEST_FORWARD_KEYS = 384
_CUT_FORWARD_KEYS = 512
_CUT_BACKWARD_KEYS = 256
_SUBLANES = 8  # rows of a float32 tile
_LANES = 128
# Scoped VMEM a cell may use. At the Mellum2 widths (704 rows) the
# forward cell holds ~10 MB of [rows, 1408] intermediates, the backward
# cell ~5 MB of [rows, 384] ones beside 5 MB of double-buffered
# operands; the chip's default is 16 MiB of its 128.
_VMEM_LIMIT = 48 * 1024 * 1024


def _vmem_limit(d):
    """A cell's scoped VMEM for heads of `d`: the row operands (q, out,
    dout, dq, the accumulator: [rows, d]) and the key tiles grow with
    the head, the [rows, block] intermediates do not. Heads of 256
    (models/qwen3next.py: 2,048 rows a cell, float32 operands) needed
    48.4 MB in the backward cell; the chip has 128 MiB."""
    return _VMEM_LIMIT * max(1, d // _LANES)


_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(
        a, b, dims, precision=precision,
        preferred_element_type=jnp.float32,
    )


def _key_blocks(num_keys, terms):
    """(forward, backward): the keys a cell of either pass takes."""
    if terms > 1:
        return (
            key_block(
                num_keys, _CUT_FORWARD_KEYS, _CUT_FEWEST_FORWARD_KEYS
            ),
            key_block(num_keys, _CUT_BACKWARD_KEYS),
        )
    return (
        key_block(num_keys, _FORWARD_KEYS),
        key_block(num_keys, _BACKWARD_KEYS),
    )


def _operand(x, terms):
    """A tile as `_matmul` reads it: itself at one term (cast outside
    the kernel, to bfloat16 on the chip), else the bfloat16 terms of
    the float32 tile."""
    return x if terms == 1 else cut_in_kernel(x, terms)


def _matmul(a, b, terms, dims=_NN):
    """a x b over `dims`, each as `_operand` gives it: one pass, or the
    passes of its terms."""
    if terms == 1:
        return _dot(a, b, dims)
    return product_of_terms(a, b, dims)


def _keep_terms(terms_ref, x):
    """The bfloat16 terms of the float32 tile x into scratch [terms,
    ...]: a row operand, cut once a cell."""
    for i, term in enumerate(cut_in_kernel(x, terms_ref.shape[0])):
        terms_ref[i] = term


def _kept_terms(terms_ref):
    return [terms_ref[i] for i in range(terms_ref.shape[0])]


def key_block(num_keys: int, most: int, fewest: int = 2 * _LANES) -> int:
    """Keys a grid cell for `num_keys` keys: the multiple of 128 from
    `fewest` (256) to `most` that pads the keys the least, the largest
    on a tie (4,176 keys: 1,408 forward, 384 backward, both to 4,224;
    1,104: 1,152 and 384)."""
    if num_keys <= _LANES:
        return _LANES
    return min(
        range(fewest, most + 1, _LANES),
        key=lambda block: (-(-num_keys // block) * block, -block),
    )


def padded_steps(steps: int) -> int:
    """T in whole sublane tiles: the steps of a head's query rows."""
    return -(-steps // _SUBLANES) * _SUBLANES


def _padded_keys(num_keys: int, blocks) -> int:
    """The keys in whole blocks of either pass (`blocks`: forward,
    backward): what a mask is padded to."""
    return max(-(-num_keys // block) * block for block in blocks)


def _whole_keys(x, block_index, num_keys):
    """A [block, D] tile of keys or values with the rows past the last
    key zeroed: where the keys do not fill their last block the tile's
    tail is whatever memory held, and 0 x NaN is NaN in a matmul."""
    block = x.shape[0]
    if num_keys % block == 0:
        return x
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < num_keys - block_index * block, x, 0)


def _masked(s, admitted, scale, groups):
    """Masked f32 scores [G * Tp, block] of a cell from the product
    q k^T: admitted [Tp, block] int8 (the mask's slab, shared by the G
    query heads of the group)."""
    rows, block = s.shape
    s = s * scale
    s = s.reshape(groups, rows // groups, block)
    s = jnp.where((admitted != 0)[None], s, BIG_NEG)
    return s.reshape(rows, block)


def _scores(q, k, admitted, scale, groups, precision=None):
    """`_masked` scores of q [G * Tp, D] against k [block, D]."""
    return _masked(_dot(q, k, _NT, precision), admitted, scale, groups)


def _forward_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, lse_ref,
                    top_ref, den_ref, acc_ref, *kept, scale, groups,
                    num_keys, terms):
    """`kept`: at more than one term, scratch [terms, G * Tp, D] for
    the terms of q."""
    block_index = pl.program_id(2)

    @pl.when(block_index == 0)
    def _():
        # -inf, not BIG_NEG: a first block the mask excludes whole then
        # weighs nothing once a later block admits a key.
        top_ref[...] = jnp.full_like(top_ref, -jnp.inf)
        den_ref[...] = jnp.zeros_like(den_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if kept:
            _keep_terms(kept[0], q_ref[0, 0])

    k = _operand(_whole_keys(k_ref[...], block_index, num_keys), terms)
    v = _operand(_whole_keys(v_ref[...], block_index, num_keys), terms)
    q = _kept_terms(kept[0]) if kept else q_ref[0, 0]
    admitted = mask_ref[0]
    s = _masked(_matmul(q, k, terms, _NT), admitted, scale, groups)
    top = jnp.maximum(top_ref[...], s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - top)
    shrink = jnp.exp(top_ref[...] - top)
    den_ref[...] = shrink * den_ref[...] + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = shrink * acc_ref[...] + _matmul(
        _operand(p.astype(v_ref.dtype), terms), v, terms
    )
    top_ref[...] = top

    @pl.when(block_index == pl.num_programs(2) - 1)
    def _():
        out_ref[0, 0] = (acc_ref[...] / den_ref[...]).astype(out_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            top_ref[...] + jnp.log(den_ref[...]), lse_ref.shape[2:]
        )


def _backward_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, lse_ref,
                     dout_ref, dq_ref, dk_ref, dv_ref, dq_acc_ref,
                     delta_ref, *kept, scale, groups, num_keys,
                     first_block, terms):
    """`kept`: at more than one term, scratch [terms, G * Tp, D] for
    the terms of q and for those of dout."""
    block_index = pl.program_id(2)
    operand = q_ref.dtype

    @pl.when(block_index == 0)
    def _():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        # sum_k p (dp): the softmax's own term, from the output.
        delta_ref[...] = jnp.sum(
            out_ref[0, 0] * dout_ref[0, 0], axis=-1, keepdims=True
        )
        for terms_ref, x_ref in zip(kept, (q_ref, dout_ref)):
            _keep_terms(terms_ref, x_ref[0, 0])

    q = _kept_terms(kept[0]) if kept else q_ref[0, 0]
    k = _operand(_whole_keys(k_ref[...], block_index, num_keys), terms)
    v = _operand(_whole_keys(v_ref[...], block_index, num_keys), terms)
    dout = _kept_terms(kept[1]) if kept else dout_ref[0, 0].astype(operand)
    admitted = mask_ref[0]
    s = _masked(_matmul(q, k, terms, _NT), admitted, scale, groups)
    p = jnp.exp(s - lse_ref[0, 0][:, :1])
    # The 1/sqrt(D) of the scores goes on the products, in f32: ds is
    # rounded to the operand type once either way.
    ds = _operand(
        (p * (_matmul(dout, v, terms, _NT) - delta_ref[...])).astype(operand),
        terms,
    )
    dq_acc_ref[...] += _matmul(ds, k, terms)

    # dk, dv from the first block that holds a key that takes them: the
    # blocks before it are not written (nor part of dk_ref, dv_ref).
    @pl.when(block_index >= first_block)
    def _():
        dv_ref[...] = _matmul(
            _operand(p.astype(operand), terms), dout, terms, _TN
        )
        dk_ref[...] = _matmul(ds, q, terms, _TN) * scale

    @pl.when(block_index == pl.num_programs(2) - 1)
    def _():
        dq_ref[0, 0] = dq_acc_ref[...] * scale


def _row_specs(rows, d, tp, block):
    """Block specs of what a (batch row b, key/value head h, key block
    j) cell reads by its rows: the row operands [B, Hkv, rows, D], the
    mask [B, Tp, Kp] and the log-sum-exp [B, Hkv, rows, 128]."""
    by_rows = pl.BlockSpec((1, 1, rows, d), lambda b, h, j: (b, h, 0, 0))
    mask = pl.BlockSpec((1, tp, block), lambda b, h, j: (b, 0, j))
    lse = pl.BlockSpec((1, 1, rows, _LANES), lambda b, h, j: (b, h, 0, 0))
    return by_rows, mask, lse


def _key_spec(d, hkv, block, first_block=0):
    """Block spec of a key operand [K, B * Hkv * D]: a cell's keys are
    a [block, D] tile, rows j of column block b * Hkv + h; with
    `first_block`, of an array that starts at that block."""
    return pl.BlockSpec(
        (block, d),
        lambda b, h, j: (jnp.maximum(j - first_block, 0), b * hkv + h),
    )


def _compiler_params(interpret, vmem_limit=None):
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit or _VMEM_LIMIT,
        )
    }


def _at_one_pass(kernel_call):
    """`kernel_call` traced under no matmul precision, whatever its
    caller traces under: the kernels' dots are on bfloat16 operands on
    the chip, one pass each (three passes are three such dots, `_matmul`),
    and Mosaic refuses a dot at `high` (models/nemotron3.py traces
    under it; a rematerialised block's forward rule is traced outside
    whatever narrower context its block set)."""
    def call(*operands):
        with jax.default_matmul_precision(None):
            return kernel_call(*operands)

    return call


def _kept_rows(rows, d, terms, operands):
    """Scratch for the bfloat16 terms of `operands` row operands
    [rows, d], cut once a cell; none at one term."""
    if terms == 1:
        return []
    return [pltpu.VMEM((terms, rows, d), jnp.bfloat16)] * operands


def _forward_call(q, k, v, mask, groups, interpret, terms, scale):
    """q [B, Hkv, G * Tp, D]; k, v [K, B * Hkv * D]; mask [B, Tp, Kp]
    int8 -> (out f32 like q, lse f32 [B, Hkv, G * Tp, 128]). `scale`:
    what the scores are multiplied by, the true head's D^-0.5
    (`fused_attend` pads a narrow one)."""
    b, hkv, rows, d = q.shape
    num_keys = k.shape[0]
    block = _key_blocks(num_keys, terms)[0]
    by_rows, mask_spec, lse_spec = _row_specs(rows, d, mask.shape[1], block)
    by_keys = _key_spec(d, hkv, block)
    return _at_one_pass(pl.pallas_call(
        functools.partial(
            _forward_kernel, scale=scale, groups=groups,
            num_keys=num_keys, terms=terms,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rows, _LANES), jnp.float32),
        ),
        grid=(b, hkv, pl.cdiv(num_keys, block)),
        in_specs=[by_rows, by_keys, by_keys, mask_spec],
        out_specs=(by_rows, lse_spec),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ] + _kept_rows(rows, d, terms, 1),
        interpret=interpret,
        name="fused_attend_forward",
        **_compiler_params(interpret, _vmem_limit(d)),
    ))(q, k, v, mask)


def _backward_call(q, k, v, mask, out, lse, dout, groups, first_block,
                   interpret, terms, scale):
    """The forward's operands, its two results and dout like out ->
    (dq like q, dk, dv like k from block `first_block` on), all f32."""
    b, hkv, rows, d = q.shape
    num_keys = k.shape[0]
    block = _key_blocks(num_keys, terms)[1]
    by_rows, mask_spec, lse_spec = _row_specs(rows, d, mask.shape[1], block)
    by_keys = _key_spec(d, hkv, block)
    by_later_keys = _key_spec(d, hkv, block, first_block)
    grads = jax.ShapeDtypeStruct(
        (num_keys - first_block * block, k.shape[1]), jnp.float32
    )
    return _at_one_pass(pl.pallas_call(
        functools.partial(
            _backward_kernel, scale=scale, groups=groups,
            num_keys=num_keys, first_block=first_block, terms=terms,
        ),
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32), grads, grads),
        grid=(b, hkv, pl.cdiv(num_keys, block)),
        in_specs=[
            by_rows, by_keys, by_keys, mask_spec, by_rows, lse_spec, by_rows
        ],
        out_specs=(by_rows, by_later_keys, by_later_keys),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ] + _kept_rows(rows, d, terms, 2),
        interpret=interpret,
        name="fused_attend_backward",
        **_compiler_params(interpret, _vmem_limit(d)),
    ))(q, k, v, mask, out, lse, dout)


def _as_rows(x, hkv, tp):
    """[B, T, H, D] -> [B, Hkv, G * Tp, D]: a key/value head's query
    heads one after the other, each padded to Tp steps."""
    b, t, h, d = x.shape
    x = x.reshape(b, t, hkv, h // hkv, d).transpose(0, 2, 3, 1, 4)
    x = jnp.pad(x, ((0, 0),) * 3 + ((0, tp - t), (0, 0)))
    return x.reshape(b, hkv, -1, d)


def _from_rows(x, t):
    """`_as_rows` undone: [B, Hkv, G * Tp, D] -> [B, T, H, D]."""
    b, hkv, rows, d = x.shape
    tp = padded_steps(t)
    x = x.reshape(b, hkv, rows // tp, tp, d)[:, :, :, :t]
    return x.transpose(0, 3, 1, 2, 4).reshape(b, t, -1, d)


def _as_keys(x):
    """[B, K, Hkv, D] -> [K, B * Hkv * D]: time-major, as the state
    holds a cache, so that `[cache; k]` reaches the kernel by one pass
    over the cache (a cast, the family's rotation fused into it) and
    not by a transposed copy of it; not padded, for the same reason."""
    return x.transpose(1, 0, 2, 3).reshape(x.shape[1], -1)


def _from_keys(x, like, no_grad_keys):
    """A gradient [K - first, B * Hkv * D] of the keys from `first` on
    as one of all K like `like` [B, K, Hkv, 0]: zeros before
    `no_grad_keys`."""
    b, num_keys, hkv, _ = like.shape
    first = num_keys - x.shape[0]
    x = x.reshape(x.shape[0], b, hkv, -1).transpose(1, 0, 2, 3)
    if no_grad_keys:
        takes = jnp.arange(first, num_keys) >= no_grad_keys
        x = jnp.where(takes[None, :, None, None], x, 0)
    x = jnp.pad(x, ((0, 0), (first, 0), (0, 0), (0, 0)))
    return x.astype(like.dtype)


def _operands(q, k_all, v_all, mask, on_chip, terms):
    """The four operands as the kernels read them: bfloat16 at one term
    on the chip, else float32 (for the cells to cut, or interpreted);
    the mask padded with False to a whole number of either pass's
    blocks."""
    t, hkv = q.shape[1], k_all.shape[2]
    num_keys = k_all.shape[1]
    tp = padded_steps(t)
    kp = _padded_keys(num_keys, _key_blocks(num_keys, terms))
    operand = jnp.bfloat16 if on_chip and terms == 1 else jnp.float32
    return (
        _as_rows(q.astype(operand), hkv, tp),
        _as_keys(k_all.astype(operand)),
        _as_keys(v_all.astype(operand)),
        jnp.pad(
            mask.astype(jnp.int8),
            ((0, 0), (0, tp - t), (0, kp - num_keys)),
        ),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused_attend(q, k_all, v_all, mask, no_grad_keys, on_chip, terms,
                  scale):
    return _fused_attend_fwd(
        q, k_all, v_all, mask, no_grad_keys, on_chip, terms, scale
    )[0]


def _fused_attend_fwd(q, k_all, v_all, mask, no_grad_keys, on_chip, terms,
                      scale):
    operands = _operands(q, k_all, v_all, mask, on_chip, terms)
    out, lse = _forward_call(
        *operands, q.shape[2] // k_all.shape[2], not on_chip, terms, scale
    )
    # Named BEFORE anything reads them: under a policy that keeps the
    # names the second forward of a rematerialised caller then has no
    # use for the kernel. The log-sum-exp as the one float a row it is
    # ([B, Hkv, G * Tp]: a last axis of 1 would be padded to the lanes
    # again in HBM), and WHEN the column is cut left to the scheduler:
    # an `optimization_barrier` that ties the cut to `out` or to the
    # result cost the Trinity cell 0.2% / 1.3% of its rate and 50 / 59
    # MB at the peak on the chip, though the compile for a described
    # v5e read 0.3 GiB better with it (PERF.md section 6, PR 63).
    out = checkpoint_name(out, KEPT_FORWARD[0])
    lse = checkpoint_name(lse[..., 0], KEPT_FORWARD[1])
    result = _from_rows(out, q.shape[1]).astype(v_all.dtype)
    # Empty carriers of what the gradients are shaped and typed like.
    like = tuple(
        jnp.zeros(x.shape[:-1] + (0,), x.dtype) for x in (q, k_all, v_all)
    )
    return result, (operands, out, lse, like)


def _fused_attend_bwd(no_grad_keys, on_chip, terms, scale, residuals,
                      dresult):
    operands, out, lse, like = residuals
    q_rows, keys, _, mask = operands
    hkv, tp = q_rows.shape[1], mask.shape[1]
    dq, dk, dv = _backward_call(
        *operands, out,
        jnp.broadcast_to(lse[..., None], lse.shape + (_LANES,)),
        _as_rows(dresult.astype(jnp.float32), hkv, tp),
        q_rows.shape[2] // tp,
        no_grad_keys // _key_blocks(keys.shape[0], terms)[1],
        not on_chip, terms, scale,
    )
    return (
        _from_rows(dq, like[0].shape[1]).astype(like[0].dtype),
        _from_keys(dk, like[1], no_grad_keys),
        _from_keys(dv, like[2], no_grad_keys),
        None,
    )


_fused_attend.defvjp(_fused_attend_fwd, _fused_attend_bwd)


def fused_attend(q, k_all, v_all, mask, no_grad_keys=0, terms=1, scale=None):
    """softmax(mask(q k^T * scale)) v with grouped heads, as
    `ops/attention.dense_transformer_attend` with no `rel_bias`, the
    scores never in HBM (see the module's header).

    q: [B, T, H, D]; k_all, v_all: [B, K, Hkv, D] with Hkv a divisor of
    H (query head j reads key/value head j // (H // Hkv)); mask:
    [B, T, K] bool, True where the query admits the key, at least one
    key a query. Returns [B, T, H, D] in v_all's dtype. Differentiable
    in q, k_all and v_all; the first `no_grad_keys` (a Python int) of
    k_all and v_all take zeros for a gradient, and the blocks that hold
    no other key cost the backward pass neither their two products nor
    the write of their rows. `terms` (a Python int): the bfloat16 terms
    a float32 operand of the products is, forward and backward, as
    `bf16_terms.terms_traced_under` counts them: 1, one pass on
    operands cast to bfloat16 outside the kernels; 2 (`high`) or 3
    (`highest`), float32 operands cut in VMEM and a product's 3 or 6
    passes made from the terms (see the module's header). `scale` (a
    Python float, static): None, the default, is D^-0.5; else what the
    family's config states (models/granite4.py: 1/64 on heads of 64).

    Differentiated inside `jax.checkpoint` under a policy that saves
    the names `KEPT_FORWARD` (`save_only_these_names`), the forward
    kernel runs once: its output and the rows' log-sum-exp (one float a
    row) are kept for the backward kernel and the second forward makes
    the operands alone. Under no such policy the names do nothing.

    A head narrower than the 128 lanes (models/lfm2.py: 64) is padded
    to them with zero columns, which add nothing to a score and come
    out of the combine as zeros that are dropped; the scores keep the
    caller's `scale` (by default the true D^-0.5, not the padded
    width's). The MXU contracts over 64 at the cost of 128 either way;
    the padding costs half the operands' VMEM and keys' one copy."""
    d = q.shape[-1]
    narrow = -d % _LANES if d < _LANES else 0
    if narrow:
        q, k_all, v_all = (
            jnp.pad(x, ((0, 0),) * 3 + ((0, narrow),))
            for x in (q, k_all, v_all)
        )
    out = _fused_attend(
        q, k_all, v_all, mask, no_grad_keys, jax.default_backend() == "tpu",
        int(terms), d ** -0.5 if scale is None else float(scale),
    )
    return out[..., :d] if narrow else out


# --- The latent leg: H heads against ONE key a slot -----------------------
#
# Latent attention (ops/attention.latent_cached_attend) reads its cache
# in absorbed form: every head's query, carried into the latent's space,
# scores against the same `C + Dr`-wide key a slot (the latent and the
# placed rope key side by side), and the weights combine the latents
# themselves, the key's first C columns. So a cell takes a group of
# heads x Tp steps as the rows of one matmul against a `[block, C + Dr]`
# tile, and the combine reads the SAME tile: one read of the cache a
# block. The rope part is padded to whole lane tiles with zero columns
# (64 -> 128: a 640-wide key for Kanana-2's 576); a contraction of 576
# costs the MXU what 640 does. The key's two parts reach a cell apart
# and in float32, as [block, .] tiles of the cache batch-major ([B, M,
# C]: ONE transposing copy of the latents by XLA), and the cell casts
# them side by side into one operand. Cast, joined and laid out
# time-major ([M, B * 640], PR 37's layout) by XLA they cost five
# passes over the cache a call, 3 ms beside 12 of kernels: a float32
# cache of [M, B, 1, C] is rows of 128 in the state, and tiles of 8
# slots x 128 have to be made of it either way.
#
# Operands as their producers hold them (PERF.md section 6, PR 41: the
# kernels ran at 85-94% of the MXU's pace from the first, and the XLA
# passes that cast, padded and relaid their operands cost as much
# again). The queries come HEAD-major and in float32, [H, B, Tp, .],
# the steps padded to the sublane tile by their producer: the latent
# part is the absorb einsum's own output (a matmul batched over heads
# puts the heads first), the rope part a small array beside it, and a
# cell casts and joins the two in VMEM once. The output leaves the
# same way, which is how the lift (batched over heads again) reads it,
# and so do its cotangent and the queries' gradients, two outputs. The
# softmax's own term (delta = sum out dout) is made in the cell.
#
# The leg is one of two of a softmax, so the forward returns its output
# normalised WITHIN the leg and the rows' log-sum-exp, and both take a
# cotangent. A row that admits no slot (every row of a learner cell's
# empty caches, and the padded steps) comes out finite: an average of
# the latents and a log-sum-exp of BIG_NEG, which the join weighs by
# exp(BIG_NEG - top) = 0; its scores are the mask's constant, so its
# gradient is zeros whatever its cotangents (the cell drops them: p is
# 1 there, not 0). The cache is data: the backward kernel makes the
# queries' gradients alone.

# Heads a cell, keys a forward / backward cell (PERF.md section 6, PR 41).
_LATENT_HEADS = 16
# And query rows (heads x padded steps) a cell, at most: an unroll of
# 256 steps (models/ling3.py) at 16 heads is 4,096 rows, whose four
# double-buffered [rows, 512] float32 blocks beside the scores are 99.75
# MB of the backward cell's 96; at 8 heads they fit. An unroll of 81
# steps (96 padded: models/kanana2.py, models/xing4.py) keeps its 16.
_LATENT_ROWS = 2048
_LATENT_FORWARD_KEYS = 1024
_LATENT_BACKWARD_KEYS = 1024
_LATENT_VMEM_LIMIT = 96 * 1024 * 1024
# One bf16 pass whatever the caller traces under: a family that traces
# at `high` (models/kanana2.py) keeps this leg at the default.
_ONE_PASS = jax.lax.Precision.DEFAULT


def _join_queries(q_ref, q_latent_ref, q_rope_ref):
    """The cell's queries [heads, Tp, .] in two float32 parts as ONE
    [heads * Tp, C + Wr] operand of the matmuls' type."""
    rows, latent = q_ref.shape[0], q_latent_ref.shape[-1]
    q_ref[:, :latent] = q_latent_ref[...].reshape(rows, latent).astype(
        q_ref.dtype
    )
    q_ref[:, latent:] = q_rope_ref[...].reshape(rows, -1).astype(q_ref.dtype)


def _join_keys(k_ref, k_latent_ref, k_rope_ref, block_index, num_keys):
    """The block's keys [block, .] in two float32 parts as ONE [block,
    C + Wr] operand of the matmuls' type, the rows past the last slot
    zeroed (`_whole_keys`)."""
    latent = k_latent_ref.shape[-1]
    for part, columns in (
        (k_latent_ref, slice(0, latent)), (k_rope_ref, slice(latent, None))
    ):
        k_ref[:, columns] = _whole_keys(
            part[...], block_index, num_keys
        ).astype(k_ref.dtype)
    return k_ref[...]


def _latent_forward_kernel(q_latent_ref, q_rope_ref, k_latent_ref,
                           k_rope_ref, mask_ref, out_ref, lse_ref, q_ref,
                           k_ref, top_ref, den_ref, acc_ref, *, scale,
                           groups, num_keys):
    block_index = pl.program_id(2)
    latent = out_ref.shape[-1]

    @pl.when(block_index == 0)
    def _():
        _join_queries(q_ref, q_latent_ref, q_rope_ref)
        top_ref[...] = jnp.full_like(top_ref, -jnp.inf)
        den_ref[...] = jnp.zeros_like(den_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = _join_keys(k_ref, k_latent_ref, k_rope_ref, block_index, num_keys)
    s = _scores(q_ref[...], k, mask_ref[0], scale, groups, _ONE_PASS)
    top = jnp.maximum(top_ref[...], s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - top)
    shrink = jnp.exp(top_ref[...] - top)
    den_ref[...] = shrink * den_ref[...] + p.sum(axis=-1, keepdims=True)
    # The latent is both key and value.
    acc_ref[...] = shrink * acc_ref[...] + _dot(
        p.astype(k.dtype), k[:, :latent], precision=_ONE_PASS
    )
    top_ref[...] = top

    @pl.when(block_index == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = (acc_ref[...] / den_ref[...]).reshape(out_ref.shape)
        lse_ref[...] = jnp.broadcast_to(
            top_ref[...] + jnp.log(den_ref[...]), (q_ref.shape[0], _LANES)
        ).reshape(lse_ref.shape)


def _latent_backward_kernel(q_latent_ref, q_rope_ref, k_latent_ref,
                            k_rope_ref, mask_ref, lse_ref, dlse_ref, out_ref,
                            dout_ref, dq_latent_ref, dq_rope_ref, q_ref,
                            k_ref, dout_cast_ref, lse_row_ref, shift_ref,
                            dq_ref, *, scale, groups, num_keys):
    block_index = pl.program_id(2)
    rows, latent = dout_cast_ref.shape

    @pl.when(block_index == 0)
    def _():
        _join_queries(q_ref, q_latent_ref, q_rope_ref)
        # ds = p (dP - delta + dlse): `shift` is delta - dlse, a row
        # each, delta = sum_k p dP from the output. A row that admitted
        # no slot takes no gradient: its cotangents are dropped here.
        lse = lse_ref[...].reshape(rows, _LANES)[:, :1]
        dlse = dlse_ref[...].reshape(rows, _LANES)[:, :1]
        live = lse > BIG_NEG / 2
        dout = dout_ref[...].reshape(rows, latent)
        delta = jnp.sum(
            out_ref[...].reshape(rows, latent) * dout, axis=-1, keepdims=True
        )
        lse_row_ref[...] = lse
        shift_ref[...] = jnp.where(live, delta - dlse, 0.0)
        dout_cast_ref[...] = jnp.where(live, dout, 0.0).astype(
            dout_cast_ref.dtype
        )
        dq_ref[...] = jnp.zeros_like(dq_ref)

    q = q_ref[...]
    k = _join_keys(k_ref, k_latent_ref, k_rope_ref, block_index, num_keys)
    s = _scores(q, k, mask_ref[0], scale, groups, _ONE_PASS)
    p = jnp.exp(s - lse_row_ref[...])
    dp = _dot(dout_cast_ref[...], k[:, :latent], _NT, _ONE_PASS)
    ds = (p * (dp - shift_ref[...])).astype(q.dtype)
    dq_ref[...] += _dot(ds, k, precision=_ONE_PASS)

    @pl.when(block_index == pl.num_programs(2) - 1)
    def _():
        dq = dq_ref[...] * scale
        dq_latent_ref[...] = dq[:, :latent].reshape(dq_latent_ref.shape)
        dq_rope_ref[...] = dq[:, latent:].reshape(dq_rope_ref.shape)


def _latent_cells(q_latent, q_rope, k_latent, most_keys):
    """How a pass divides q_latent [H, B, Tp, C], q_rope [.., Wr] and
    the keys [B, M, .] into (batch row b, head group g, key block j)
    cells: (heads a cell, its rows, keys a block, the grid, block
    specs). The specs, in order: what is shaped like the queries'
    latent part (a cell's is [heads, Tp, C]), like their rope part, the
    key's two parts (a cell's are rows j of batch row b), the mask
    [B, Tp, Kp] and a row statistic [H, B, Tp, 128]."""
    h, b, tp, latent = q_latent.shape
    rope, num_keys = q_rope.shape[-1], k_latent.shape[1]
    # The largest divisor of the heads up to `_LATENT_HEADS` whose rows
    # are `_LATENT_ROWS` at most.
    heads = max(
        n for n in range(1, min(h, _LATENT_HEADS) + 1)
        if h % n == 0 and (n == 1 or n * tp <= _LATENT_ROWS)
    )
    block = key_block(num_keys, most_keys)

    def by_rows(d):
        return pl.BlockSpec(
            (heads, None, tp, d), lambda b, g, j: (g, b, 0, 0)
        )

    def by_keys(d):
        return pl.BlockSpec((None, block, d), lambda b, g, j: (b, j, 0))

    specs = (
        by_rows(latent), by_rows(rope), by_keys(latent), by_keys(rope),
        pl.BlockSpec((1, tp, block), lambda b, g, j: (b, 0, j)),
        by_rows(_LANES),
    )
    grid = (b, h // heads, pl.cdiv(num_keys, block))
    return heads, heads * tp, block, grid, specs


# Jitted of their own: a model's layers call them with the same shapes,
# and JAX then traces and lowers each kernel once a program, not once a
# layer and pass (15 call sites in the Kanana-2 update: a second of
# every start's set-up on the chip's host).
_KERNEL_CALL = functools.partial(
    jax.jit, static_argnames=("scale", "operand", "interpret")
)


@_KERNEL_CALL
def _latent_forward_call(q_latent, q_rope, k_latent, k_rope, mask, scale,
                         operand, interpret):
    """q_latent [H, B, Tp, C] and q_rope [.., Wr]; k_latent [B, M, C]
    and k_rope [B, M, Wr]; all f32, cast to `operand` in the cells;
    mask [B, Tp, Kp] int8 -> (out f32 like q_latent, lse f32 [.., 128])."""
    heads, rows, block, grid, specs = _latent_cells(
        q_latent, q_rope, k_latent, _LATENT_FORWARD_KEYS
    )
    by_latent, by_rope, keys_latent, keys_rope, mask_spec, by_stat = specs
    latent, rope = q_latent.shape[-1], q_rope.shape[-1]
    return pl.pallas_call(
        functools.partial(
            _latent_forward_kernel, scale=scale, groups=heads,
            num_keys=k_latent.shape[1],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q_latent.shape, jnp.float32),
            jax.ShapeDtypeStruct(
                q_latent.shape[:-1] + (_LANES,), jnp.float32
            ),
        ),
        grid=grid,
        in_specs=[by_latent, by_rope, keys_latent, keys_rope, mask_spec],
        out_specs=(by_latent, by_stat),
        scratch_shapes=[
            pltpu.VMEM((rows, latent + rope), operand),
            pltpu.VMEM((block, latent + rope), operand),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, latent), jnp.float32),
        ],
        interpret=interpret,
        name="fused_latent_leg_forward",
        **_compiler_params(interpret, _LATENT_VMEM_LIMIT),
    )(q_latent, q_rope, k_latent, k_rope, mask)


@_KERNEL_CALL
def _latent_backward_call(q_latent, q_rope, k_latent, k_rope, mask, lse,
                          dlse, out, dout, scale, operand, interpret):
    """The forward's operands and results, dlse like lse and dout like
    out -> (dq_latent, dq_rope), f32 like the queries' two parts."""
    heads, rows, block, grid, specs = _latent_cells(
        q_latent, q_rope, k_latent, _LATENT_BACKWARD_KEYS
    )
    by_latent, by_rope, keys_latent, keys_rope, mask_spec, by_stat = specs
    latent, rope = q_latent.shape[-1], q_rope.shape[-1]
    return pl.pallas_call(
        functools.partial(
            _latent_backward_kernel, scale=scale, groups=heads,
            num_keys=k_latent.shape[1],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q_latent.shape, jnp.float32),
            jax.ShapeDtypeStruct(q_rope.shape, jnp.float32),
        ),
        grid=grid,
        in_specs=[
            by_latent, by_rope, keys_latent, keys_rope, mask_spec, by_stat,
            by_stat, by_latent, by_latent,
        ],
        out_specs=(by_latent, by_rope),
        scratch_shapes=[
            pltpu.VMEM((rows, latent + rope), operand),
            pltpu.VMEM((block, latent + rope), operand),
            pltpu.VMEM((rows, latent), operand),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, latent + rope), jnp.float32),
        ],
        interpret=interpret,
        name="fused_latent_leg_backward",
        **_compiler_params(interpret, _LATENT_VMEM_LIMIT),
    )(q_latent, q_rope, k_latent, k_rope, mask, lse, dlse, out, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_latent_leg(q_latent, q_rope, k_latent, k_rope, mask, scale,
                      on_chip):
    return _fused_latent_leg_fwd(
        q_latent, q_rope, k_latent, k_rope, mask, scale, on_chip
    )[0]


def _latent_operand(on_chip):
    return (jnp.bfloat16 if on_chip else jnp.float32), not on_chip


def _fused_latent_leg_fwd(q_latent, q_rope, k_latent, k_rope, mask, scale,
                          on_chip):
    tp, num_keys = q_latent.shape[2], k_latent.shape[1]
    kp = _padded_keys(num_keys, (
        key_block(num_keys, _LATENT_FORWARD_KEYS),
        key_block(num_keys, _LATENT_BACKWARD_KEYS),
    ))
    operands = tuple(
        x.astype(jnp.float32) for x in (q_latent, q_rope, k_latent, k_rope)
    ) + (
        jnp.pad(
            mask.astype(jnp.int8),
            ((0, 0), (0, tp - mask.shape[1]), (0, kp - num_keys)),
        ),
    )
    out, lse = _latent_forward_call(
        *operands, scale, *_latent_operand(on_chip)
    )
    # Empty carriers of what the gradients are typed like.
    like = (jnp.zeros((0,), q_latent.dtype), jnp.zeros((0,), q_rope.dtype))
    return (out, lse[..., 0]), (operands, out, lse, like)


def _fused_latent_leg_bwd(scale, on_chip, residuals, cotangents):
    operands, out, lse, like = residuals
    dout, dlse = cotangents
    dq_latent, dq_rope = _latent_backward_call(
        *operands, lse,
        jnp.broadcast_to(dlse.astype(jnp.float32)[..., None], lse.shape),
        out, dout.astype(jnp.float32), scale, *_latent_operand(on_chip),
    )
    return (
        dq_latent.astype(like[0].dtype), dq_rope.astype(like[1].dtype),
        None, None, None,
    )


_fused_latent_leg.defvjp(_fused_latent_leg_fwd, _fused_latent_leg_bwd)


def fused_latent_leg(q_latent, q_rope, latent, rope, mask, scale):
    """The cache leg of latent attention with its scores in VMEM.

    The absorbed queries HEAD-major, their steps padded to `padded_
    steps(T)` (zeros) by whoever makes them: q_latent [H, B, Tp, C], the
    part that scores against the latents, and q_rope [H, B, Tp, Dr];
    latent [M, B, C] and rope [M, B, Dr], a cache AS THE STATE HOLDS IT
    (time-major), the rope keys placed; mask [B, T, M] bool; scale, a
    Python float, on the scores. Returns (out [H, B, Tp, C] f32, the
    softmax over the M slots alone applied to the latents, and lse
    [H, B, Tp] f32, the log-sum-exp of the rows' masked, scaled
    scores): what a second leg needs to join this one in one softmax.
    Differentiable in the queries, through both results; the cache is
    data and takes no gradient. A row that admits no slot (the padded
    steps among them) gets a finite output, BIG_NEG for a log-sum-exp
    and zeros for a gradient.

    The cache reaches the kernels in float32 and batch-major, as
    `[block, C]` and `[block, Wr]` tiles of `[B, M, C]` and `[B, M, Wr]`
    (Wr: Dr padded with zero columns to whole lane tiles): ONE
    transposing copy of the latents by XLA, no cast, join or padded
    copy of them; a cell casts a block's two parts to the matmuls' type
    (bfloat16 on the chip) side by side in VMEM.
    """
    pad = ((0, 0),) * (rope.ndim - 1) + ((0, -rope.shape[-1] % _LANES),)
    return _fused_latent_leg(
        q_latent, jnp.pad(q_rope, ((0, 0),) + pad),
        jax.lax.stop_gradient(latent.transpose(1, 0, 2)),
        jax.lax.stop_gradient(jnp.pad(rope, pad).transpose(1, 0, 2)),
        mask, float(scale), jax.default_backend() == "tpu",
    )
