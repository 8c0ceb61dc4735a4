"""The residual streams' maps, pre-sum and mix as Mosaic kernels: one
pass over a sublayer's streams a stage and direction.

models/xing4.py holds a token as n = 4 residual streams X [n, tokens,
d] float32 (57 KB a token at the published widths) and wraps every
sublayer F in

    m   = vec(X) Phi / sqrt(mean(vec(X)^2) + eps)          [24]
    H_pre = sigmoid(a m_pre + b_pre),   u = sum_i H_pre[i] X[i]
    y   = F(u),   X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

Written as `jax.numpy`, XLA runs that as a fusion a map entry: `Phi`'s
four products and the flat RMS each read the streams, the mix's
backward is sixteen dH_res[i, j] = sum_d dX'[i] X[j] and four dH_post a
sublayer, each a pass of its own over two streams (PERF.md section 5,
PR 59: 65 ms of a 456 ms step where the bytes owe 11). Here a cell is a
block of tokens with each token's whole [n, d] row in VMEM, so that a
token's maps and its pre-sum come from one load:

`maps_and_pre` (forward): the 24 products on the MXU, the sum of
squares, the RMS, the pre logits, H_pre and u from one read of X;
writes u [tokens, d] and the 24 normalised products m. (backward): from
one read of X, du, the streams' incoming cotangent and m's cotangent,
dX written once and dPhi accumulated over the token blocks in float32.
H_post's sigmoid, the clipped exponent and the Sinkhorn steps stay the
caller's `jax.numpy`: they are [.., tokens] arrays, not streams.

`mix` (backward): from one read of dX', X, y and the maps, dX[j] =
sum_i H_res[i, j] dX'[i], dy = sum_i H_post[i] dX'[i], the sixteen
dH_res and four dH_post as lane reductions of the loaded rows. Its
forward is the `jax.numpy` expression (XLA's one fusion).

**Same arithmetic.** Float32 throughout. `Phi`'s products (forward and
both backward ones) are what `Precision.HIGHEST` states: three bfloat16
terms a side cut after the tile is loaded, six one-pass products summed
in float32 (`ops/bf16_terms.py` `product_in_kernel`). The flat RMS is
over all n d values of a token. What differs from the `jax.numpy` body
is the order of the sums.

**Layouts.** The streams' tiles hold tokens on the sublanes and d on
the lanes. A product with X as the MXU's latched operand comes out with
the tokens on the LANES ([24 -> 32 rows, tokens]), `Phi` is held
transposed ([n, 32, d]), and a cell turns its [32, 128] of products to
[128 tokens, 128] once (and its cotangent back), so that every
per-token scalar is a column that broadcasts along the lanes. The small
arrays cross HBM as [tokens, 128] tiles; the caller's [24, tokens] is a
transpose of 1.3 MB outside.

**Shapes.** `kernels_apply`: float32 streams, d whole lane tiles, a
cell's rows within the VMEM the kernels ask for. Anything else keeps
the caller's `jax.numpy` body. Tokens need be no whole blocks: the last
block's rows past the end are read as whatever the buffer holds and
written nowhere; the one sum over tokens (dPhi) masks them. Fewer
tokens than a block (acting, T = 1) are padded to one. The kernels
take a token a row, [n, tokens, d], and models/xing4.py holds its
streams so between blocks (`Streams`): handed [n, B, T, d] with an
unroll of 81 steps, which the chip tiles as 88 rows, every call laid
the streams out again on the way in and out, 20 ms a step (PERF.md
section 6, PR 60).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbeast_tpu.ops.bf16_terms import product_in_kernel

_LANES = 128
_TILE = 8  # tokens of a float32 sublane tile
# Tokens of a cell. The maps' kernels turn a [rows, 128] tile of
# products over, so their block is the lane tile; the mix's backward has
# no product and takes the block that divides a [81, 32] batch.
MAP_TOKENS = 128
MIX_TOKENS = 32
# `Phi`'s 24 columns as rows of whole bfloat16 sublane tiles.
_ROWS = 32
_TERMS = 3  # Precision.HIGHEST: six passes
_VMEM_LIMIT = 100 * 1024 * 1024
# A cell's largest tiles: the maps' backward holds three stream blocks
# and du, double-buffered.
_STREAM_BUDGET = 64 * 1024 * 1024


def kernels_apply(streams, d, dtype) -> bool:
    """Whether a sublayer's maps, pre-sum and mix run as these kernels:
    float32 streams whose width is whole lane tiles and whose token rows
    fit the cells. A function of the shapes alone (the published 4 x
    3584 is; tier-1's toy width 48 is not)."""
    row_bytes = 4 * streams * d
    return (
        dtype == jnp.float32
        and d % _LANES == 0
        and streams * (streams + 2) <= _ROWS
        and 2 * (3 * row_bytes + 4 * d) * MAP_TOKENS <= _STREAM_BUDGET
    )


def _compiler_params(interpret, semantics):
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=(semantics,),
            vmem_limit_bytes=_VMEM_LIMIT,
        )
    }


def _widest(d, most):
    """The widest run of whole lane tiles, `most` lanes at most, that
    divides d."""
    return next(w for w in range(most, 0, -_LANES) if d % w == 0)


def _chunk(d):
    """Lanes of d a product's operand tiles take at a time."""
    return _widest(d, 512)


def _slab(d):
    """Lanes of d a turn of the vector unit's loops takes: wide enough
    that a turn's work hides the loop's own (at a lane tile a turn the
    mix's backward ran at 60% of its bytes' pace)."""
    return _widest(d, 1024)


def _lane_tiles(slab):
    """A [rows, slab] value's lane tiles, [rows, 128] each."""
    return [slab[:, at : at + _LANES] for at in range(0, slab.shape[1], _LANES)]


def _over_lanes(d, width, body, carry=None):
    """`body(lanes, carry)` for every `width` lanes of d in turn, as ONE
    traced body in a loop: unrolled in Python the kernels' bodies took
    the host 26 s of every start to trace and lower (PERF.md section 6,
    PR 60)."""
    def step(k, carry):
        return body(pl.ds(pl.multiple_of(k * width, _LANES), width), carry)

    return jax.lax.fori_loop(0, d // width, step, carry)


def _column(tile, k):
    """Lane k of a [rows, 128] tile as [rows, 1]: a token's scalar,
    which broadcasts along the lanes of its row."""
    return tile[:, k : k + 1]


def _lane_sums(tiles):
    """[8, 128] tiles -> one [8, 128] tile with tile k's sum over its
    lanes in lane k: a group of tokens' scalars, a token a row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _LANES), 1)
    out = jnp.zeros((_TILE, _LANES), jnp.float32)
    for k, tile in enumerate(tiles):
        out = jnp.where(lane == k, jnp.sum(tile, axis=1, keepdims=True), out)
    return out


# ---------------------------------------------------------------- the mix


def _mix_backward_kernel(dxp_ref, x_ref, y_ref, h_ref, dx_ref, dy_ref,
                         dh_ref):
    n, block, d = x_ref.shape
    zero = jnp.zeros((_TILE, _LANES), jnp.float32)

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * _TILE, _TILE), _TILE)
        h = h_ref[rows, :]
        h_res = [
            [_column(h, i * n + j) for j in range(n)] for i in range(n)
        ]
        h_post = [_column(h, n * n + i) for i in range(n)]

        def tile(lanes, sums):
            dxp = [dxp_ref[i, rows, lanes] for i in range(n)]
            x = [x_ref[j, rows, lanes] for j in range(n)]
            y = y_ref[rows, lanes]
            for j in range(n):
                dx_ref[j, rows, lanes] = functools.reduce(
                    jnp.add, [h_res[i][j] * dxp[i] for i in range(n)]
                )
            dy_ref[rows, lanes] = functools.reduce(
                jnp.add, [h_post[i] * dxp[i] for i in range(n)]
            )
            # dH_res[i, j], row-major, then dH_post[i].
            products = [dxp[i] * x[j] for i in range(n) for j in range(n)]
            products += [dxp[i] * y for i in range(n)]
            return tuple(
                functools.reduce(jnp.add, _lane_tiles(p), acc)
                for acc, p in zip(sums, products)
            )

        sums = _over_lanes(d, _slab(d), tile, (zero,) * (n * n + n))
        dh_ref[rows, :] = _lane_sums(sums)
        return carry

    jax.lax.fori_loop(0, block // _TILE, group, 0)


def _token_tiles(maps, tokens):
    """Small arrays [k, tokens] one under the other -> [tokens, 128]."""
    rows = jnp.concatenate(
        [m.reshape(-1, tokens).astype(jnp.float32) for m in maps]
    )
    return jnp.pad(rows, ((0, _LANES - rows.shape[0]), (0, 0))).T


# The calls are jitted, as ops/grouped_matmul.py's are: a step's ten
# sublayers, forward, rematerialised and backward, then trace and lower
# each kernel's long unrolled body once, not forty times (25 s of every
# warm start on the host, PERF.md section 6, PR 60).
@functools.partial(jax.jit, static_argnames=("interpret",))
def _mix_backward(dxp, x, y, h_res, h_post, *, interpret):
    """dX' and X [n, tokens, d], y [tokens, d], H_res [n, n, tokens],
    H_post [n, tokens] -> (dX, dy, dH_res, dH_post)."""
    n, tokens, d = x.shape
    block = MIX_TOKENS
    streams = pl.BlockSpec((n, block, d), lambda t: (0, t, 0))
    one = pl.BlockSpec((block, d), lambda t: (t, 0))
    small = pl.BlockSpec((block, _LANES), lambda t: (t, 0))
    f32 = jnp.float32
    dx, dy, dh = pl.pallas_call(
        _mix_backward_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, f32),
            jax.ShapeDtypeStruct(y.shape, f32),
            jax.ShapeDtypeStruct((tokens, _LANES), f32),
        ),
        grid=(pl.cdiv(tokens, block),),
        in_specs=[streams, streams, one, small],
        out_specs=(streams, one, small),
        interpret=interpret,
        name="stream_mix_backward",
        **_compiler_params(interpret, "parallel"),
    )(dxp, x, y, _token_tiles([h_res, h_post], tokens))
    dh = dh.T
    return (
        dx, dy, dh[: n * n].reshape(n, n, tokens),
        dh[n * n : n * n + n],
    )


def plain_mix(x, y, h_res, h_post):
    """X' [n, ..., d] from X [n, ..., d], y [..., d], H_res [n, n, ...]
    and H_post [n, ...]: the 4 x 4 mix as sixteen scaled adds (no
    contraction of 4: that would lay the streams out again)."""
    n = x.shape[0]
    mixed = sum(h_res[:, j][..., None] * x[j][None] for j in range(n))
    return mixed + h_post[..., None] * y[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _mix(x, y, h_res, h_post, interpret):
    del interpret
    return plain_mix(x, y, h_res, h_post)


def _mix_fwd(x, y, h_res, h_post, interpret):
    return plain_mix(x, y, h_res, h_post), (x, y, h_res, h_post)


def _mix_bwd(interpret, residuals, dxp):
    return _mix_backward(dxp, *residuals, interpret=interpret)


_mix.defvjp(_mix_fwd, _mix_bwd)


# ------------------------------------------------- the maps and the pre-sum


def _maps_forward_kernel(x_ref, phi_ref, scale_ref, b_ref, u_ref, m_ref,
                         inv_ref, h_ref, *, eps):
    n, block, d = x_ref.shape
    against_tokens = (((1,), (1,)), ((), ()))

    # Unrolled, unlike the other loops over the lanes: in a loop this one
    # ran at half its pace, and its body is short to trace.
    products = jnp.zeros((_ROWS, block), jnp.float32)
    squares = jnp.zeros((block, _LANES), jnp.float32)
    width = _chunk(d)
    for i in range(n):
        for at in range(0, d, width):
            x = x_ref[i, :, at : at + width]
            squares = functools.reduce(
                jnp.add, _lane_tiles(jnp.square(x)), squares
            )
            products = products + product_in_kernel(
                phi_ref[i, :, at : at + width], x, _TERMS, against_tokens
            )
    inv = jax.lax.rsqrt(
        jnp.sum(squares, axis=1, keepdims=True) / (n * d) + eps
    )
    # The tokens from the lanes to the sublanes: [32, block] -> [block,
    # 128], a token's 24 products along its row.
    m = jnp.concatenate(
        [products, jnp.zeros((_LANES - _ROWS, block), jnp.float32)]
    ).T * inv
    m_ref[...] = m
    inv_ref[...] = jnp.broadcast_to(inv, (block, _LANES))
    h_ref[...] = jax.nn.sigmoid(m * scale_ref[...] + b_ref[...])

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * _TILE, _TILE), _TILE)
        h = h_ref[rows, :]
        h_pre = [_column(h, i) for i in range(n)]

        def tile(lanes, carry):
            u_ref[rows, lanes] = functools.reduce(
                jnp.add, [h_pre[i] * x_ref[i, rows, lanes] for i in range(n)]
            )
            return carry

        return _over_lanes(d, _slab(d), tile, carry)

    jax.lax.fori_loop(0, block // _TILE, group, 0)


def _maps_backward_kernel(x_ref, dxin_ref, du_ref, phi_ref, scale_ref,
                          b_ref, m_ref, inv_ref, dm_ref, dx_ref, dphi_ref,
                          g_ref, dh_ref, *, tokens):
    n, block, d = x_ref.shape
    # How many of this cell's rows are tokens.
    left = tokens - pl.program_id(0) * block

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, jnp.float32)

    # dH_pre[i] = sum_d du X[i], a token's into lane i of its row.
    zero = jnp.zeros((_TILE, _LANES), jnp.float32)

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * _TILE, _TILE), _TILE)

        def tile(lanes, sums):
            du = du_ref[rows, lanes]
            return tuple(
                functools.reduce(
                    jnp.add, _lane_tiles(du * x_ref[i, rows, lanes]), acc
                )
                for i, acc in enumerate(sums)
            )

        dh_ref[rows, :] = _lane_sums(
            _over_lanes(d, _slab(d), tile, (zero,) * n)
        )
        return carry

    jax.lax.fori_loop(0, block // _TILE, group, 0)

    m, inv, scale = m_ref[...], inv_ref[...], scale_ref[...]
    h = jax.nn.sigmoid(m * scale + b_ref[...])
    row = jax.lax.broadcasted_iota(jnp.int32, (block, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, _LANES), 1)
    # The logits' cotangent: the caller's through m, the pre-sum's own.
    g = jnp.where(lane < n, dh_ref[...] * h * (1.0 - h), 0.0)
    g_ref[...] = g
    dm = jnp.where(row < left, dm_ref[...] + g * scale, 0.0)
    # Past the end inv is whatever the buffer held: 0 x that may be NaN.
    dp = jnp.where(row < left, dm * inv, 0.0)
    # Through the RMS: d inv / d X = -inv^3 X / (n d), and p = m / inv.
    through_rms = inv * inv * (
        jnp.sum(dm * m, axis=1, keepdims=True) * (-1.0 / (n * d))
    )
    dp_by_token = dp.T[:_ROWS]
    h_pre = [_column(h, i) for i in range(n)]
    rms = _column(through_rms, 0)
    over = (((1,), (0,)), ((), ()))
    valid = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) < left

    def chunk(lanes, carry):
        for i in range(n):
            x = x_ref[i, :, lanes]
            phi = phi_ref[i, :, lanes]
            dphi_ref[i, :, lanes] += product_in_kernel(
                dp_by_token, jnp.where(valid, x, 0.0), _TERMS, over
            )
            dx_ref[i, :, lanes] = (
                dxin_ref[i, :, lanes]
                + product_in_kernel(dp[:, :_ROWS], phi, _TERMS, over)
                + h_pre[i] * du_ref[:, lanes]
                + rms * x
            )
        return carry

    _over_lanes(d, _chunk(d), chunk)


def _phi_rows(phi):
    """`Phi` [n, d, columns] -> [n, 32, d]: its columns as rows, d on
    the lanes."""
    columns = phi.shape[2]
    return jnp.pad(
        phi.astype(jnp.float32).transpose(0, 2, 1),
        ((0, 0), (0, _ROWS - columns), (0, 0)),
    )


def _lane_row(v):
    return jnp.pad(v.astype(jnp.float32), (0, _LANES - v.shape[0]))[None]


def _maps_specs(n, d):
    block = MAP_TOKENS
    return dict(
        streams=pl.BlockSpec((n, block, d), lambda t: (0, t, 0)),
        one=pl.BlockSpec((block, d), lambda t: (t, 0)),
        small=pl.BlockSpec((block, _LANES), lambda t: (t, 0)),
        phi=pl.BlockSpec((n, _ROWS, d), lambda t: (0, 0, 0)),
        row=pl.BlockSpec((1, _LANES), lambda t: (0, 0)),
    )


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _maps_forward(x, phi_rows, scale, b, *, eps, interpret):
    """X [n, tokens, d], `_phi_rows`, the pre logits' scale and bias as
    lane rows -> (u [tokens, d], m [tokens, 128], inv [tokens, 128])."""
    n, tokens, d = x.shape
    spec = _maps_specs(n, d)
    f32 = jnp.float32
    small = jax.ShapeDtypeStruct((tokens, _LANES), f32)
    return pl.pallas_call(
        functools.partial(_maps_forward_kernel, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((tokens, d), f32), small, small),
        grid=(pl.cdiv(tokens, MAP_TOKENS),),
        in_specs=[spec["streams"], spec["phi"], spec["row"], spec["row"]],
        out_specs=(spec["one"], spec["small"], spec["small"]),
        scratch_shapes=[pltpu.VMEM((MAP_TOKENS, _LANES), f32)],
        interpret=interpret,
        name="stream_maps_forward",
        **_compiler_params(interpret, "parallel"),
    )(x, phi_rows, scale, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _maps_backward(x, dxin, du, phi_rows, scale, b, m, inv, dm, *, interpret):
    """-> (dX, d`_phi_rows`, the pre logits' cotangent [tokens, 128])."""
    n, tokens, d = x.shape
    spec = _maps_specs(n, d)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_maps_backward_kernel, tokens=tokens),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, f32),
            jax.ShapeDtypeStruct(phi_rows.shape, f32),
            jax.ShapeDtypeStruct((tokens, _LANES), f32),
        ),
        grid=(pl.cdiv(tokens, MAP_TOKENS),),
        in_specs=[
            spec["streams"], spec["streams"], spec["one"], spec["phi"],
            spec["row"], spec["row"], spec["small"], spec["small"],
            spec["small"],
        ],
        out_specs=(spec["streams"], spec["phi"], spec["small"]),
        scratch_shapes=[pltpu.VMEM((MAP_TOKENS, _LANES), f32)],
        interpret=interpret,
        name="stream_maps_backward",
        # dPhi is summed over the token blocks in place.
        **_compiler_params(interpret, "arbitrary"),
    )(x, dxin, du, phi_rows, scale, b, m, inv, dm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _maps_pre(x, phi_rows, scale, b, eps, interpret):
    u, m, _ = _maps_forward(
        x, phi_rows, scale, b, eps=eps, interpret=interpret
    )
    return x, u, m


def _maps_pre_fwd(x, phi_rows, scale, b, eps, interpret):
    u, m, inv = _maps_forward(
        x, phi_rows, scale, b, eps=eps, interpret=interpret
    )
    return (x, u, m), (x, phi_rows, scale, b, m, inv)


def _maps_pre_bwd(eps, interpret, residuals, cotangents):
    del eps
    x, phi_rows, scale, b, m, inv = residuals
    dxin, du, dm = cotangents
    dx, dphi_rows, g = _maps_backward(
        x, dxin, du, phi_rows, scale, b, m, inv, dm, interpret=interpret
    )
    return (
        dx, dphi_rows, jnp.sum(g * m, axis=0, keepdims=True),
        jnp.sum(g, axis=0, keepdims=True),
    )


_maps_pre.defvjp(_maps_pre_fwd, _maps_pre_bwd)


def _interpret():
    return jax.default_backend() != "tpu"


def _padded(tokens, block):
    """Rows to add so that fewer tokens than a block are one block."""
    return max(block - tokens, 0)


def maps_and_pre(streams, phi, scale, b, eps):
    """A sublayer's normalised products and its pre-sum from one read
    of the streams: streams [n, ..., d] float32 (a token for each index
    of the dots), phi [n, d, columns], the logits' scale and bias
    [columns] (the first n are H_pre's) -> (the streams, u [..., d], m
    [columns, ...]). The streams come back as they went in: the caller
    mixes THOSE, so that the cotangent the mix hands them reaches the
    backward kernel and dX is written once. The kernels take a token a
    row, [n, tokens, d]: streams held so are passed as they lie, any
    other shape is laid out again on the way in and out. The shapes
    must be `kernels_apply`'s."""
    n, *lead, d = streams.shape
    columns = phi.shape[2]
    tokens = math.prod(lead)
    pad = _padded(tokens, MAP_TOKENS)
    x = jnp.pad(streams.reshape(n, tokens, d), ((0, 0), (0, pad), (0, 0)))
    x, u, m = _maps_pre(
        x, _phi_rows(phi), _lane_row(scale), _lane_row(b), eps, _interpret()
    )
    return (
        x[:, :tokens].reshape(streams.shape),
        u[:tokens].reshape(*lead, d),
        m[:tokens, :columns].T.reshape(columns, *lead),
    )


def mix(streams, y, h_res, h_post):
    """`plain_mix` whose backward is one pass: streams [n, ..., d] and
    y [..., d] float32, H_res [n, n, ...], H_post [n, ...] -> X' like
    the streams."""
    n, *lead, d = streams.shape
    tokens = math.prod(lead)
    pad = _padded(tokens, MIX_TOKENS)

    def by_token(a, at):
        a = a.reshape(a.shape[:at] + (tokens,) + a.shape[at + len(lead) :])
        widths = [(0, 0)] * a.ndim
        widths[at] = (0, pad)
        return jnp.pad(a, widths)

    mixed = _mix(
        by_token(streams, 1), by_token(y, 0), by_token(h_res, 2),
        by_token(h_post, 1), _interpret(),
    )
    return mixed[:, :tokens].reshape(streams.shape)
