"""Pallas TPU kernel for the 3x3/stride-2 max-pool backward.

The autodiff backward of `reduce_window(max)` is SelectAndScatter; on a
v5e it costs ~10x the pool forward at the IMPALA trunk's stage-1 shape
and is the learner step's largest single op. This kernel computes the
same gradient in one fused pass:

    gx[n, h, w, c] = sum over taps (kh, kw) of
        g[n, oh, ow, c] * (x[n, h, w, c] == y[n, oh, ow, c])
        where (oh, ow) = ((h + 1 - kh) / 2, (w + 1 - kw) / 2)
        and the tap only contributes when those divisions are exact.

Geometry: arrays are viewed as [N, H, W*C] so the channel dim rides the
lane dimension fused with W — full 128-lane VPU utilization instead of
C/128. The kernel sees x and 2x-upsampled/padded y and g ("doubled grid":
y_up[i] = y[i // 2]); each tap is then a STATIC slice of that grid plus a
parity mask from `broadcasted_iota`, so nothing in the kernel is strided,
scattered, or gathered. The (cheap, output-sized) upsample+pad runs in
XLA before the call.

Tie semantics match ops.pool's CPU tap-sum VJP: every input position that
ties at the window max is credited (a valid subgradient). SelectAndScatter
credits only the first in scan order; ties are measure-zero for conv
activations.

Specialized to window (3, 3), strides (2, 2), padding ((1, 1), (1, 1)) —
the only configuration the IMPALA trunks use (reference
polybeast_learner.py:168, monobeast.py:563 use stride-2 3x3 pools);
`supports(...)` gates the dispatch and everything else falls back to the
caller's default backward.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_WINDOW = (3, 3)
_STRIDES = (2, 2)
_PADDING = ((1, 1), (1, 1))


def supports(x, window, strides, padding) -> bool:
    return (
        tuple(window) == _WINDOW
        and tuple(strides) == _STRIDES
        and tuple(tuple(p) for p in padding) == _PADDING
        and x.ndim == 4
        and jnp.issubdtype(x.dtype, jnp.floating)
    )


def _kernel(x_ref, y_ref, g_ref, gx_ref, *, H, WC, C, taps=3):
    """One [bn, H, W*C] block: accumulate all taps' credited gradient.

    y_ref/g_ref hold the doubled grid [bn, 2Ho + 2, (2Wo + 2) * C] with a
    one-slot border (y border = +inf so it never equals x; g border = 0).
    """
    # v5e's VPU has no bf16 compare/select: widen once on load.
    x = x_ref[:].astype(jnp.float32)
    # Parity masks: tap (kh, kw) reaches input (h, w) iff h + 1 - kh and
    # w + 1 - kw are both even (i.e. land on an even doubled-grid slot).
    h_idx = lax.broadcasted_iota(jnp.int32, (1, H, WC), 1)
    w_idx = lax.broadcasted_iota(jnp.int32, (1, H, WC), 2) // C
    gx = jnp.zeros_like(x)
    for kh in range(taps):
        # (h + 1 - kh) % 2 == 0, written % 2 == (1 - kh) % 2 on h alone.
        mh = (h_idx % 2) == ((1 - kh) % 2)
        for kw in range(taps):
            mw = (w_idx % 2) == ((1 - kw) % 2)
            # Doubled-grid slice for this tap: row h reads upsampled row
            # h + 1 - kh, i.e. padded row h + 2 - kh; same for lanes in
            # units of C.
            y_tap = y_ref[:, 2 - kh : 2 - kh + H,
                          (2 - kw) * C : (2 - kw) * C + WC
                          ].astype(jnp.float32)
            g_tap = g_ref[:, 2 - kh : 2 - kh + H,
                          (2 - kw) * C : (2 - kw) * C + WC
                          ].astype(jnp.float32)
            hit = (x == y_tap) & mh & mw
            gx = gx + jnp.where(hit, g_tap, jnp.zeros_like(g_tap))
    gx_ref[:] = gx.astype(gx_ref.dtype)


def _doubled_grid(a, H_pad_value):
    """[N, Ho, Wo, C] -> [N, 2Ho + 2, (2Wo + 2) * C]: 2x nearest-neighbor
    upsample plus a one-slot border filled with `H_pad_value`."""
    N, Ho, Wo, C = a.shape
    up = jnp.broadcast_to(
        a[:, :, None, :, None, :], (N, Ho, 2, Wo, 2, C)
    ).reshape(N, 2 * Ho, 2 * Wo, C)
    up = jnp.pad(
        up, ((0, 0), (1, 1), (1, 1), (0, 0)),
        constant_values=H_pad_value,
    )
    return up.reshape(N, 2 * Ho + 2, (2 * Wo + 2) * C)


# Mosaic's default scoped-VMEM limit is 16 MiB per kernel; leave
# headroom for what the model below does not see.
_VMEM_BUDGET = 14 * 1024 * 1024

# f32 temporaries the unrolled 9-tap body keeps live, in units of one
# tile-padded [H, W*C] block (fitted to the compiler's scoped-allocation
# sizes at the three flagship trunk stages, N=2592: 20.76M / 41.10M at
# block_n 2 / 4 for [84, 1344], 22.49M at block_n 4 for [42, 1344]).
_TEMP_BLOCKS = 14


def _tile_bytes(rows, lanes, dtype):
    """Bytes of one [rows, lanes] VMEM buffer: Mosaic pads the last two
    dims to the dtype's (sublane, 128) tile — (8, 128) f32, (16, 128)
    bf16 — so 84 x 1344 f32 occupies 88 x 1408."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 8 * (4 // itemsize)
    rows = -(-rows // sublane) * sublane
    lanes = -(-lanes // 128) * 128
    return rows * lanes * itemsize


def _auto_block_n(H, WC, Hd, WdC, dtype):
    """Largest batch rows per block whose footprint fits the budget.

    Per batch row: x, gx ([H, WC]) and the doubled y, g grids
    ([Hd, WdC]) in the storage dtype, each double-buffered by the
    pipeline, plus the body's f32 temporaries.
    """
    per_n = (
        2 * 2 * (_tile_bytes(H, WC, dtype) + _tile_bytes(Hd, WdC, dtype))
        + _TEMP_BLOCKS * _tile_bytes(H, WC, jnp.float32)
    )
    return max(1, _VMEM_BUDGET // per_n)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pool_bwd(x, y, g, block_n: int | None = None, interpret: bool = False):
    """Gradient of `reduce_window(max, 3x3, stride 2, pad 1)` wrt x.

    x: [N, H, W, C] pool input; y: pooled output; g: cotangent of y.
    block_n: batch rows per grid cell; None picks the largest that fits
    the scoped-VMEM budget (the flagship's stage-1 shape tiles down to 1).
    """
    from jax.experimental import pallas as pl

    N, H, W, C = x.shape
    _, Ho, Wo, _ = y.shape
    WC = W * C
    if block_n is None:
        block_n = min(
            N,
            _auto_block_n(H, WC, 2 * Ho + 2, (2 * Wo + 2) * C, x.dtype),
        )

    y_d = _doubled_grid(y, jnp.inf)
    g_d = _doubled_grid(g, 0)
    x3 = x.reshape(N, H, WC)

    grid = (pl.cdiv(N, block_n),)
    kernel = functools.partial(_kernel, H=H, WC=WC, C=C)
    gx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, H, WC), lambda n: (n, 0, 0)),
            pl.BlockSpec(
                (block_n, 2 * Ho + 2, (2 * Wo + 2) * C), lambda n: (n, 0, 0)
            ),
            pl.BlockSpec(
                (block_n, 2 * Ho + 2, (2 * Wo + 2) * C), lambda n: (n, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((block_n, H, WC), lambda n: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H, WC), x.dtype),
        interpret=interpret,
    )(x3, y_d, g_d)
    return gx.reshape(N, H, W, C)
