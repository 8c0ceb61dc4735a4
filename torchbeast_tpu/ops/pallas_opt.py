"""Fused Pallas optimizer tail: the last multi-pass chain XLA leaves
unfused, as ONE VMEM-resident kernel per leaf chunk.

The learner's update tail — global-norm grad clip, torch-RMSprop second
moment, optional momentum trace, LR apply, f32 master write, and (under
--precision bf16_train) the bf16 resident-param narrowing cast — is a
chain of ~15 elementwise passes over master-sized arrays in the lowered
HLO. XLA fuses parts of it on chip, but the clip/scale boundary (a
reduction feeding every leaf) and the optimizer-state read-modify-write
keep it a multi-pass region; the committed learner_bench.json bytes
matrix shows the tail dominating full-update bytes once bf16_train has
shrunk the fwd/bwd section. This kernel makes the whole tail ONE pass:
each leaf is read once (grad, second moment, momentum, master), every
intermediate lives in VMEM/registers, and exactly the new state is
written back.

Leaves run in their NATIVE shapes — no flatten/pad plumbing (those
would lower to real pre-opt HLO ops and re-inflate the very bytes
figure the kernel exists to shrink; the lowered accounting of this
module is pure operand/result traffic). The one view change is free:
scalars and vectors are presented as one-row matrices (_run_leaf). Leaves above a VMEM-sized
threshold are chunked by a grid over their leading axis; everything
else is one whole-leaf block.

The f32-accumulate contract (torchbeast_tpu/precision.py) is preserved
IN-KERNEL: grads and the second moment are widened to f32 in registers,
the EMA/clip/update math runs f32, and only the writes narrow (nu to
its storage dtype, the resident params to bf16). The master params are
read and written f32 — the one full-width traffic the contract
requires.

Exposed as an optax.GradientTransformation whose `update` returns the
NEW RESIDENT PARAMS as the updates value (state carries the f32 master
under bf16_train), applied by learner.apply_updates — the same
not-a-delta convention as learner._bf16_resident_params, for the same
reason: materializing a params-sized delta for optax.apply_updates
would round-trip every leaf through extra converts for nothing.

The scalar global-norm FINALIZE (sqrt + clip-factor select) happens
inside the kernel from the summed squares: the cross-leaf sum is the
one reduction that genuinely spans leaves, so XLA computes it (and CSEs
it with the update step's grad_norm stat); everything downstream is
fused here. Parity with the optax chain (clip -> _scale_by_rms_torch ->
trace -> scale_by_learning_rate [-> master rebase]) is
exact-to-f32-rounding and pinned by tests/test_pallas_opt.py across
{MLP, LSTM} x {f32, bf16_train} x clip on/off.

Compiled on TPU (lowering pinned via jax.export in
benchmarks/pallas_smoke.py opt cases and tests/test_mosaic_lowering.py);
`interpret=True` runs the identical kernel under the Pallas interpreter
— the CPU CI path, selected automatically off-TPU like
ops/vtrace._pallas_interpret.
"""

import functools
import os
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

# Mosaic's default scoped-VMEM limit is 16 MiB per kernel; leave
# headroom for the body's elementwise temporaries.
_VMEM_BUDGET = 12 * 1024 * 1024


def _interpret_default() -> bool:
    """Compile on TPU, interpret elsewhere (the CPU CI path).
    TORCHBEAST_OPT_PALLAS_COMPILE=1 forces compilation off-TPU so
    benchmarks/pallas_smoke.py can rehearse the clean-failure path,
    mirroring the V-trace kernel's env knob."""
    if os.environ.get("TORCHBEAST_OPT_PALLAS_COMPILE"):
        return False
    return jax.default_backend() != "tpu"


def _tail_kernel(refs, *, alpha, eps, momentum, max_norm, res_dtype,
                 nu_dtype, has_mom, emit_master):
    """One leaf (or leading-axis chunk of one): global-norm finalize ->
    clip -> torch-RMSprop [-> momentum] -> master write [-> resident
    narrowing cast], all in VMEM. Scalars ride as (1,)*ndim blocks —
    Mosaic rejects rank-0 scalar/vector mixed compares — and broadcast
    against the chunk."""
    it = iter(refs)
    g_ref, nu_ref = next(it), next(it)
    mom_ref = next(it) if has_mom else None
    mst_ref, sumsq_ref, lr_ref = next(it), next(it), next(it)
    res_ref, nnu_ref = next(it), next(it)
    nmom_ref = next(it) if has_mom else None
    nmst_ref = next(it) if emit_master else None

    g = g_ref[:].astype(jnp.float32)
    if max_norm is not None:
        gnorm = jnp.sqrt(sumsq_ref[:])  # global-norm finalize
        scale = jnp.where(
            gnorm < max_norm, jnp.ones_like(gnorm), max_norm / gnorm
        )
        g = g * scale
    # torch-RMSprop: f32 EMA accumulate whatever nu's storage dtype
    # (the precision module's f32-accumulate contract), torch
    # denominator form g / (sqrt(nu) + eps).
    nu = alpha * nu_ref[:].astype(jnp.float32) + (1.0 - alpha) * g * g
    upd = g / (jnp.sqrt(nu) + eps)
    if has_mom:
        upd = momentum * mom_ref[:] + upd
        nmom_ref[:] = upd
    new_mst = mst_ref[:] - lr_ref[:] * upd
    res_ref[:] = new_mst.astype(res_dtype)
    nnu_ref[:] = nu.astype(nu_dtype)
    if emit_master:
        nmst_ref[:] = new_mst


def _leaf_grid(shape, n_arrays: int) -> Optional[int]:
    """Rows-per-block for leaves too big to sit whole in VMEM (None =
    whole-leaf single block, the common case). Only the leading axis
    chunks. `n_arrays` leaf-shaped operands and results are resident at
    once and the grid pipeline double-buffers each block, so one block
    may take budget / (2 * n_arrays) — the flagship's [3872, 256] fc
    kernel with momentum on (7 arrays) asked for 24 MB of scoped VMEM
    at a flat 2 MiB per block. Bytes are counted f32 and tile-padded
    (the last dim to 128 lanes, the one before it to 8 sublanes)."""
    if len(shape) < 2:
        return None
    lanes = -(-shape[-1] // 128) * 128
    inner = shape[1:-1]
    if inner:
        inner = inner[:-1] + (-(-inner[-1] // 8) * 8,)
    row_bytes = 4 * lanes * int(
        functools.reduce(lambda a, b: a * b, inner, 1)
    )
    block_bytes = _VMEM_BUDGET // (2 * n_arrays)
    if shape[0] * row_bytes <= block_bytes:
        return None
    rows = max(1, block_bytes // row_bytes)
    if len(shape) == 2:
        # The chunked axis is the sublane axis: a partial block must be
        # a whole number of tiles in every dtype (16 rows for bf16).
        rows = max(16, rows - rows % 16)
    return rows


def _run_leaf(
    g, nu, mom, mst, sumsq, lr, *,
    alpha, eps, momentum, max_norm, res_dtype, interpret,
):
    """Run the fused tail over ONE leaf in its native shape. Returns
    (resident, new_nu, new_mom, new_master); new_mom is None when
    momentum is off, new_master None when the resident params ARE the
    f32 master (the f32 policy)."""
    from jax.experimental import pallas as pl

    has_mom = bool(momentum)
    emit_master = res_dtype != mst.dtype
    # Scalars and vectors ride as one-row matrices: Mosaic stores a 1-D
    # bf16 vector only when its length is a multiple of 256 ("offset
    # not aligned to sublanes" / "masked along subelements" for every
    # bias of the flagship tree), while [1, n] compiles in both dtypes.
    shape = g.shape if g.ndim >= 2 else (1, g.size)
    ndim = len(shape)
    ones = (1,) * ndim
    leaf = lambda x: x.reshape(shape)  # noqa: E731
    scalars = (
        sumsq.reshape(ones).astype(jnp.float32),
        lr.reshape(ones).astype(jnp.float32),
    )

    kernel = functools.partial(
        _tail_kernel,
        alpha=alpha, eps=eps, momentum=momentum, max_norm=max_norm,
        res_dtype=res_dtype, nu_dtype=nu.dtype,
        has_mom=has_mom, emit_master=emit_master,
    )

    inputs = [leaf(g), leaf(nu)]
    if has_mom:
        inputs.append(leaf(mom))
    inputs += [leaf(mst), *scalars]
    out_shape = [
        jax.ShapeDtypeStruct(shape, res_dtype),
        jax.ShapeDtypeStruct(shape, nu.dtype),
    ]
    if has_mom:
        out_shape.append(jax.ShapeDtypeStruct(shape, jnp.float32))
    if emit_master:
        out_shape.append(jax.ShapeDtypeStruct(shape, jnp.float32))

    block_rows = _leaf_grid(shape, len(inputs) - 2 + len(out_shape))
    if block_rows is None:
        out = pl.pallas_call(
            lambda *refs: kernel(refs),
            out_shape=tuple(out_shape),
            interpret=interpret,
        )(*inputs)
    else:
        rest = shape[1:]
        chunk = pl.BlockSpec(
            (block_rows,) + rest, lambda i: (i,) + (0,) * len(rest)
        )
        scalar_spec = pl.BlockSpec(ones, lambda i: (0,) * ndim)
        n_leaf = len(inputs) - 2
        out = pl.pallas_call(
            lambda *refs: kernel(refs),
            grid=(-(-shape[0] // block_rows),),
            in_specs=[chunk] * n_leaf + [scalar_spec, scalar_spec],
            out_specs=[chunk] * len(out_shape),
            out_shape=tuple(out_shape),
            interpret=interpret,
        )(*inputs)

    out = [o.reshape(g.shape) for o in out]
    it = iter(out)
    res, new_nu = next(it), next(it)
    new_mom = next(it) if has_mom else None
    new_mst = next(it) if emit_master else None
    return res, new_nu, new_mom, new_mst


class FusedTailState(NamedTuple):
    """State of the fused optimizer tail. `count` is the schedule clock
    (named `count` so optax.tree_utils.tree_get — the entropy anneal's
    lookup — finds it exactly like the optax chain's). `master` holds
    the f32 master params under bf16-resident training and None
    otherwise (the resident params ARE the f32 master then); `mom` is
    None when momentum is off, matching the optax chain's conditional
    trace. learner.apply_updates recognizes this state type: the
    transform's updates value is the NEW RESIDENT PARAMS, not a delta.
    """

    count: Any
    nu: Any
    mom: Any
    master: Any


def fused_rmsprop_tail(
    learning_rate,
    decay: float,
    eps: float,
    momentum: float = 0.0,
    max_norm: Optional[float] = None,
    param_dtype: str = "f32",
    state_dtype=None,
    interpret: Optional[bool] = None,
) -> optax.GradientTransformation:
    """The full learner optimizer tail as one fused transform
    (--opt_impl pallas): clip-by-global-norm (`max_norm`; None = no
    clip), torch-denominator RMSprop (`decay`, `eps`, second moment
    stored as `state_dtype`), momentum trace, LR schedule apply, and —
    under param_dtype="bf16" — the f32 master write + bf16 resident
    narrowing cast. Semantics match learner.make_optimizer's optax
    chain exactly (pinned by tests/test_pallas_opt.py).

    `learning_rate` may be a float or an optax schedule over the update
    count. `update` returns (new_resident_params, state); apply with
    learner.apply_updates.
    """
    schedule = (
        learning_rate if callable(learning_rate)
        else (lambda _: learning_rate)
    )
    bf16_resident = param_dtype == "bf16"

    def init_fn(params):
        # Same contract as _bf16_resident_params: callers cast params
        # to the resident dtype BEFORE optimizer.init; the f32 master
        # materializes here.
        master = (
            jax.tree_util.tree_map(
                lambda p: p.astype(jnp.float32), params
            )
            if bf16_resident else None
        )
        nu = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, state_dtype or jnp.float32),
            params,
        )
        mom = (
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            if momentum else None
        )
        return FusedTailState(
            count=jnp.zeros([], jnp.int32), nu=nu, mom=mom,
            master=master,
        )

    def update_fn(updates, state, params=None):
        itp = _interpret_default() if interpret is None else interpret
        grads = updates
        lr = jnp.asarray(schedule(state.count), jnp.float32)
        # The one genuinely cross-leaf reduction: summed squares in f32
        # (each leaf read half-width under bf16 grads, widened in
        # registers — XLA CSEs these partial sums with the update
        # step's grad_norm stat).
        if max_norm is not None:
            sumsq = sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)
            )
        else:
            sumsq = jnp.zeros([], jnp.float32)
        masters = state.master if bf16_resident else params
        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_nu = jax.tree_util.tree_leaves(state.nu)
        flat_mom = (
            jax.tree_util.tree_leaves(state.mom)
            if momentum else [None] * len(flat_g)
        )
        flat_mst = jax.tree_util.tree_leaves(masters)
        new_res, new_nu, new_mom, new_mst = [], [], [], []
        for g, nu, mom, mst in zip(flat_g, flat_nu, flat_mom, flat_mst):
            res_dtype = jnp.bfloat16 if bf16_resident else mst.dtype
            r, n_nu, n_mom, n_mst = _run_leaf(
                g, nu, mom, mst, sumsq, lr,
                alpha=decay, eps=eps, momentum=momentum,
                max_norm=max_norm, res_dtype=res_dtype, interpret=itp,
            )
            new_res.append(r)
            new_nu.append(n_nu)
            new_mom.append(n_mom)
            new_mst.append(n_mst if n_mst is not None else r)
        unflatten = functools.partial(
            jax.tree_util.tree_unflatten, treedef
        )
        new_state = FusedTailState(
            count=optax.safe_int32_increment(state.count),
            nu=unflatten(new_nu),
            mom=unflatten(new_mom) if momentum else None,
            master=unflatten(new_mst) if bf16_resident else None,
        )
        return unflatten(new_res), new_state

    return optax.GradientTransformation(init_fn, update_fn)
