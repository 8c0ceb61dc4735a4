"""Precision policies: what dtype each byte of the learner step lives in.

The one chip record of the learner step (2026-07-31, deleted in PR 21)
had it memory-bound: MFU 0.115 with idle MXU lanes. The premise here is
that the path to 2x is moving fewer bytes per update, not more FLOPs — so precision is a POLICY over
storage, with one hard contract:

    f32-accumulate: losses, V-trace targets, gradient reductions, and
    the optimizer's second-moment EMA are COMPUTED in float32 whatever
    the storage dtype. Master params stay float32 always. bfloat16 only
    ever changes what is STORED and MOVED, never what is accumulated.

Three policies (the drivers' `--precision` flag):

    f32           Everything float32 (the seed behavior).
    bf16_compute  Trunk compute in bfloat16 (the MXU path; exactly the
                  old `--model_dtype bfloat16`, which now deprecates to
                  this policy). Storage unchanged.
    bf16_train    bf16_compute PLUS bf16 storage: the recurrent core
                  and policy head also compute in bf16 (activations the
                  backward re-reads are half-width end to end; logits/
                  baseline/new-state upcast to f32 at the model
                  boundary), the staged [K, T+1, B, ...] batch stack's
                  float leaves travel host->device as bf16 (halving the
                  PR 4 arena transfer), and the RMSprop second moment
                  is stored bf16 (learner.HParams.opt_state_dtype).

Measurement lives here too: `bytes_accessed` reads XLA's own cost
analysis off the LOWERED (pre-optimization) HLO, where every tensor
still carries its semantic dtype. The CPU backend widens bf16 matmuls
to f32 during optimization, so COMPILED cost analysis on this container
reports the CPU emulation, not the policy — the lowered module is the
platform-neutral accounting the `learner.hbm_bytes_per_update` gauge
reports.
"""

import logging
import threading
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

_NP_BF16 = np.dtype(ml_dtypes.bfloat16)

log = logging.getLogger(__name__)

CHOICES = ("f32", "bf16_compute", "bf16_train")


class Policy(NamedTuple):
    """One precision policy. `compute_dtype` is the conv/fc trunk's
    compute dtype (the old --model_dtype knob); `head_dtype` the
    recurrent-core + policy-head compute dtype; `param_dtype` the
    RESIDENT param storage ("bf16" keeps an f32 master in the optimizer
    state — learner._bf16_resident_params); `batch_dtype` the numpy
    dtype float32 leaves of the staged batch are stored/transferred as
    (None = keep f32); `opt_state_dtype` the RMSprop second-moment
    storage dtype string consumed by learner.HParams."""

    name: str
    compute_dtype: Any
    head_dtype: Any
    param_dtype: str
    batch_dtype: Optional[Any]
    opt_state_dtype: str


POLICIES = {
    "f32": Policy("f32", jnp.float32, jnp.float32, "f32", None, "f32"),
    "bf16_compute": Policy(
        "bf16_compute", jnp.bfloat16, jnp.float32, "f32", None, "f32"
    ),
    "bf16_train": Policy(
        "bf16_train", jnp.bfloat16, jnp.bfloat16, "bf16", _NP_BF16,
        "bf16",
    ),
}


def get(name: str) -> Policy:
    try:
        return POLICIES[name]
    except KeyError:
        raise ValueError(
            f"Unknown precision policy {name!r}; choices: {CHOICES}"
        ) from None


def resolve_flags(flags) -> Policy:
    """Flags -> Policy, honoring the deprecated --model_dtype alias.

    `--model_dtype bfloat16` predates the policy layer and only ever
    flipped trunk compute; it now aliases `--precision bf16_compute`
    with a deprecation warning. Passing both (with a non-default
    --precision) is a conflict, not a silent priority rule."""
    name = getattr(flags, "precision", "f32") or "f32"
    legacy = getattr(flags, "model_dtype", None)
    if legacy and legacy != "float32":
        if name != "f32" and name != "bf16_compute":
            raise ValueError(
                f"--model_dtype {legacy} conflicts with --precision "
                f"{name}; drop the deprecated --model_dtype flag"
            )
        if not getattr(resolve_flags, "_warned_model_dtype", False):
            resolve_flags._warned_model_dtype = True
            log.warning(
                "--model_dtype bfloat16 is deprecated; use --precision "
                "bf16_compute (aliased for you). bf16_train additionally "
                "makes params/activations bf16-resident and compacts "
                "the staged batch and optimizer second moment — see "
                "README 'Precision & memory'."
            )
        name = "bf16_compute"
    return get(name)


def cast_params(params, policy: Policy):
    """Model-init (f32) params -> the policy's resident dtype. The f32
    master copy is recreated by the optimizer's init
    (learner._bf16_resident_params) — callers cast BEFORE
    optimizer.init."""
    if policy.param_dtype != "bf16":
        return params
    return jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16)
        if hasattr(p, "dtype") and p.dtype == jnp.float32 else p,
        params,
    )


def cast_batch(tree, batch_dtype=None):
    """Host-side staging cast: float32 numpy leaves -> `batch_dtype`
    (bf16 under bf16_train), everything else untouched. Applied at the
    staging boundary (BatchArena write-through / the drivers' place_fn)
    so the host->device transfer and the device-resident batch are
    half-width; learner.compute_loss upcasts at point of use (the
    f32-accumulate contract), which XLA fuses into the first consumer —
    the batch is READ from HBM as bf16 and widened in registers."""
    if batch_dtype is None:
        return tree

    def cast(leaf):
        a = np.asarray(leaf)
        if a.dtype == np.float32:
            return a.astype(batch_dtype)
        return a

    return jax.tree_util.tree_map(cast, tree)


def bytes_accessed(jittable, *args) -> Optional[float]:
    """XLA-reported `bytes accessed` of `jittable(*args)` from the
    LOWERED (pre-optimization) HLO — the dtype-faithful, platform-
    neutral accounting (see module docstring for why not the compiled
    module on CPU). `args` may be real arrays or ShapeDtypeStructs
    (lowering needs only avals). Returns None when cost analysis is
    unavailable (no compile is ever triggered here)."""
    try:
        lower = getattr(jittable, "lower", None)
        if lower is None:
            return None
        analysis = lower(*args).cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        value = float(analysis.get("bytes accessed", 0.0))
        return value if value > 0 else None
    except Exception:  # best-effort accounting, never sinks a run
        log.debug("bytes_accessed cost analysis failed", exc_info=True)
        return None


class MemoryStats(NamedTuple):
    """XLA-reported memory figures for one jittable signature.

    `bytes_accessed` is the lowered (pre-optimization) cost-analysis
    traffic figure — dtype-faithful, platform-neutral, CONSERVATIVE for
    bf16 (module docstring). `argument_bytes`/`output_bytes`/
    `temp_bytes` come from the compiled module's memory analysis when a
    compile is possible (None otherwise): `temp_bytes` is XLA's own
    peak temp-buffer allocation — the activation/workspace footprint the
    remat planner trades against recompute. `peak_bytes` is the
    arguments + outputs + temps sum: the HBM envelope one live dispatch
    of this program needs (params/opt-state/batch are arguments here;
    callers add anything they keep resident OUTSIDE the dispatch).

    Compiled on the ambient backend: on this chipless container that is
    XLA:CPU, whose buffer assignment widens bf16 dots to f32 emulation —
    the reported peak is an UPPER bound for the bf16 policies (the safe
    direction for a fits-in-budget decision)."""

    bytes_accessed: Optional[float]
    argument_bytes: Optional[float]
    output_bytes: Optional[float]
    temp_bytes: Optional[float]
    peak_bytes: Optional[float]


def memory_stats(jittable, *args, compiled: bool = True) -> MemoryStats:
    """The `bytes_accessed` machinery extended to temp/peak allocation
    (the remat planner's budget oracle). `args` may be real arrays or
    ShapeDtypeStructs. `compiled=False` skips the compile and reports
    traffic only (cheap: lowering never compiles).

    Never raises: a platform where lowering or compilation is
    unavailable reports None fields, and callers (the planner) degrade
    to their documented fallback instead of sinking a run."""
    accessed = bytes_accessed(jittable, *args)
    arg_b = out_b = temp_b = peak = None
    if compiled:
        try:
            lower = getattr(jittable, "lower", None)
            mem = lower(*args).compile().memory_analysis()
            arg_b = float(mem.argument_size_in_bytes)
            out_b = float(mem.output_size_in_bytes)
            temp_b = float(mem.temp_size_in_bytes)
            # Donation (alias_size) re-uses argument buffers for
            # outputs; counting both would double the aliased set.
            peak = arg_b + out_b + temp_b - float(
                mem.alias_size_in_bytes
            )
        except Exception:
            log.debug("compiled memory analysis failed", exc_info=True)
    return MemoryStats(
        bytes_accessed=accessed,
        argument_bytes=arg_b,
        output_bytes=out_b,
        temp_bytes=temp_b,
        peak_bytes=peak,
    )


def shape_structs(tree, placed=False):
    """Concrete arrays -> ShapeDtypeStructs (lowering fodder that holds
    no buffers); `placed`, each with its array's sharding, so that a
    jitted function lowers to the program the arrays' call compiled."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), jnp.asarray(a).dtype if not hasattr(a, "dtype")
            else a.dtype,
            sharding=getattr(a, "sharding", None) if placed else None,
        ),
        tree,
    )


def hbm_gauge_async(update_fn, args, gauge):
    """Set `gauge` to the per-update XLA bytes-accessed figure of
    `update_fn(*args)` without stalling the caller: tracing/lowering a
    deep net takes seconds, so the analysis runs on a daemon thread
    (lowering never compiles and JAX tracing is thread-safe). The
    thread captures ShapeDtypeStructs, not the live arrays — staged
    batches may be donated/deleted by the time it runs.

    The figure needs NO division by superstep_k: the lowered HLO counts
    a lax.scan body once, so a K-update superstep program's
    bytes-accessed is already one update's compute (plus the K-stack
    staging operands)."""
    structs = tuple(shape_structs(a) for a in args)

    def run():
        total = bytes_accessed(update_fn, *structs)
        if total is not None:
            gauge.set(total)

    threading.Thread(
        target=run, daemon=True, name="hbm-bytes-analysis"
    ).start()
