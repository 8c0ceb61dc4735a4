"""Pipeline parallelism over a `pipe` mesh axis.

The reference has no pipeline parallelism (its nets are 3-block convs,
SURVEY.md §2.3) and the IMPALA trunks here don't need it either — but a
framework that scales deep uniform towers (transformer stacks) across
chips needs the schedule, so it is built first-class and validated in the
full-training-step multichip dryrun.

Design (TPU-idiomatic, compare Praxis/scaling-book pipelining rather than
torch RPC): every device holds ONE stage's parameters (a pytree whose
leaves carry a leading stage axis sharded over `pipe`); the batch is cut
into microbatches; a `lax.scan` runs the GPipe schedule — at tick t, stage
s processes microbatch t-s and hands its activations to stage s+1 via
`lax.ppermute` over ICI. Fill/drain bubbles compute on zeros and their
outputs are masked out, so autodiff through the scan yields exactly the
sequential gradients. The whole schedule lives inside one `shard_map`, so
XLA sees static shapes and a fixed collective ring.

Constraints (asserted): stage output shape == stage input shape (uniform
tower), batch divisible by the microbatch count, and a 1-D stage axis.

Why GPipe-in-scan and not 1F1B: autodiff through the scan already runs
the schedule in REVERSE for the backward — stage s's grads compute at
mirrored ticks, pipelined over the same ring — so the bubble fraction of
the combined fwd+bwd matches non-interleaved 1F1B at equal M
((S-1)/(S+M-1) per direction; raise n_microbatches to amortize). 1F1B's
remaining advantage is peak activation memory, and that lever exists
here as per-stage rematerialization (jax.checkpoint around stage_fn —
models/transformer_pp.py `remat`), which bounds live activations to one
microbatch per stage exactly like 1F1B's eager backward does, with none
of the hand-staged VJP machinery a manual schedule would need.
"""

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def default_n_microbatches(
    mesh: Mesh, axis: str = "pipe", n_microbatches: Optional[int] = None
) -> int:
    """The microbatch count pipeline_apply will actually use — the single
    source of truth for model-side divisibility checks and fallbacks
    (models/pipelined.py, models/transformer_pp.py)."""
    return (
        n_microbatches if n_microbatches is not None else mesh.shape[axis]
    )


def can_pipeline(
    mesh: Mesh,
    batch_rows: int,
    axis: str = "pipe",
    n_microbatches: Optional[int] = None,
    batch_axis: Optional[str] = None,
) -> bool:
    """Whether pipeline_apply accepts `batch_rows` — rows must divide
    into microbatches AND (on a composite mesh) each microbatch's rows
    must divide over `batch_axis`. The single gate the models' silent
    sequential fallback and the drivers' up-front validation both use,
    so they can never disagree with pipeline_apply's own checks."""
    M = default_n_microbatches(mesh, axis, n_microbatches)
    if batch_rows % M != 0:
        return False
    if batch_axis is not None and (
        (batch_rows // M) % mesh.shape[batch_axis] != 0
    ):
        return False
    return True


def stack_stages(per_stage_trees):
    """Stack a list of per-stage pytrees along a new leading stage axis
    (the layout pipeline_apply expects for `stage_params`)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_trees
    )


def stage_param_shardings(mesh: Mesh, stage_params: Any, axis: str = "pipe"):
    """params-pytree of NamedShardings: leading stage axis over `axis`."""
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(axis)), stage_params
    )


def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "pipe",
    n_microbatches: Optional[int] = None,
    stage_carry: Any = None,
    shared: Any = None,
    batch_axis: Optional[str] = None,
):
    """Run a uniform tower of S stages as a pipeline over `axis`.

    Args:
      stage_fn: `(params, x_mb, carry_mb, shared_mb) -> (y_mb, new_carry_mb)`
        applied per microbatch. `y_mb.shape == x_mb.shape` (activations
        rotate between stages, so the width is uniform).
      stage_params: pytree, every leaf `[S, ...]` — stage s's params at
        index s. Shard with `stage_param_shardings` (or leave unplaced;
        shard_map partitions logically either way).
      x: `[B, ...]` activations entering stage 0.
      n_microbatches: M; default S. `B % M == 0`.
      stage_carry: optional pytree, leaves `[S, B, ...]` — per-stage,
        per-example state (e.g. a KV cache per layer). Stays resident on
        its stage; never rotates.
      shared: optional pytree, leaves `[B, ...]` — inputs every stage
        reads for the microbatch it is processing (masks, segment ids).
      batch_axis: optional name of a DATA axis on the same mesh — each
        microbatch additionally shards its rows over it, so a
        (data x pipe) mesh runs an independent GPipe per data group
        (the cross-group gradient all-reduce comes from the params
        being replicated over `batch_axis`, inserted by XLA as usual).
        Requires B/M divisible by the axis size.

    Returns:
      `(y, new_stage_carry)`: y `[B, ...]` from the last stage (replicated
      over `axis`), new_stage_carry with the same `[S, B, ...]` layout.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    M = default_n_microbatches(mesh, axis, n_microbatches)
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by n_microbatches={M}")
    if batch_axis is not None and (B // M) % mesh.shape[batch_axis] != 0:
        raise ValueError(
            f"microbatch rows {B // M} not divisible by the "
            f"`{batch_axis}` axis size {mesh.shape[batch_axis]}"
        )
    for tree, what in ((stage_params, "stage_params"),
                       (stage_carry, "stage_carry")):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if leaf.shape[0] != S:
                # shard_map would hand each device leading_dim/S stages
                # and the local `[0]` would silently drop all but the
                # first — wrong results, no error. Reject instead.
                raise ValueError(
                    f"{what} leaf {jax.tree_util.keystr(path)} has "
                    f"leading dim {leaf.shape[0]}; the pipeline needs "
                    f"exactly one stage per device on `{axis}` (= {S})"
                )
    mb = B // M

    def to_mb(leaf):  # [B, ...] -> [M, mb, ...]
        return leaf.reshape((M, mb) + leaf.shape[1:])

    def from_mb(leaf):  # [M, mb, ...] -> [B, ...]
        return leaf.reshape((M * mb,) + leaf.shape[2:])

    xs = to_mb(x)
    shared_mb = jax.tree_util.tree_map(to_mb, shared)
    # stage_carry [S, B, ...] -> [S, M, mb, ...]
    carry_mb = jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((S, M, mb) + leaf.shape[2:]), stage_carry
    )

    # Microbatch rows shard over batch_axis (if any): [M, mb, ...] ->
    # P(None, batch_axis); the resident carry keeps its stage axis too.
    mb_spec = P(None, batch_axis) if batch_axis else P()
    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    cspec = jax.tree_util.tree_map(
        lambda _: P(axis, None, batch_axis) if batch_axis else P(axis),
        carry_mb,
    )
    rspec = jax.tree_util.tree_map(lambda _: mb_spec, (xs, shared_mb))
    ring = [(i, (i + 1) % S) for i in range(S)]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(pspec, cspec, rspec[0], rspec[1]),
        out_specs=(mb_spec, cspec),
        check_vma=False,
    )
    def run(params, carry, xs, shared_mb):
        # Local leaves keep a leading stage axis of size 1 — drop it.
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        carry = jax.tree_util.tree_map(lambda c: c[0], carry)
        idx = lax.axis_index(axis)

        state = jnp.zeros_like(xs[0])
        out_acc = jnp.zeros_like(xs)

        def body(scan_carry, t):
            state, out_acc, carry = scan_carry
            # Stage `idx` processes microbatch j = t - idx at tick t.
            j = t - idx
            active = (j >= 0) & (j < M)
            jc = jnp.clip(j, 0, M - 1)
            inp = jnp.where(idx == 0, xs[jc], state)
            carry_in = jax.tree_util.tree_map(lambda c: c[jc], carry)
            shared_in = jax.tree_util.tree_map(
                lambda s: s[jc], shared_mb
            )
            out, carry_out = stage_fn(params, inp, carry_in, shared_in)
            # Persist this stage's new per-microbatch state (bubble ticks
            # write nothing — `where` keeps the old row).
            carry = jax.tree_util.tree_map(
                lambda c, new: c.at[jc].set(
                    jnp.where(
                        active.reshape((1,) * new.ndim), new, c[jc]
                    )
                ),
                carry,
                carry_out,
            )
            # The last stage's active outputs are the pipeline's outputs.
            take = active & (idx == S - 1)
            out_acc = out_acc.at[jc].set(
                jnp.where(take.reshape((1,) * out.ndim), out, out_acc[jc])
            )
            # Rotate activations one stage forward over the ICI ring.
            state = lax.ppermute(out, axis, ring)
            return (state, out_acc, carry), None

        (state, out_acc, carry), _ = lax.scan(
            body, (state, out_acc, carry), jnp.arange(S + M - 1)
        )
        # out_acc is non-zero only on the last stage; psum replicates it.
        y = lax.psum(out_acc, axis)
        carry = jax.tree_util.tree_map(lambda c: c[None], carry)
        return y, carry

    y, new_carry = run(stage_params, carry_mb, xs, shared_mb)
    new_carry = jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((S, M * mb) + leaf.shape[3:]), new_carry
    )
    return from_mb(y), new_carry


def pipeline_apply_multi(
    stage_fn: Callable,
    stage_params: Any,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "pipe",
    n_microbatches: Optional[int] = None,
    stage_carry: Any = None,
    shared: Any = None,
    batch_axis: Optional[str] = None,
):
    """Pipeline S = k*P stages over P devices as k sequential passes of
    the P-stage GPipe schedule (a looped pipeline: device d runs global
    stages j*P + d for j in 0..k-1).

    Accepts the same `[S, ...]`-leading stage_params/stage_carry layout
    as `pipeline_apply` and reduces to it when S == P. Each pass pays its
    own fill/drain bubble — the simple schedule; an interleaved 1F1B
    would trade that for a much hairier program. Bubble cost is
    (P-1)/(M+P-1) per pass, so raise n_microbatches to amortize.
    """
    S_total = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    P_devices = mesh.shape[axis]
    if S_total == P_devices:
        return pipeline_apply(
            stage_fn, stage_params, x, mesh=mesh, axis=axis,
            n_microbatches=n_microbatches, stage_carry=stage_carry,
            shared=shared, batch_axis=batch_axis,
        )
    if S_total % P_devices != 0:
        raise ValueError(
            f"{S_total} stages not divisible by the `{axis}` axis size "
            f"{P_devices}"
        )
    k = S_total // P_devices

    def pass_slice(tree, j):
        return jax.tree_util.tree_map(
            lambda leaf: leaf.reshape(
                (k, P_devices) + leaf.shape[1:]
            )[j],
            tree,
        )

    new_carries = []
    for j in range(k):
        carry_j = None if stage_carry is None else pass_slice(
            stage_carry, j
        )
        x, new_c = pipeline_apply(
            stage_fn, pass_slice(stage_params, j), x, mesh=mesh,
            axis=axis, n_microbatches=n_microbatches,
            stage_carry=carry_j, shared=shared, batch_axis=batch_axis,
        )
        new_carries.append(new_c)
    if stage_carry is None:
        return x, None
    new_carry = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves).reshape(
            (S_total,) + leaves[0].shape[1:]
        ),
        *new_carries,
    )
    return x, new_carry
