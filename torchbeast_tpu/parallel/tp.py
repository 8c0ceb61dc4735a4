"""Tensor-parallel param shardings over the mesh's `model` axis.

The reference has no tensor parallelism (its nets are small conv+LSTM,
SURVEY.md §2.3) and these nets don't need it either — but the mesh carries
a `model` axis precisely so wider models can shard without changing the
training loop. Two levels:

- `dense_kernel_shardings`: the generic rule — 2-D matrix kernels shard
  their INPUT dim over `model` (row-parallel: each chip holds the rows
  matching its activation shard, partial products all-reduce once per
  layer), everything else replicated. Right for the conv+LSTM families
  (conv channels are far below MXU tile sizes), and collective-wise the
  better generic rule than the column layout it replaced in ISSUE 13
  (one all-reduce vs a gather per layer).

- `transformer_tp_shardings`: Megatron-style COLUMN/ROW pairing for the
  transformer tower — q/k/v projections and the FFN up-projection are
  column-parallel (heads / d_ff sharded), the attention out-projection
  and FFN down-projection are row-parallel, so within each block the
  activations stay sharded between the pair and XLA inserts exactly ONE
  all-reduce per attention and one per FFN (the canonical layout,
  shaped like Megatron-LM/praxis) instead of a gather per layer.

XLA inserts every collective implied by the shardings — no hand-written
collectives anywhere. Used by make_parallel_update_step(...,
param_shardings=...), polybeast's --tensor_parallel, and
__graft_entry__.dryrun_multichip on a (data x model) mesh.
"""

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dense_kernel_shardings(mesh: Mesh, params: Any) -> Any:
    """params-pytree of NamedShardings: 2-D kernels -> P("model", None)
    (row-parallel — see module docstring for why not column), everything
    else replicated."""
    model_size = mesh.shape["model"]

    def rule(leaf):
        if (
            model_size > 1
            and hasattr(leaf, "ndim")
            and leaf.ndim == 2
            and leaf.shape[0] % model_size == 0
        ):
            return NamedSharding(mesh, P("model", None))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(rule, params)


def transformer_tp_shardings(
    mesh: Mesh, params: Any, axis: str = "model"
) -> Any:
    """Megatron-paired shardings for the TransformerNet param tree.

    Inside every `block_*` scope (models/transformer.py):
      q/k/v kernels [d, H, hd]  -> P(None, axis, None)   (column: heads)
      q/k/v biases  [H, hd]     -> P(axis, None)
      rel_bias      [H, M+1]    -> P(axis, None)         (per-head)
      out kernel    [H, hd, d]  -> P(axis, None, None)   (row: heads)
      FFN Dense_0   [d, ff]     -> P(None, axis), bias [ff] -> P(axis)
      FFN Dense_1   [ff, d]     -> P(axis, None)         (row)
    Everything else (LayerNorms, out/Dense_1 biases, encoder, extras,
    head, MoE leaves — EP owns those) replicated. Raises if the head
    count or FFN width does not divide the axis — a silently replicated
    half of a column/row pair would force per-layer resharding, the
    exact failure mode this layout exists to avoid.

    Works verbatim on matching trees (optax state) like the EP rule.
    """
    size = mesh.shape[axis]

    def tok(entry):
        for attr in ("key", "name", "idx"):
            if hasattr(entry, attr):
                return getattr(entry, attr)
        return None

    def check(dim, what, path):
        if dim % size != 0:
            raise ValueError(
                f"transformer_tp_shardings: {what} ({dim}) at "
                f"{jax.tree_util.keystr(path)} not divisible by the "
                f"`{axis}` axis size {size}"
            )

    def rule(path, leaf):
        toks = [tok(p) for p in path]
        in_block = any(
            isinstance(t, str) and t.startswith("block_") for t in toks
        )
        if size <= 1 or not in_block or not hasattr(leaf, "ndim"):
            return NamedSharding(mesh, P())
        name = toks[-1]
        parent = toks[-2] if len(toks) >= 2 else None
        if parent in ("q", "k", "v"):
            if name == "kernel" and leaf.ndim == 3:
                check(leaf.shape[1], "num_heads", path)
                return NamedSharding(mesh, P(None, axis, None))
            if name == "bias" and leaf.ndim == 2:
                check(leaf.shape[0], "num_heads", path)
                return NamedSharding(mesh, P(axis, None))
        if parent == "out" and name == "kernel" and leaf.ndim == 3:
            check(leaf.shape[0], "num_heads", path)
            return NamedSharding(mesh, P(axis, None, None))
        if name == "rel_bias" and leaf.ndim == 2:
            check(leaf.shape[0], "num_heads", path)
            return NamedSharding(mesh, P(axis, None))
        if parent == "Dense_0":  # FFN up-projection (column)
            if name == "kernel" and leaf.ndim == 2:
                check(leaf.shape[1], "d_ff", path)
                return NamedSharding(mesh, P(None, axis))
            if name == "bias" and leaf.ndim == 1:
                check(leaf.shape[0], "d_ff", path)
                return NamedSharding(mesh, P(axis))
        if parent == "Dense_1" and name == "kernel" and leaf.ndim == 2:
            check(leaf.shape[0], "d_ff", path)
            return NamedSharding(mesh, P(axis, None))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(rule, params)


def merge_param_shardings(*sharding_trees: Any) -> Any:
    """Leaf-wise union of sharding rules over ONE mesh: for each leaf, at
    most one input tree may be non-replicated (rules are expected to
    target disjoint leaves — e.g. the transformer TP pairing shards the
    attention/dense-FFN leaves while the EP rule shards the MoE expert
    kernels); a genuine conflict raises rather than silently picking.
    """

    def pick(path, *shardings):
        non_repl = [s for s in shardings if not s.is_fully_replicated]
        if len({s.spec for s in non_repl}) > 1:
            raise ValueError(
                "merge_param_shardings: conflicting non-replicated "
                f"shardings at {jax.tree_util.keystr(path)}: "
                f"{[s.spec for s in non_repl]}"
            )
        return non_repl[0] if non_repl else shardings[0]

    return jax.tree_util.tree_map_with_path(
        pick, sharding_trees[0], *sharding_trees[1:]
    )


def place_params(mesh: Mesh, params: Any, shardings: Any) -> Any:
    return jax.tree_util.tree_map(jax.device_put, params, shardings)
