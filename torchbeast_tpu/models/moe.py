"""Mixture-of-experts feed-forward layer with expert parallelism.

Beyond the reference (its nets are small dense conv+LSTM, SURVEY.md §2.3):
this is the layer that gives the framework an `expert` sharding axis. The
design is TPU-first throughout:

- Routing is TOP-K with a fixed CAPACITY per expert, and dispatch/combine
  are dense one-hot einsums — static shapes, pure matmuls on the MXU; no
  gather/scatter, no dynamic shapes, nothing XLA can't tile.
- With a mesh carrying an `expert` axis, the expert-stacked tensors
  (`w_in [E, d, ff]`, the `[E, C, d]` dispatched activations) are
  sharding-constrained over that axis; XLA inserts the dispatch/combine
  all-to-alls on ICI. No hand-written collectives.
- The load-balance auxiliary loss is sown into the `losses` collection;
  the learner adds every sown loss to the objective (a no-op for models
  that sow nothing — and `sow` itself is a no-op outside mutable apply,
  so the acting path is untouched).

Routing semantics (fresh implementation of the standard top-k/capacity
scheme): each token picks its top-k experts by router probability; the
selected gates are renormalized to sum to 1; experts take at most
`capacity` assignments, earlier-rank selections win capacity first and
ties break by token order; over-capacity assignments are dropped (the
token's output loses that expert's contribution — with the residual
connection around the layer this degrades gracefully).

`DroplessMoE` (below, the `olmoe` family's layer) is the second dispatch:
every one of the t x K assignments is computed, whatever the router does.
The assignments are sorted by expert, so each expert's rows are one
contiguous group of a [t*K, d] matrix and the experts are three grouped
matmuls (`grouped_matmul`); t x K rows is a static shape, so no
assignment is padded to a capacity and none is dropped. The capacity
path stays for `--num_experts` and `parallel/ep.py`.

A `DroplessMoE` may hold a share of its experts (`held`: the chip's
part of a layer that several chips divide, models/mellum2.py): it
routes over all of them, has weights for its own alone, and returns
the part of the sum that its own experts give. The sorted rows of the
other experts are never visited (the kernels' `group_offset`).
"""

import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox
from jax.sharding import NamedSharding, PartitionSpec as P


def _constrain(x, mesh, spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec)
    )


class MoEFFN(nn.Module):
    """[tokens, d_model] -> [tokens, d_model] mixture of expert MLPs."""

    d_model: int
    d_ff: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    mesh: Optional[Any] = None  # mesh with an `expert` axis -> EP
    expert_axis: str = "expert"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        tokens, d = x.shape
        E, K = self.num_experts, self.top_k
        if K > E:
            raise ValueError(f"top_k={K} exceeds num_experts={E}")
        capacity = max(
            1, int(math.ceil(K * tokens / E * self.capacity_factor))
        )
        espec = P(self.expert_axis)

        # --- Routing (f32 for a stable softmax regardless of self.dtype).
        router_logits = nn.Dense(
            E, use_bias=False, name="router"
        )(x.astype(jnp.float32))
        probs = jax.nn.softmax(router_logits, axis=-1)  # [t, E]
        gate, idx = jax.lax.top_k(probs, K)  # [t, K]
        gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-9)

        # --- Capacity assignment. Rank-major flattening gives rank-0
        # selections strict priority over rank-1, then token order.
        sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [t, K, E]
        sel_flat = sel.transpose(1, 0, 2).reshape(K * tokens, E)
        pos_flat = jnp.cumsum(sel_flat, axis=0) - sel_flat
        pos = pos_flat.reshape(K, tokens, E).transpose(1, 0, 2)  # [t, K, E]
        kept = sel * (pos < capacity)

        # slot[t, k, e, c]: one-hot over the capacity slot this (token,
        # rank) pair occupies in expert e, zero if dropped.
        slot = jax.nn.one_hot(
            pos.astype(jnp.int32), capacity, dtype=jnp.float32
        ) * kept[..., None]
        dispatch = slot.sum(axis=1)  # [t, E, C] (0/1)
        combine = (gate[:, :, None, None] * slot).sum(axis=1)  # [t, E, C]

        # --- Expert computation: batched matmuls over the expert axis.
        kernel_init = nn.initializers.lecun_normal()
        w_in = self.param(
            "w_in", kernel_init, (E, d, self.d_ff)
        ).astype(self.dtype)
        b_in = self.param("b_in", nn.initializers.zeros, (E, self.d_ff))
        w_out = self.param(
            "w_out", kernel_init, (E, self.d_ff, d)
        ).astype(self.dtype)
        b_out = self.param("b_out", nn.initializers.zeros, (E, d))

        w_in = _constrain(w_in, self.mesh, P(self.expert_axis, None, None))
        w_out = _constrain(w_out, self.mesh, P(self.expert_axis, None, None))

        # Dispatch all-to-all: [t, E, C] x [t, d] -> [E, C, d] sharded
        # over `expert`.
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype), x.astype(self.dtype)
        )
        expert_in = _constrain(expert_in, self.mesh, P(self.expert_axis))
        h = nn.gelu(
            jnp.einsum("ecd,edf->ecf", expert_in, w_in)
            + b_in[:, None, :].astype(self.dtype)
        )
        h = _constrain(h, self.mesh, P(self.expert_axis))
        expert_out = (
            jnp.einsum("ecf,efd->ecd", h, w_out)
            + b_out[:, None, :].astype(self.dtype)
        )
        expert_out = _constrain(expert_out, self.mesh, P(self.expert_axis))
        # Combine all-to-all back to token order.
        y = jnp.einsum(
            "ecd,tec->td",
            expert_out.astype(jnp.float32),
            combine.astype(jnp.float32),
        )

        # --- Load-balance loss (top-1 dispatch fraction x mean router
        # prob, scaled so a perfectly uniform router scores 1.0 before
        # weighting).
        top1 = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
        frac_dispatched = top1.mean(axis=0)
        mean_prob = probs.mean(axis=0)
        aux = E * jnp.sum(frac_dispatched * mean_prob)
        # Guarded so init() never materializes a `losses` collection in
        # the variables dict (it would end up inside checkpoints and the
        # optimizer state); overwrite-reduce so re-application can never
        # double-count.
        if not self.is_initializing():
            self.sow(
                "losses",
                "moe_load_balance",
                self.aux_loss_weight * aux,
                reduce_fn=lambda prev, new: new,
            )

        return y.astype(jnp.float32)


@jax.custom_vjp
def _permute(rows, perm, inverse):
    """rows[perm], for a permutation and its inverse. The gradient of a
    gather is a scatter-add, which the chip serialises; a permutation's
    is the gather by the inverse."""
    del inverse
    return rows[perm]


def _permute_fwd(rows, perm, inverse):
    return rows[perm], (perm, inverse)


def _permute_bwd(residuals, grad):
    perm, inverse = residuals
    return _permute(grad, inverse, perm), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


# The grouped matmul's tiles (rows of a group, contracted, output
# columns), tuned on the v5e at the OLMoE cell's shapes (PERF.md, PR 27).
_GMM_TILING = (256, 1024, 1024)


# bfloat16 terms an operand is cut into, by the matmul precision the
# caller traces under (JAX's names and their aliases); n terms make
# n (n + 1) / 2 passes of the kernel.
_TERMS = {"high": 2, "tensorfloat32": 2, "highest": 3, "float32": 3}


def _terms_traced_under():
    return _TERMS.get(jax.config.jax_default_matmul_precision, 1)


def _bf16_terms(x, terms):
    """x as a sum of `terms` bfloat16 arrays, the largest first."""
    if terms == 1:
        return [x.astype(jnp.bfloat16)]
    out = []
    for _ in range(terms):
        # Not astype there and back: XLA takes that round trip for the
        # identity on the chip, and the next term comes out as zeros.
        head = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        out.append(head.astype(jnp.bfloat16))
        x = x - head
    return out


def _gmm_call(kernel, lhs, rhs, sizes, rows, terms, **kwargs):
    """One product by a megablox kernel. On the chip its operands are
    bfloat16 and its sums and result float32. With one term a side,
    that is what XLA makes of a float32 matmul at JAX's default
    precision; with two (a caller that traces under `high`, models/
    kanana2.py) the three passes XLA makes there: head x head, head x
    tail, tail x head. So the experts are computed as every other
    matmul of the model is. Elsewhere the kernel is interpreted, in
    float32."""
    on_chip = jax.default_backend() == "tpu"
    _, tk, tn = _GMM_TILING

    def call(lhs, rhs):
        # The kernel's own dot is the plain one whatever the caller
        # traces under: Mosaic refuses a bfloat16 operand at a float32
        # contraction ("Bad lhs type").
        with jax.default_matmul_precision(None):
            return kernel(
                lhs, rhs, sizes, jnp.float32, (rows, tk, tn),
                interpret=not on_chip, **kwargs,
            )

    if not on_chip:
        return call(lhs, rhs)
    lhs, rhs = _bf16_terms(lhs, terms), _bf16_terms(rhs, terms)
    # The smallest products first, so that they are not lost one by
    # one beside the largest.
    out = None
    for order in reversed(range(terms)):
        for i in range(order + 1):
            part = call(lhs[i], rhs[order - i])
            out = part if out is None else out + part
    return out


def grouped_matmul(lhs, rhs, sizes, first=None):
    """lhs [m, k] in contiguous groups of `sizes` [E] rows, rhs
    [E, k, n] -> [m, n]: rows of group e times rhs[e]. The kernels are
    JAX's shipped megablox `gmm` / `tgmm`; this wrapper fixes their
    operand and result types (above), forward and backward by the
    `jax.default_matmul_precision` this call is traced under, and pads
    the rows to the kernel's tile, the padding going to the last group
    as rows of zeros.

    With `first` (a Python int), rhs [C, k, n] holds groups first ..
    first + C - 1 of the E alone: the rows of the other groups are not
    visited, and come out as zeros (as do their gradients)."""
    return _grouped_matmul(lhs, rhs, sizes, first, _terms_traced_under())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(lhs, rhs, sizes, first, terms):
    return _grouped_matmul_fwd(lhs, rhs, sizes, first, terms)[0]


def _pad_rows(sizes, *matrices):
    m = matrices[0].shape[0]
    tile = min(_GMM_TILING[0], -(-m // 128) * 128)
    pad = -m % tile
    if pad:
        sizes = sizes.at[-1].add(pad)
        matrices = [jnp.pad(a, ((0, pad), (0, 0))) for a in matrices]
    return tile, sizes, matrices


def _from_group(first):
    if first is None:
        return {}
    return {"group_offset": jnp.asarray(first, jnp.int32)}


def _grouped_matmul_fwd(lhs, rhs, sizes, first, terms):
    tile, padded_sizes, (padded,) = _pad_rows(sizes, lhs)
    out = _gmm_call(
        _megablox.gmm, padded, rhs, padded_sizes, tile, terms,
        **_from_group(first),
    )
    return out[: lhs.shape[0]], (lhs, rhs, sizes)


def _grouped_matmul_bwd(first, terms, residuals, grad):
    lhs, rhs, sizes = residuals
    tile, padded_sizes, (lhs_p, grad_p) = _pad_rows(sizes, lhs, grad)
    grad_lhs = _gmm_call(
        _megablox.gmm, grad_p, rhs, padded_sizes, tile, terms,
        transpose_rhs=True, **_from_group(first),
    )[: lhs.shape[0]]
    grad_rhs = _gmm_call(
        _megablox.tgmm, lhs_p.swapaxes(0, 1), grad_p, padded_sizes, tile,
        terms, num_actual_groups=rhs.shape[0], **_from_group(first),
    )
    return grad_lhs.astype(lhs.dtype), grad_rhs.astype(rhs.dtype), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def dropless_experts(x, idx, gate, w_gate, w_up, w_down, first_of=None):
    """sum_k gate[t, k] * expert_{idx[t, k]}(x[t]) with SwiGLU experts,
    every assignment computed.

    x [t, d]; idx, gate [t, K]; w_gate, w_up [E, d, f]; w_down [E, f, d].
    Returns (y [t, d], group sizes [E]).

    `first_of` = (first, E) when the weights are those of experts first
    .. first + C - 1 of E ([C, d, f], [C, f, d]): the sum then runs over
    the assignments to those alone, the sizes are still all E experts'.
    """
    tokens, K = idx.shape
    first, E = first_of or (None, w_gate.shape[0])
    with jax.named_scope("moe_dispatch"):
        flat = idx.reshape(tokens * K)
        # order[i]: which (token, rank) assignment sits in sorted row i.
        order = jnp.argsort(flat, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(tokens * K, dtype=order.dtype), unique_indices=True
        )
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        rows = _permute(jnp.repeat(x, K, axis=0), order, inverse)
    with jax.named_scope("moe_experts"):
        hidden = nn.silu(
            grouped_matmul(rows, w_gate, sizes, first)
        ) * grouped_matmul(rows, w_up, sizes, first)
        out = grouped_matmul(hidden, w_down, sizes, first)
    with jax.named_scope("moe_combine"):
        out = _permute(out, inverse, order).reshape(tokens, K, -1)
        y = jnp.einsum(
            "tkd,tk->td", out.astype(jnp.float32), gate.astype(jnp.float32)
        )
    return y, sizes


def held_experts(expert_share, num_experts):
    """(first, count) of the experts that share i of n holds
    (`--expert_share i/n`: experts i * num_experts / n onward), None
    for (0, 1): all of them. A share that is none of the n, or an n
    that does not divide the experts, is refused."""
    share, of = expert_share
    if not 0 <= share < of or num_experts % of:
        raise ValueError(
            f"--expert_share {share}/{of}: share i of n takes "
            f"0 <= i < n, and n divides the {num_experts} experts"
        )
    count = num_experts // of
    return None if of == 1 else (share * count, count)


class DroplessMoE(nn.Module):
    """[tokens, d_model] -> [tokens, d_model]: a router over all the
    experts, top-k gates, SwiGLU experts without biases, no capacity.

    The router as the fields say. `scoring` "softmax" (OLMoE's and
    Mellum2's layers): the gates are the selected probabilities as they
    are, or over their sum (`renormalise`, a config's `norm_topk_prob`),
    and the load-balance term is sown. `scoring` "sigmoid" (DeepSeek-V3's
    router, models/kanana2.py): each expert's score is a sigmoid of its
    own logit; with `selection_bias` the k experts are chosen by score +
    bias (a parameter `e_score_correction_bias` [E] that takes no
    gradient: it moves by the load, below) while the gates are the
    chosen SCORES, bias left out. Either way the gates are then
    multiplied by `routed_scaling`. `shared_width` > 0 adds one SwiGLU
    of that width that every token takes, beside the routed sum.

    `held` = (first, count) of the `num_experts`: the layer routes over
    all of them, computes gates and the load-balance term over all of
    them, and holds `w_gate`/`w_up`/`w_down` for `count` alone; what it
    returns is those experts' part of the sum (and the shared expert,
    whole on every chip), and nothing stands in for the rest. None: all
    are held."""

    d_ff: int  # width of one expert
    num_experts: int
    top_k: int
    aux_loss_weight: float = 1e-2  # 0: no load-balance term is sown
    dtype: Any = jnp.float32
    renormalise: bool = False
    held: Optional[Tuple[int, int]] = None
    scoring: str = "softmax"  # or "sigmoid"
    selection_bias: bool = False
    # What the bias moves by after an update: `bias_update_rate` x
    # sign(mean load - load) (DeepSeek-V3, arXiv:2412.19437, 2.1.2).
    bias_update_rate: float = 0.0
    routed_scaling: float = 1.0
    shared_width: int = 0

    @nn.compact
    def __call__(self, x):
        tokens, d = x.shape
        E, K = self.num_experts, self.top_k
        if K > E:
            raise ValueError(f"top_k={K} exceeds num_experts={E}")
        first, count = self.held or (0, E)
        if first < 0 or count < 1 or first + count > E:
            raise ValueError(
                f"held={self.held} is not a range of the {E} experts"
            )
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"Unknown scoring {self.scoring!r}")

        # f32 at the highest matmul precision: the logits decide WHICH
        # experts run, and a rounded logit picks another expert where
        # the eighth and ninth are close.
        with jax.named_scope("moe_route"):
            router_logits = nn.Dense(
                E, use_bias=False, name="router",
                precision=jax.lax.Precision.HIGHEST,
            )(x.astype(jnp.float32))
            if self.scoring == "softmax":
                probs = jax.nn.softmax(router_logits, axis=-1)  # [t, E]
            else:
                probs = nn.sigmoid(router_logits)
            if self.selection_bias:
                bias = self.param(
                    "e_score_correction_bias", nn.initializers.zeros, (E,)
                )
                # The indices carry no gradient, so the bias has none.
                _, idx = jax.lax.top_k(probs + bias, K)  # [t, K]
                gate = jnp.take_along_axis(probs, idx, axis=-1)
            else:
                gate, idx = jax.lax.top_k(probs, K)  # [t, K]
            if self.renormalise:
                total = jnp.sum(gate, axis=-1, keepdims=True)
                if self.scoring == "sigmoid":
                    total = total + 1e-20  # scores may all be zero
                gate = gate / total
            if self.routed_scaling != 1.0:
                gate = gate * self.routed_scaling

        # fan-in is one expert's d (or f), not E times it.
        kernel_init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("w_gate", kernel_init, (count, d, self.d_ff))
        w_up = self.param("w_up", kernel_init, (count, d, self.d_ff))
        w_down = self.param("w_down", kernel_init, (count, self.d_ff, d))
        y, sizes = dropless_experts(
            x.astype(self.dtype), idx, gate,
            w_gate.astype(self.dtype), w_up.astype(self.dtype),
            w_down.astype(self.dtype),
            **({} if count == E else {"first_of": (first, E)}),
        )
        if self.shared_width:
            with jax.named_scope("moe_shared"):
                def proj(name, width):
                    return nn.Dense(
                        width, use_bias=False, dtype=self.dtype, name=name
                    )

                h = x.astype(self.dtype)
                y = y + proj("shared_down", d)(
                    nn.silu(proj("shared_gate", self.shared_width)(h))
                    * proj("shared_up", self.shared_width)(h)
                ).astype(jnp.float32)

        load = sizes.astype(jnp.float32)
        if self.aux_loss_weight:
            # Load balance: E x sum_e (share of the K*t assignments that
            # went to e) x (mean router probability of e); 1.0 when
            # uniform.
            aux = E * jnp.sum(load / (tokens * K) * probs.mean(axis=0))
        if not self.is_initializing():
            # `losses` is added to the objective, `moe_stats` (what the
            # router did) to the update's stats, `param_steps` to the
            # parameters after the optimizer's step: learner.py.
            sown = [
                ("losses", "moe_load_balance", self.aux_loss_weight * aux),
            ] if self.aux_loss_weight else []
            sown += [
                ("moe_stats", "assignments", jnp.sum(load)),
                ("moe_stats", "load_max_over_mean",
                 jnp.max(load) * E / (tokens * K)),
            ]
            if self.held is not None:
                # The part of those this layer computed, and how uneven
                # its own experts' rows are.
                mine = load[first : first + count]
                sown += [
                    ("moe_stats", "held_assignments", jnp.sum(mine)),
                    ("moe_stats", "held_load_max_over_mean",
                     jnp.max(mine) * count / jnp.maximum(jnp.sum(mine), 1.0)),
                ]
            if self.selection_bias:
                # Under the parameter's own name: the learner adds a
                # sown step to the leaf of `params` at the same path.
                sown += [
                    ("param_steps", "e_score_correction_bias",
                     self.bias_update_rate
                     * jnp.sign(jnp.mean(load) - load)),
                    ("moe_stats", "bias_abs_max", jnp.max(jnp.abs(bias))),
                ]
            if self.shared_width:
                sown.append(
                    ("moe_stats", "shared_applications", jnp.float32(1.0))
                )
            for collection, name, value in sown:
                self.sow(
                    collection, name, value,
                    reduce_fn=lambda prev, new: new,
                )
        return y.astype(jnp.float32)
