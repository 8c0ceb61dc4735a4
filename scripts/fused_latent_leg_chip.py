"""The fused latent leg against the XLA body ON THE CHIP, at the
Kanana-2 cell's widths: `ops/attention.latent_cached_attend` in both
regimes of `fused_latent_leg_applies` on the same operands — parity of
the output and of the gradients of q_nope, q_rope, w_uk, w_uv and the
unroll's k_nope, k_rope, v for caches that are full, partly filled and
empty, each against the same body with every matmul in f32 — and the
time of a forward and of a forward + backward of each, by how full the
cache is (no block is skipped on the mask: the times must not differ).

    chiprun -- python3 scripts/fused_latent_leg_chip.py --out chiprun_out/pr41

`--sweep` times the kernels alone over the tilings (heads a cell, keys
a forward / backward cell) that `ops/fused_attention.py`'s constants
were chosen from. Prints one JSON object and writes it to
<out>/fused_latent_leg_chip.json. Exits 1 without a TPU: a CPU's times
are nobody's.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchbeast_tpu.models import kanana2  # noqa: E402
from torchbeast_tpu.ops import attention, fused_attention  # noqa: E402

B, T, H, C, DN, DR, DV, M = 32, 81, 32, 512, 128, 64, 128, 4095
NAMES = ("q_nope", "q_rope", "k_nope", "k_rope", "v", "w_uk", "w_uv")
THETA = 1e6


def case(filled, seed):
    """Operands like a learner step's. `filled`: "full" (every slot
    valid), "partly" (a different number a row, an episode end in some
    rows) or "empty" (the benchmark's learner traffic)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 12)

    def normal(i, *shape):
        return jax.random.normal(keys[i], shape)

    held = {
        "full": jnp.full((B,), M), "empty": jnp.zeros((B,), jnp.int32),
        "partly": jax.random.randint(keys[9], (B,), 0, M + 1),
    }[filled]
    cache_valid = jnp.arange(M)[None, :] >= (M - held)[:, None]
    cache_band, seq_band = attention.band_by_leg(T, M)
    end = jax.random.randint(keys[10], (B,), 1, 2 * T)  # >= T: no end
    if filled != "partly":
        end = jnp.full((B,), 2 * T)
    segment = (jnp.arange(T)[None, :] >= end[:, None]).astype(jnp.int32)
    operands = dict(
        q_nope=normal(0, B, T, H, DN), q_rope=normal(1, B, T, H, DR),
        k_nope=normal(2, B, T, H, DN), k_rope=normal(3, B, T, 1, DR),
        v=normal(4, B, T, H, DV),
        # Decompression halves at the scale of a trained kv_b.
        w_uk=normal(5, C, H, DN) * C ** -0.5,
        w_uv=normal(6, C, H, DV) * C ** -0.5,
    )
    fixed = dict(
        cache_latent=normal(7, M, B, 1, C), cache_rope=normal(8, M, B, 1, DR),
        cache_mask=(
            cache_band[None] & cache_valid[:, None, :]
            & (segment == 0)[:, :, None]
        ),
        seq_mask=seq_band[None] & (
            segment[:, :, None] == segment[:, None, :]
        ),
        dout=normal(11, B, T, H, DV),
    )
    return operands, fixed


def attend(operands, fixed, fused, precision="default"):
    """`latent_cached_attend` as the Kanana-2 block calls it, in the
    regime asked for (the rule would take the fused leg at these
    shapes), the cache leg at `precision`."""
    saved = attention.FUSED_SCORE_BYTES
    attention.FUSED_SCORE_BYTES = saved if fused else float("inf")
    try:
        with jax.default_matmul_precision(
            "high" if precision == "default" else precision
        ):
            return attention.latent_cached_attend(
                *(operands[name] for name in NAMES[:5]),
                fixed["cache_latent"], fixed["cache_rope"],
                operands["w_uk"], operands["w_uv"],
                fixed["cache_mask"], fixed["seq_mask"],
                place_cache_keys=lambda keys, times: kanana2.rope_pairs(
                    keys, times, THETA, time_axis=0
                ),
                cache_precision=precision,
            )
    finally:
        attention.FUSED_SCORE_BYTES = saved


def forward(fused, precision="default"):
    return jax.jit(
        lambda operands, fixed: attend(operands, fixed, fused, precision)
    )


def value_and_grads(fused, precision="default"):
    def run(operands, fixed):
        out, pull = jax.vjp(
            lambda operands: attend(operands, fixed, fused, precision),
            operands,
        )
        return dict(pull(fixed["dout"])[0], out=out)

    return jax.jit(run)


def rel(a, b):
    """max |a - b| over max |b|; 0 where both are zeros throughout
    (w_uk's and w_uv's gradients through an empty cache)."""
    return float(
        jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)
    )


def seconds_a_call(fn, args, calls=5):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls


def kernels_alone(scale):
    """The two kernels as fresh jitted functions of the calls' bodies
    (traces are cached by the function traced, and the tiling is read
    when one is traced: the calls' own jit would hand every tiling the
    first one's program)."""
    forward = fused_attention._latent_forward_call.__wrapped__
    backward = fused_attention._latent_backward_call.__wrapped__
    return (
        jax.jit(
            lambda *operands: forward(*operands, scale, jnp.bfloat16, False)
        ),
        jax.jit(
            lambda *operands: backward(*operands, scale, jnp.bfloat16, False)
        ),
    )


def sweep(seed):
    """ms of the two kernels ALONE (their operands as the kernels read
    them, made beforehand), by tiling."""
    _, fixed = case("partly", seed)
    tp = fused_attention.padded_steps(T)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    k_latent = jax.random.normal(keys[0], (B, M, C))
    k_rope = jax.random.normal(keys[4], (B, M, 128))
    kp = -(-M // 1024) * 1024
    mask = jnp.pad(
        fixed["cache_mask"].astype(jnp.int8),
        ((0, 0), (0, tp - T), (0, kp - M)),
    )
    scale = (DN + DR) ** -0.5
    rows = {}
    shipped = (
        fused_attention._LATENT_HEADS,
        fused_attention._LATENT_FORWARD_KEYS,
        fused_attention._LATENT_BACKWARD_KEYS,
        fused_attention._LATENT_VMEM_LIMIT,
    )
    for heads, forward_keys, backward_keys in (
        (8, 1024, 512), (16, 1024, 512), (16, 512, 512), (16, 2048, 1024),
    ):
        fused_attention._LATENT_FORWARD_KEYS = forward_keys
        fused_attention._LATENT_BACKWARD_KEYS = backward_keys
        fused_attention._LATENT_VMEM_LIMIT = 100 * 2 ** 20
        fused_attention._LATENT_HEADS = heads

        def rows_of(key, width):
            return jax.random.normal(key, (H, B, tp, width))

        q_latent, q_rope = rows_of(keys[1], C), rows_of(keys[2], 128)
        dout = rows_of(keys[3], C)
        name = f"heads{heads}_f{forward_keys}_b{backward_keys}"
        try:
            forward, backward = kernels_alone(scale)
            operands = (q_latent, q_rope, k_latent, k_rope, mask)
            out, lse = forward(*operands)
            rows[name] = {
                "forward": 1e3 * seconds_a_call(forward, operands),
                "backward": 1e3 * seconds_a_call(
                    backward, operands + (lse, lse, out, dout)
                ),
            }
        except Exception as e:  # noqa: BLE001 — a tiling the chip refuses
            rows[name] = {"error": str(e)[:300]}
    (
        fused_attention._LATENT_HEADS,
        fused_attention._LATENT_FORWARD_KEYS,
        fused_attention._LATENT_BACKWARD_KEYS,
        fused_attention._LATENT_VMEM_LIMIT,
    ) = shipped
    return rows


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="chiprun_out/pr41")
    parser.add_argument("--seed", type=int, default=41)
    parser.add_argument("--sweep", action="store_true")
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny widths on whatever device there is: the control "
        "flow alone, its times mean nothing",
    )
    args = parser.parse_args()
    device = jax.devices()[0]
    if args.rehearse:
        global B, T, H, C, DN, DR, DV, M
        B, T, H, C, DN, DR, DV, M = 2, 5, 4, 128, 16, 8, 12, 300
        attention.FUSED_SCORE_BYTES = 1
    elif device.platform != "tpu":
        print(f"no TPU: {device.platform}", file=sys.stderr)
        return 1
    report = {
        "device": device.device_kind, "rehearsal": args.rehearse,
        "parity": {}, "ms": {},
    }
    if args.sweep:
        report["sweep_ms"] = sweep(args.seed)
    for filled in ("full", "partly", "empty"):
        operands, fixed = case(filled, args.seed)
        assert attention.fused_latent_leg_applies(
            (B, T, H, DR), M, C, "default"
        )
        fused = value_and_grads(True)(operands, fixed)
        plain = value_and_grads(False)(operands, fixed)
        # Both against the same body with every matmul in f32: how far
        # each is from the mathematics, beside how far from each other.
        exact = value_and_grads(False, "highest")(operands, fixed)
        report["parity"][filled] = {
            "finite": bool(all(
                jnp.isfinite(x).all() for x in jax.tree_util.tree_leaves(fused)
            )),
            "fused_vs_xla": {k: rel(fused[k], plain[k]) for k in fused},
            "fused_vs_f32": {k: rel(fused[k], exact[k]) for k in fused},
            "xla_vs_f32": {k: rel(plain[k], exact[k]) for k in fused},
        }
        del fused, plain, exact
        report["ms"][filled] = {
            "fused_forward": 1e3 * seconds_a_call(
                forward(True), (operands, fixed)
            ),
            "xla_forward": 1e3 * seconds_a_call(
                forward(False), (operands, fixed)
            ),
            "fused": 1e3 * seconds_a_call(
                value_and_grads(True), (operands, fixed)
            ),
            "xla": 1e3 * seconds_a_call(
                value_and_grads(False), (operands, fixed)
            ),
        }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "fused_latent_leg_chip.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
