"""What a run executes on, said once at start-up.

Each driver's main() logs the JAX backend, the device kind and count,
and — for every Pallas kernel its flags enable — whether that kernel is
Mosaic-compiled or runs under the Pallas interpreter. The kernels choose
by backend (compiled on TPU, interpreted elsewhere, which is how the CPU
tests run them); this line is where that choice becomes visible.
chip_smoke.py asserts "compiled" on it.
"""

import logging
import os
from typing import Dict

import jax


def _mode(interpret: bool) -> str:
    return "interpreted" if interpret else "compiled"


def describe_backend(flags) -> Dict:
    """{"backend", "device_kind", "device_count", "pallas": {kernel:
    "compiled" | "interpreted"}} for the kernels `flags` turn on."""
    devices = jax.devices()
    backend = jax.default_backend()
    pallas = {}
    if getattr(flags, "vtrace_impl", None) == "pallas":
        from torchbeast_tpu.ops import vtrace

        pallas["vtrace"] = _mode(vtrace._pallas_interpret())
    if getattr(flags, "opt_impl", None) == "pallas":
        from torchbeast_tpu.ops import pallas_opt

        pallas["opt_tail"] = _mode(pallas_opt._interpret_default())
    if getattr(flags, "attention_impl", None) == "pallas":
        from torchbeast_tpu.ops import pallas_attention

        pallas["attention"] = _mode(
            pallas_attention.attention_interpret_default()
        )
    # ops/pool.py takes the Pallas backward only on TPU; elsewhere the
    # switch selects nothing, so there is no kernel to report.
    if os.environ.get("TBT_POOL_PALLAS") == "1" and backend == "tpu":
        pallas["pool_bwd"] = "compiled"
    return {
        "backend": backend,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "pallas": pallas,
    }


def log_backend(log: logging.Logger, flags) -> Dict:
    report = describe_backend(flags)
    log.info(
        "Running on backend=%s device_kind=%s device_count=%d; "
        "Pallas kernels: %s",
        report["backend"], report["device_kind"], report["device_count"],
        ", ".join(f"{k}={v}" for k, v in report["pallas"].items())
        or "none enabled",
    )
    return report
