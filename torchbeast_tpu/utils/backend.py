"""What a run executes on, said once at start-up: each driver's main()
logs the JAX backend, the device kind and the device count."""

import logging

import jax


def log_backend(log: logging.Logger) -> None:
    devices = jax.devices()
    log.info(
        "Running on backend=%s device_kind=%s device_count=%d",
        jax.default_backend(), devices[0].device_kind, len(devices),
    )
