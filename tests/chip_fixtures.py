"""The described v5e every `tests/test_chip_compile*.py` compiles for,
in one place: the fixtures (`topo`, `one_chip`) and the shapes placed on
them. A test file imports the two fixtures by name; pytest then builds
them once a file (`scope="module"`).

A whole-cell compile is one AOT compile of the real cell, 150-210 s
with nothing to share or to jit away, so each has a file of its own
(`tests/test_chip_compile_<family>.py`): under `--dist loadfile` a file
is what a worker takes, and no file may be a worker's whole run. One
process at a time may load libtpu unless `ALLOW_MULTIPLE_LIBTPU_LOAD=1`
is in the environment, as it is in tier-1's command (ROADMAP.md): run
under xdist WITHOUT it, the files that lose the race skip at `topo`.

The persistent compile cache is off around these compiles: an
executable for a described device is written but cannot be read back
without a chip.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import (  # noqa: E402
    compilation_cache,
)
from jax.sharding import SingleDeviceSharding  # noqa: E402

# The flagship learner's batch, and every cell's: unroll 80, 32 rows.
T, B, NUM_ACTIONS = 80, 32, 6


@pytest.fixture(scope="module")
def topo():
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def on(sharding, tree):
    """ShapeDtypeStructs of `tree` placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), jnp.result_type(x), sharding=sharding
        ),
        tree,
    )


def struct(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def assert_conv_kernels(text, layers):
    """A cell's compiled `text` holds ops/short_conv.py's kernels for
    the `layers` that call `conv_over_episodes` (PR 67): the forward
    kernel twice a layer (the rematerialised block's second forward
    calls it again), the backward's once."""
    for kernel, calls in (
        ("short_conv_forward", 2 * layers), ("short_conv_backward", layers),
    ):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel, text
        )) == calls, kernel


def assert_scan_kernels(text, shapes, mixers, chunks, state_elements):
    """A cell's compiled `text` holds ops/ssd_scan.py's kernels for its
    `mixers` Mamba-2 layers (PR 65): the forward kernel twice a layer
    (the rematerialised block's second forward calls it again), the
    backward's once; and, among `shapes` (the text's float32 arrays'),
    none of a state a chunk ([.., c, H, P, N]: the `jax.numpy` form's
    `left` and `entering`) beside the carried states' own
    `state_elements` a layer."""
    for kernel, calls in (
        ("ssd_scan_forward", 2 * mixers), ("ssd_scan_backward", mixers),
    ):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel, text
        )) == calls, kernel
    states = {
        s for s in shapes
        if len(s) >= 4 and s[-1] == 128 and chunks in s[:-1]
        and int(np.prod(s)) == chunks * state_elements
    }
    assert not states, states
