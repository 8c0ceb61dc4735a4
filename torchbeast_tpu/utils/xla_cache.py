"""Where the persistent XLA compile cache lives.

`JAX_COMPILATION_CACHE_DIR` decides when it is set: JAX reads that
variable itself, so nothing is configured in code and whoever launches
the program can place the cache. Otherwise the cache is one fixed
directory inside the checkout. The directory is part of what makes an
entry findable again, so it never depends on the host, the user's home,
a pid or a temp name — every entry point of one checkout (drivers,
chip_smoke.py, the tests, the benchmark) shares one set of compiles.

The directory is listed in .gitignore, .dockerignore and
.chiprunignore: a copied checkout starts cold instead of loading XLA:CPU
entries compiled for another machine's ISA.
"""

import os

import jax

IN_CHECKOUT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one place; returns
    the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", IN_CHECKOUT_DIR)
    return IN_CHECKOUT_DIR
