"""What a model says of itself to the update's stats, and how it folds.

A layer calls `sow_stat(self, "moe_assignments", value, "sum")`: the
name is the key the update's stats carry, and `fold` is how the value
joins the other layers' under that name: `sum` (counts: assignments,
applications, bytes a row), `max` (the worst layer: a load's unevenness),
`min` (the strongest of the layers' log-decays, models/ling3.py)
or `same` (what every layer says alike: the chunks an unroll's scan was
cut into). `learner.compute_loss` collects `COLLECTIONS` and calls
`folded`; polybeast makes the gauge `gauge_name` says of every such key.
Adding a counter is one `sow_stat` in the model that knows it.
"""

import flax
import jax.numpy as jnp

# `same` keeps the first: every layer says the same.
FOLDS = {
    "sum": jnp.add,
    "max": jnp.maximum,
    "min": jnp.minimum,
    "same": lambda first, again: first,
}
COLLECTIONS = tuple("stats_" + fold for fold in FOLDS)
# A stat is `<family>_<name>`, its gauge `<family>.<name>`.
FAMILIES = (
    "moe", "ssm", "delta", "conv", "loop", "attention", "shared", "hc",
    "obs", "mlp", "kda", "router", "experts",
)


def sow_stat(module, name: str, value, fold: str) -> None:
    """`value` (a scalar) into the update's stats under `name`, joined
    by `fold` with what this module already sowed under the name (a
    module applied again: models/ouro.py) and, in `folded`, with the
    other modules'. Nothing at init: a sown collection would end up in
    the checkpoint."""
    if gauge_name(name) is None:
        raise ValueError(
            f"stat {name!r} is no `<family>_<name>` of {FAMILIES}: "
            "it would get no gauge"
        )
    if module.is_initializing():
        return
    join = FOLDS[fold]
    module.sow(
        "stats_" + fold, name, jnp.float32(value),
        init_fn=lambda: None,
        reduce_fn=lambda had, new: new if had is None else join(had, new),
    )


def folded(variables) -> dict:
    """The sown stats of one `apply(..., mutable=COLLECTIONS)`, every
    name folded over the modules that sowed it, in their order; empty
    for a model that sows none."""
    stats = {}
    for fold, join in FOLDS.items():
        for path, leaf in flax.traverse_util.flatten_dict(
            variables.get("stats_" + fold, {})
        ).items():
            name = path[-1]
            stats[name] = join(stats[name], leaf) if name in stats else leaf
    return stats


def gauge_name(stat: str):
    """`moe.assignments` for `moe_assignments`; None for a key of the
    update's stats that is no family's (losses, returns, norms)."""
    family, _, name = stat.partition("_")
    return f"{family}.{name}" if family in FAMILIES and name else None
