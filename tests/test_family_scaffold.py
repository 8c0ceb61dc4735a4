"""The scaffold's own rule (tests/family_scaffold.py): a flax module is
traced, never run op by op, and traced ONCE a module and input shape."""

import jax
import pytest

from tests import family_scaffold as scaffold
from torchbeast_tpu.models import moe


def _layer():
    # A new instance each time: the memo is by the module's fields.
    return moe.DroplessMoE(d_ff=8, num_experts=4, top_k=2, shared_width=6)


def _init(x):
    return scaffold.init(_layer(), jax.random.PRNGKey(1), x)


def _apply(x):
    return scaffold.apply(_layer(), mutable=("losses",))(_init(x), x)


def _forward(x):
    model, params = scaffold.build("ouro")
    return scaffold.forward(model)(
        params, scaffold.inputs(0), model.initial_state(scaffold.B)
    )


def _expert_layer(x):
    layer, x, params = scaffold.expert_layer("qwen3next", held=(4, 4))
    return scaffold.apply(layer)(params, x)


@pytest.mark.parametrize(
    "call", [_init, _apply, _forward, _expert_layer],
    ids=["init", "apply", "forward", "expert_layer"],
)
def test_a_second_call_with_the_same_module_and_shapes_traces_nothing(
    monkeypatch, call
):
    """The memo holds: the same fields and shapes a second time hand XLA
    no program (a trace anew would be a compile request, whatever the
    caches then answer), and give the same values to the bit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 16))
    first = call(x)
    requests = scaffold.compile_requests(monkeypatch)
    again = call(x)
    assert not requests
    for a, b in zip(
        jax.tree_util.tree_leaves(first), jax.tree_util.tree_leaves(again)
    ):
        assert (a == b).all()
