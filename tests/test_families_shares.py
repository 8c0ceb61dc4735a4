"""A share of a layer's experts, of every policy family that can hold
one: a case a (family, side) of ONE parametrised test
(tests/family_scaffold.py has the rule for the next family: an
`experts` entry there, no copy here). Apart from tests/test_families.py,
as tests/test_families_remat.py is: under `--dist loadfile` a file is
one worker's chain (ISSUE 49)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import family_scaffold as scaffold

def _value_and_pullback(f):
    """Jitted `(x, weight) -> (f(x), the gradient of sum(weight * f(x)))`,
    `weight` held constant; without one it is cos(f(x)), which makes the
    gradient that of sum(sin(f(x)))."""

    def program(x, weight=None):
        out, pull = jax.vjp(f, x)
        return out, pull(jnp.cos(out) if weight is None else weight)[0]

    return jax.jit(program)


SHARED = [n for n, toy in scaffold.FAMILIES.items() if toy.experts]


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("family", SHARED)
def test_the_expert_shares_add_up_to_the_uncut_layer(family, side):
    """The test that ties the share to the model (`Family.experts` has
    each family's counts and why): the routed parts of the shares, each
    holding its own slice of the uncut layer's expert weights under the
    same router and biases, plus the shared expert COUNTED ONCE (every
    chip computes it alike), add up to the uncut layer's output. Values
    and the gradient with respect to x, on the program and on the
    reference; a share's forward and gradient are one traced program
    (`held` is a field of the module: a program a share)."""
    toy = scaffold.FAMILIES[family]
    spec = toy.experts
    uncut, x, params = scaffold.expert_layer(family, seed=4, **spec.uncut)
    E, K = uncut.num_experts, uncut.top_k
    count = E // spec.shares
    p = dict(params["params"])
    if "e_score_correction_bias" in p:
        p["e_score_correction_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(9), (E,)
        )
    stacked = [name for name in p if name.startswith("w_")]
    assert all(p[name].shape[0] == E for name in stacked)

    def shared(x):
        return spec.shared(x, p) if spec.shared else jnp.zeros_like(x)

    def run(first, count):
        cut = dict(p, **{k: p[k][first : first + count] for k in stacked})
        if side == "program":
            layer = uncut.clone(held=None if count == E else (first, count))
            return lambda x: layer.apply({"params": cut}, x)
        config = spec.config(E, K, first, count)

        def experts(x):
            out = toy.reference._experts(x, cut, config)
            return out[0] if isinstance(out, tuple) else out

        return experts

    def routed(first):
        share = run(first, count)
        return lambda x: share(x) - shared(x)

    whole, grad_whole = _value_and_pullback(run(0, E))(x)
    weight = jnp.cos(whole)
    parts, grads = zip(*(
        _value_and_pullback(routed(first))(x, weight)
        for first in range(0, E, count)
    ))
    once, grad_once = _value_and_pullback(shared)(x, weight)
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)
    np.testing.assert_allclose(sum(parts) + once, whole, spec.tol, spec.tol)
    # No share is the whole, and the shared expert counted once a share
    # is not it either.
    assert float(jnp.max(jnp.abs(parts[0] + once - whole))) > 1e-3
    if spec.shared:
        assert float(
            jnp.max(jnp.abs(sum(parts) + spec.shares * once - whole))
        ) > 1e-3
    np.testing.assert_allclose(
        sum(grads) + grad_once, grad_whole, rtol=1e-4, atol=1e-5
    )
