"""The grouped matmul's passes, by the precision it is traced under
(models/moe.py `grouped_matmul`, ops/bf16_terms.py), and the kernels
that cut their operands into bfloat16 terms in VMEM (ops/grouped_
matmul.py, interpreted here) against a float64 product."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("terms", [1, 2, 3])
def test_bf16_terms_add_up_to_the_operand(terms):
    """An operand cut into bfloat16 terms: the first is the plain cast,
    each further term takes eight more bits of what the others left."""
    from torchbeast_tpu.models import moe

    x = jax.random.normal(jax.random.PRNGKey(3), (64, 48)) * 7.0
    cut = jax.jit(lambda x: moe._bf16_terms(x, terms))
    parts = cut(x)
    assert len(parts) == terms
    assert all(part.dtype == jnp.bfloat16 for part in parts)
    np.testing.assert_array_equal(parts[0], x.astype(jnp.bfloat16))
    total = sum(np.asarray(part, np.float64) for part in parts)
    left = np.abs(total - np.asarray(x, np.float64))
    assert np.all(left <= 2.0 ** (-8 * terms) * np.abs(x))
    if terms < 3:
        assert np.any(left > 2.0 ** (-8 * terms - 4) * np.abs(x))


@pytest.mark.parametrize(
    "precision, terms, error",
    [(None, 1, 2.0**-8), ("default", 1, 2.0**-8),
     ("high", 2, 2.0**-15), ("highest", 3, 2.0**-21)],
    ids=["unset", "default", "high", "highest"],
)
def test_grouped_matmul_passes_follow_the_traced_precision(
    monkeypatch, precision, terms, error
):
    """On the chip a grouped matmul traced under `high` or `highest`
    is ONE call of the kernel that cuts its float32 operands into two
    or three bfloat16 terms itself (ops/grouped_matmul.py), else the
    one call of the shipped kernel on one cast that it always was:
    forward and in both gradients. (What the terms' passes are worth
    is `test_kernels_that_cut_in_vmem_against_a_float64_product`'s;
    the one cast's, a stand-in's here that multiplies what it is given
    exactly.)"""
    from torchbeast_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, k, n = 128, 96, 40
    lhs = jax.random.normal(jax.random.PRNGKey(0), (rows, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (1, k, n))
    sizes = jnp.array([rows], jnp.int32)

    calls = []
    for made, module in (
        ("shipped", moe._megablox), ("cut_in_vmem", moe._cut_in_vmem)
    ):
        for name in ("gmm", "tgmm"):
            kernel = getattr(module, name)
            monkeypatch.setattr(
                module, name,
                lambda *a, _kernel=kernel, _as=(made, name), **k: (
                    calls.append(_as + (k.get("terms", 1),)),
                    _kernel(*a, **k),
                )[1],
            )

    def loss(lhs, rhs):
        # As a model that sets its precision inside its own call: the
        # gradient's kernels are traced after the context has ended.
        with jax.default_matmul_precision(precision):
            assert moe._terms_traced_under() == terms
            y = moe.grouped_matmul(lhs, rhs, sizes)
        return jnp.sum(jnp.sin(y))

    made = "shipped" if terms == 1 else "cut_in_vmem"
    jax.eval_shape(loss, lhs, rhs)
    assert calls == [(made, "gmm", terms)]
    del calls[:]
    jax.eval_shape(jax.grad(loss, argnums=(0, 1)), lhs, rhs)
    assert sorted(calls) == [(made, "gmm", terms)] * 2 + [
        (made, "tgmm", terms)
    ]
    del calls[:]

    def exact(lhs, rhs, sizes, *args, **kwargs):
        if terms == 1:  # the shipped kernel's place: one cast a side
            assert lhs.dtype == rhs.dtype == jnp.bfloat16
            assert not kwargs["interpret"]
        else:  # the cutting kernel's: the operands as they are
            assert lhs.dtype == rhs.dtype == jnp.float32
            assert kwargs == {"terms": terms, "tm": rows}
        calls.append(1)
        return jnp.dot(
            lhs.astype(jnp.float32), rhs[0].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )

    monkeypatch.setattr(
        moe._megablox if terms == 1 else moe._cut_in_vmem, "gmm", exact
    )
    # One trace: the stand-in counts its calls as the trace makes them.
    got_fn = jax.jit(
        lambda lhs, rhs: moe._gmm_call("gmm", lhs, rhs, sizes, rows, terms)
    )
    got = got_fn(lhs, rhs)
    want = np.asarray(lhs, np.float64) @ np.asarray(rhs[0], np.float64)
    scale = np.abs(np.asarray(lhs)) @ np.abs(np.asarray(rhs[0]))
    assert len(calls) == 1
    worst = np.max(np.abs(np.asarray(got, np.float64) - want) / scale)
    if terms == 1:
        assert error / 64 < worst <= error
    else:  # handed over uncut
        assert worst <= 2.0**-22


# Rows in ragged groups over row tiles of 128: an empty group, one
# across several tiles, and by layout (sizes, groups held, first):
# every group held; a rung, whose last rows are no held expert's;
# a share, whose first and last rows are other experts'.
CUT_LAYOUTS = {
    "all_held": ([40, 0, 300, 172], 4, None),
    "rung": ([40, 0, 300, 72, 100], 4, 0),
    "share_from_1": ([60, 40, 0, 300, 112], 3, 1),
}


@pytest.mark.parametrize("layout", sorted(CUT_LAYOUTS))
@pytest.mark.parametrize("kernel", ["gmm", "gmm_transposed", "tgmm"])
@pytest.mark.parametrize("terms, error", [(2, 2.0**-15), (3, 2.0**-21)])
def test_kernels_that_cut_in_vmem_against_a_float64_product(
    layout, kernel, terms, error
):
    """ops/grouped_matmul.py's `gmm` (and on transposed weights) and
    `tgmm`, interpreted: each float32 tile cut into `terms` bfloat16
    terms inside the kernel, the terms' products summed in float32,
    against the float64 product of the same operands, group by group.
    Two terms are worth `high`'s 2^-15 of the product of the
    magnitudes and NOT a sixty-fourth of it (the cut really happens:
    the kernel does not multiply the float32 operands), three
    `highest`'s 2^-21; rows of groups that are not held come out as
    zeros, an empty group's gradient as zeros."""
    from torchbeast_tpu.ops import grouped_matmul

    sizes, held, first = CUT_LAYOUTS[layout]
    m, k, n = sum(sizes), 96, 40
    keys = jax.random.split(jax.random.PRNGKey(terms), 3)
    lhs = jax.random.normal(keys[0], (m, k))
    offset = {} if first is None else {"group_offset": jnp.int32(first)}
    common = dict(terms=terms, tm=128, interpret=True, **offset)
    if kernel == "tgmm":
        rhs = jax.random.normal(keys[1], (m, n))
        got = grouped_matmul.tgmm(
            lhs, rhs, jnp.array(sizes, jnp.int32), num_actual_groups=held,
            **common,
        )
    else:
        rhs = jax.random.normal(keys[1], (held, k, n))
        transposed = kernel == "gmm_transposed"
        got = grouped_matmul.gmm(
            lhs, rhs.swapaxes(1, 2) if transposed else rhs,
            jnp.array(sizes, jnp.int32), transpose_rhs=transposed, **common,
        )
    got = np.asarray(got, np.float64)
    lhs64, rhs64 = np.asarray(lhs, np.float64), np.asarray(rhs, np.float64)
    ends = np.cumsum([0] + sizes)
    worst, visited = 0.0, np.zeros(m, bool)
    for c in range(held):
        rows = slice(ends[(first or 0) + c], ends[(first or 0) + c + 1])
        visited[rows] = True
        if kernel == "tgmm":
            a, b, mine = lhs64[rows].T, rhs64[rows], got[c]
        else:
            a, b, mine = lhs64[rows], rhs64[c], got[rows]
        if rows.start == rows.stop:
            assert not mine.any()
            continue
        worst = max(worst, np.max(
            np.abs(mine - a @ b) / (np.abs(a) @ np.abs(b))
        ))
    assert worst <= error
    if terms == 2:
        assert worst > error / 64
    if kernel == "tgmm":
        assert got.shape == (held, k, n)
    else:
        assert got.shape == (m, n) and not got[~visited].any()
        assert visited.sum() < m or layout == "all_held"
