"""The fused kernels of the chunked delta rule (`models/gdn_kernel.py`) in
Pallas interpret mode on the CPU, against the XLA lowering they replace on
the TPU (`gdn_moe.chunked_delta_rule`, itself tested against the token-by-
token recurrence in tests/test_gdn_moe.py): the output and the gradient of
every input in float32 and in bfloat16, over three chunks at a length the
chunk does not divide; a later token leaves every earlier output bit for
bit and reaches every later one through the state, to the fourth chunk; the
whole-tile
inverse against `inverse_by_halves`; the plan; and what a program traces on
either platform. Tiny shapes (one sequence, 2 key heads, 4 value heads of
128): nothing here is a device metric."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    attention_kernel, gdn_kernel as gk, gdn_moe as gm)

D = 128


def _inputs(t, dtype=jnp.float32, key=0, decay=0.3):
    """q, k [1, t, 2, 128] of unit length (q scaled as `gdn()` scales it),
    v [1, t, 4, 128], g, beta [1, t, 4], drawn by NumPy (nothing to
    compile)."""
    rng = np.random.default_rng(key)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((1, t, 2, D))) * D ** -0.5
    k = unit(rng.standard_normal((1, t, 2, D)))
    v = rng.standard_normal((1, t, 4, D))
    g = -rng.uniform(size=(1, t, 4)) * decay
    beta = 1 / (1 + np.exp(-rng.standard_normal((1, t, 4))))
    return (*(jnp.asarray(x, dtype) for x in (q, k, v)),
            *(jnp.asarray(x, jnp.float32) for x in (g, beta)))


def _kernel(dtype, t, key_heads=1):
    took = gk.Plan(gm.GDN_CHUNK, -(-t // gm.GDN_CHUNK), key_heads)
    return lambda *a: gk.chunked_delta_rule(*a, dtype, took, interpret=True)


def _xla(dtype):
    return lambda *a: gm.chunked_delta_rule(*a, dtype)


def _value_and_grads(fn, args, w):
    """The output and the gradient of `sum(o * w)` in every input, as
    `gdn()` takes it: under jax.checkpoint, the kernels' residuals kept."""
    fn = jax.checkpoint(fn, policy=gk.SAVED)
    return fn(*args), jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                               argnums=range(5))(*args)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("dtype,key_heads", [
    (jnp.float32, 1),
    (jnp.bfloat16, 2)])         # both key heads in one grid step
def test_the_kernel_matches_the_xla_rule_in_output_and_gradients(
        dtype, key_heads):
    """Three chunks, the last of 8 tokens. In float32 to the tolerance tests/test_gdn_moe.py holds the chunked
    rule to against the recurrence (the sums are taken in another order);
    in bfloat16 to that file's tolerance of bfloat16 products, and no
    further from the float32 rule than the XLA lowering in bfloat16 is."""
    t = 136
    args = _inputs(t, dtype)
    w = jnp.asarray(np.random.default_rng(7).standard_normal((1, t, 4, D)),
                    jnp.float32)
    got, grads = jax.jit(lambda *a: _value_and_grads(
        _kernel(dtype, t, key_heads), a, w))(*args)
    want, wants = jax.jit(lambda *a: _value_and_grads(_xla(dtype), a, w))(
        *args)
    assert got.dtype == jnp.float32 and got.shape == (1, t, 4, D)
    for g, wg in zip(grads, wants, strict=True):
        assert g.dtype == wg.dtype and g.shape == wg.shape
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        for name, g, wg in zip("qkvgb", grads, wants, strict=True):
            np.testing.assert_allclose(g, wg, rtol=1e-5, atol=1e-5,
                                       err_msg=name)
        return
    exact, exacts = jax.jit(lambda *a: _value_and_grads(
        _xla(jnp.float32), a, w))(*(x.astype(jnp.float32) for x in args))
    for name, g, wg, eg in zip("oqkvgb", (got,) + grads, (want,) + wants,
                               (exact,) + exacts, strict=True):
        g, wg = np.asarray(g, np.float32), np.asarray(wg, np.float32)
        assert np.abs(g - wg).max() < 0.05 * np.abs(wg).max(), name
        assert _rel(g, eg) <= 1.25 * _rel(wg, eg) + 1e-3, name


def test_a_later_token_leaves_earlier_outputs_and_reaches_later_chunks():
    """Every input moved at one token of the first chunk: the outputs
    before it come out bit for bit, and every output from it on moves, to
    the fourth chunk, which only the state carried across three chunks
    reaches (slow decays, so that it arrives above rounding)."""
    t, at = 200, 40
    kernel = jax.jit(_kernel(jnp.float32, t))
    q, k, v, g, beta = _inputs(t, decay=0.01, key=1)
    base = np.asarray(kernel(q, k, v, g, beta))
    moved = np.asarray(kernel(
        q.at[:, at].multiply(-1.0), k.at[:, at].multiply(-1.0),
        v.at[:, at].add(1.0), g.at[:, at].add(-0.5), beta.at[:, at].set(0.9)))
    np.testing.assert_array_equal(base[:, :at], moved[:, :at])
    assert (base[:, at:] != moved[:, at:]).any(axis=-1).all()


def _chunk_a(c, key=2, same_keys=False):
    """The delta rule's A for one chunk of c tokens, a head: beta_t (k_t .
    k_j) exp(G_t - G_j) below the diagonal."""
    _q, k, _v, g, beta = (np.asarray(x) for x in _inputs(c, key=key))
    k = k[0, :, 0]
    if same_keys:                       # one token repeated: A near 1
        k = np.broadcast_to(k[:1], k.shape)
    G = np.cumsum(g[0, :, 0])
    return jnp.asarray(np.tril(beta[0, :, 0, None] * (k @ k.T)
                               * np.exp(G[:, None] - G[None, :]), -1))


@pytest.mark.parametrize("c,same_keys", [(64, False), (64, True), (48, False),
                                         (16, False)])
def test_the_whole_tile_inverse_matches_inverse_by_halves(c, same_keys):
    a = _chunk_a(c, same_keys=same_keys)
    want = jax.jit(gm.inverse_by_halves)(a)
    got = jax.jit(gk.unit_lower_inverse)(a)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(np.asarray(got) @ (np.asarray(a) + np.eye(c)),
                               np.eye(c), atol=1e-5)


@pytest.mark.parametrize("t,dk,dv,want", [
    (4096, 128, 128, (64, 64)), (200, 128, 256, (64, 4)), (8, 128, 128, (64, 1)),
    (4096, 64, 128, None), (4096, 128, 192, None), (4096, 8, 8, None)])
def test_the_plan_follows_the_platform_and_the_widths(t, dk, dv, want,
                                                      monkeypatch):
    assert gk.plan(t, dk, dv, 64) is None
    monkeypatch.setattr(attention_kernel, "on_tpu", lambda: True)
    took = gk.plan(t, dk, dv, 64)
    assert (took if want is None else took[:2]) == want


def _traced(t, dk, grad=False, rule=None):
    """The jaxpr of the rule at [1, t, 2 | 4, dk] under `gdn()`'s
    checkpoint, or of the gradient of its sum."""
    q = jax.ShapeDtypeStruct((1, t, 2, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, t, 4, dk), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, t, 4), jnp.float32)
    fn = jax.checkpoint(functools.partial(rule or gm.delta_rule,
                                          dtype=jnp.bfloat16),
                        policy=gk.SAVED)
    if grad:
        fn = jax.grad(lambda *a, f=fn: jnp.sum(f(*a)), argnums=range(5))
    return str(jax.make_jaxpr(fn)(q, q, v, g, g))


def _kernels(text):
    return re.findall(r"\bname=(gdn_forward\w*|gdn_backward)", text)


@pytest.mark.parametrize("grad", [False, True])
def test_the_kernel_is_traced_only_for_the_tpu(grad, monkeypatch):
    """Here `delta_rule` traces `chunked_delta_rule` to the letter; traced
    as on the TPU, the forward kernel, or for a gradient the forward that
    keeps its residuals (the states and inverses, which the checkpoint
    saves) and the backward; no scan over chunks, no triangular solve."""
    plain = _traced(256, D, grad, rule=gm.chunked_delta_rule)
    assert _traced(256, D, grad) == plain and not _kernels(plain)
    monkeypatch.setattr(attention_kernel, "on_tpu", lambda: True)
    kernel = _traced(256, D, grad)
    assert _kernels(kernel) == (["gdn_forward_res", "gdn_backward"] if grad
                                else ["gdn_forward"])
    assert "scan" not in kernel and "triangular_solve" not in kernel
    # widths the kernel does not take stay on the XLA rule on the TPU too
    assert _traced(256, 64, grad) == _traced(256, 64, grad,
                                             rule=gm.chunked_delta_rule)
