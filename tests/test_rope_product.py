"""`token_ops.rope_half` as one product a direction (constant cos / sin
tables, the rotation as a signed permutation, a custom backward) against
the formulation it replaced, kept here verbatim as `rope_half_parent`: in
every layout the token models use, the forward and the gradient equal it
bit for bit, also under `jax.checkpoint`, and the passed widths are x.

Equality is taken operation by operation (`jax.disable_jit`): compiled,
XLA's CPU backend may contract `x c + rot s` into one multiply-add in
either formulation, and where it does so in one and not the other the two
differ by one rounding of float32. That much, and no more, is allowed
under `jax.jit`. A bfloat16 x is taken as its float32 value, as
`swa_moe.rope` hands it over: the gradient is rounded to bfloat16 once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
    lfm2_moe as lm, swa_moe as sm, token_ops)


def rope_half_parent(x, inv_freq, scale: float = 1.0):
    """Rotate-half rotary embedding, `x cos + rot(x) sin` with `rot(x) =
    [-x2, x1]`, over the first `2 * len(inv_freq)` widths of a head, the
    rest passed through; x [B, T, n, d], positions from 0. `scale`
    multiplies cos and sin (YaRN's attention factor)."""
    d, t, r = x.shape[-1], x.shape[1], 2 * inv_freq.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    xr = x if r == d else x[..., :r]
    x1, x2 = xr[..., :r // 2], xr[..., r // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)

    def scaled(fn):
        return fn(ang) if scale == 1.0 else fn(ang) * scale

    out = xr * scaled(jnp.cos) + rot * scaled(jnp.sin)
    return out if r == d else jnp.concatenate([out, x[..., r:]], axis=-1)


def _layout(name):
    """(inv_freq, scale, head width) of one model's rotary step."""
    if name == "lfm2":
        spec = lm.spec_from("lfm2-8b-a1b", "0,2,3,4,5", 8, 0, 16384)
        d = spec.head_dim
        with jax.ensure_compile_time_eval():
            inv = 1.0 / (spec.rope_theta ** (
                jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        return np.asarray(inv), 1.0, d
    spec = sm.spec_from("laguna-xs.2", "0,1,2,3,4", 16, 0, 12544)
    r = dict(spec.rope)[sm.WINDOW if name == "window" else sm.FULL]
    return sm.rope_inv_freq(r), r.scale, spec.head_dim


LAYOUTS = ["window", "full", "lfm2"]


def test_the_layouts_are_the_models():
    """Laguna's window layers rotate all 128 widths, its full layers 64 of
    128 under YaRN's factor; LFM2 all 64 of its heads."""
    rotated = {name: (2 * len(_layout(name)[0]), _layout(name)[2])
               for name in LAYOUTS}
    assert rotated == {"window": (128, 128), "full": (64, 128),
                       "lfm2": (64, 64)}
    assert _layout("full")[1] == pytest.approx(1.4159, abs=1e-4)
    assert _layout("window")[1] == _layout("lfm2")[1] == 1.0


def _data(d, r, dtype, t=64):
    """x [2, t, 3, d] in `dtype` with negative zeros among the passed
    widths, and a float32 cotangent. (Where both widths of a rotated pair
    are zero, the product's sum gives +0 where the parent may give -0.)"""
    kx, kg = jax.random.split(jax.random.PRNGKey(37))
    x = jax.random.normal(kx, (2, t, 3, d), jnp.float32)
    x = x.at[0, 1, :, r:].set(-0.0)
    g = jax.random.normal(kg, (2, t, 3, d), jnp.float32)
    return x.astype(dtype), g


def _vjp(fn, x, g, checkpoint):
    fn = jax.checkpoint(fn) if checkpoint else fn
    y, pull = jax.vjp(fn, x)
    return y, pull(g)[0]


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", LAYOUTS)
def test_forward_and_gradient_equal_the_parent_bit_for_bit(name, dtype,
                                                           checkpoint):
    inv, scale, d = _layout(name)
    x, g = _data(d, 2 * len(inv), dtype)
    with jax.disable_jit():
        y, dx = _vjp(lambda x: token_ops.rope_half(x, inv, scale), x, g,
                     checkpoint)
        y_ref, dx_ref = _vjp(
            lambda x: rope_half_parent(x.astype(jnp.float32),
                                       jnp.asarray(inv), scale),
            x, g, checkpoint)
    assert y.dtype == jnp.float32 and dx.dtype == x.dtype
    np.testing.assert_array_equal(_bits(y), _bits(y_ref))
    np.testing.assert_array_equal(_bits(dx), _bits(dx_ref))
    # the passed widths are x, and their gradient g, bit for bit
    r = 2 * len(inv)
    np.testing.assert_array_equal(_bits(y[..., r:]),
                                  _bits(x[..., r:].astype(jnp.float32)))
    np.testing.assert_array_equal(_bits(dx[..., r:]),
                                  _bits(g[..., r:].astype(x.dtype)))


@pytest.mark.parametrize("name", LAYOUTS)
def test_compiled_it_stays_within_one_rounding(name):
    inv, scale, d = _layout(name)
    x, g = _data(d, 2 * len(inv), jnp.float32)

    @jax.jit
    def both(x, g):
        return (_vjp(lambda x: token_ops.rope_half(x, inv, scale), x, g,
                     True),
                _vjp(lambda x: rope_half_parent(x, jnp.asarray(inv), scale),
                     x, g, True))

    (y, dx), (y_ref, dx_ref) = both(x, g)
    ulp = float(jnp.finfo(jnp.float32).eps) * 2 * scale
    np.testing.assert_allclose(y, y_ref, rtol=0,
                               atol=ulp * float(jnp.max(jnp.abs(x))))
    np.testing.assert_allclose(dx, dx_ref, rtol=0,
                               atol=ulp * float(jnp.max(jnp.abs(g))))


def test_the_tables_and_the_rotation():
    """c is cos times the scale on the rotated widths and 1 on the passed
    ones, s sin and 0; every column of R holds one ±1 on the rotated widths
    and none on the passed."""
    inv, scale, d = _layout("full")
    c, s, rot = token_ops._rope_tables(16, d, tuple(inv.tolist()), scale)
    r, h = 2 * len(inv), len(inv)
    ang = np.arange(16, dtype=np.float32)[:, None] * inv[None, :]
    np.testing.assert_allclose(c[:, :h], np.cos(ang) * scale, atol=1e-6)
    np.testing.assert_allclose(s[:, h:r], np.sin(ang) * scale, atol=1e-6)
    assert (c[:, r:] == 1).all() and (s[:, r:] == 0).all()
    assert (np.abs(rot[:, :r]).sum(axis=0) == 1).all()
    assert (rot[:, r:] == 0).all() and (rot[r:] == 0).all()
    x = np.arange(d, dtype=np.float32)
    np.testing.assert_array_equal(
        x @ rot, np.concatenate([-x[h:r], x[:h], np.zeros(d - r)]))
