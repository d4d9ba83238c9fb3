"""ResNet-9 (DAWNBench topology) as the program defines it, plain jax.numpy.

conv(64) -> conv(128)+pool -> residual(128) -> conv(256)+pool ->
conv(512)+pool -> residual(512) -> global max pool -> fc -> logits x 0.125.
Every conv is 3x3 SAME without bias, followed by GroupNorm(32 groups,
eps 1e-6, scale and bias per channel) and relu; a pool is 2x2 max, stride 2.
GroupNorm stands where the published network has BatchNorm (the program's
departure: all state is parameters, so the vote covers every tensor).
`params` is the tree the program trains, read as a plain dict."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
GROUPS, EPS, LOGIT_SCALE = 32, 1e-6, 0.125
# (name, width, pool) in order; a Residual holds two ConvGN of its width
PLAN = (("ConvGN_0", 64, False), ("ConvGN_1", 128, True),
        ("Residual_0", 128, False), ("ConvGN_2", 256, True),
        ("ConvGN_3", 512, True), ("Residual_1", 512, False))


def _conv_gn(x, p, pool):
    y = jax.lax.conv_general_dilated(
        x, p["Conv_0"]["kernel"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    n, h, w, c = y.shape
    g = y.reshape(n, h, w, GROUPS, c // GROUPS)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((g - mean) / jnp.sqrt(var + EPS)).reshape(n, h, w, c)
    y = jax.nn.relu(y * p["GroupNorm_0"]["scale"] + p["GroupNorm_0"]["bias"])
    if pool:
        y = y.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    return y


def forward(params, x):
    """x [n, 32, 32, 3] normalised float32 -> logits [n, n_classes]."""
    for name, _width, pool in PLAN:
        p = params[name]
        if name.startswith("Residual"):
            y = _conv_gn(x, p["ConvGN_0"], False)
            x = x + _conv_gn(y, p["ConvGN_1"], False)
        else:
            x = _conv_gn(x, p, pool)
    x = x.max(axis=(1, 2))
    logits = (jnp.dot(x, params["Dense_0"]["kernel"], precision=HIGHEST)
              + params["Dense_0"]["bias"])
    return logits * LOGIT_SCALE


def forward_flops(image_shape, n_classes: int = 10) -> float:
    """Multiply-adds x 2 of one example's convolutions and head."""
    h, w, cin = image_shape
    flops = 0
    for name, width, pool in PLAN:
        convs = 2 if name.startswith("Residual") else 1
        for _ in range(convs):
            flops += 2 * 9 * cin * width * h * w
            cin = width
        if pool:
            h, w = h // 2, w // 2
    return float(flops + 2 * cin * n_classes)
