"""CNN_MNIST of the paper's code (src/models.py:11-31), plain jax.numpy.

28x28x1 -conv3x3(32)-relu-> 26 -conv3x3(64)-relu-> 24 -maxpool2-> 12 ->
flatten 9216 -> fc 128 -relu-> fc 10; VALID convolutions, NHWC, dropout off
(evaluation). `params` is the tree the program trains, read as a plain dict."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
WIDTHS = {"conv": (32, 64), "fc": 128}


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["kernel"], (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + p["bias"]


def forward(params, x):
    """x [n, H, W, 1] normalised float32 -> logits [n, n_classes]."""
    x = jax.nn.relu(_conv(x, params["Conv_0"]))
    x = jax.nn.relu(_conv(x, params["Conv_1"]))
    n, h, w, c = x.shape
    x = x[:, :h // 2 * 2, :w // 2 * 2].reshape(
        n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    x = x.reshape(n, -1)
    x = jax.nn.relu(jnp.dot(x, params["Dense_0"]["kernel"],
                            precision=HIGHEST) + params["Dense_0"]["bias"])
    return (jnp.dot(x, params["Dense_1"]["kernel"], precision=HIGHEST)
            + params["Dense_1"]["bias"])


def forward_flops(image_shape, n_classes: int = 10) -> float:
    """Multiply-adds x 2 of one example's forward pass, from shapes."""
    h, w, c = image_shape
    flops, cin = 0, c
    for cout in WIDTHS["conv"]:
        h, w = h - 2, w - 2
        flops += 2 * 9 * cin * cout * h * w
        cin = cout
    flat = (h // 2) * (w // 2) * cin
    return float(flops + 2 * flat * WIDTHS["fc"]
                 + 2 * WIDTHS["fc"] * n_classes)
