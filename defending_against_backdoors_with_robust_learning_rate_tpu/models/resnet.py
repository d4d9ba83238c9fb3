"""ResNet-9 for the BASELINE.json north-star configs[3-4] (cifar10 at scale).

The reference has no ResNet (its CIFAR model is a 3-conv CNN, src/models.py:
33-58); BASELINE.json explicitly asks for "cifar10 ResNet-9" (SURVEY.md
2.3.11), so this is a framework extension. Design choices, TPU/FL-native:

- GroupNorm instead of BatchNorm: the reference's models have no BN (so the
  flat-parameter-vector currency carries no running stats); GroupNorm keeps
  that property — all state is parameters, so FedAvg/comed/sign/RLR apply
  unchanged to every tensor — and avoids cross-client BN-statistic leakage.
- NHWC, 3x3 SAME convs, classic DAWNBench ResNet-9 topology:
  conv(64) -> conv(128)+pool -> residual(128) -> conv(256)+pool
  -> conv(512)+pool -> residual(512) -> global maxpool -> fc, output scaled
  by 0.125 (the standard ResNet-9 logit scale).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


class ConvGN(nn.Module):
    width: int
    pool: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(self.width, (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(x)
        # names the MXU output for the selective remat policy below; a
        # transparent no-op under no remat / full blockwise remat
        x = checkpoint_name(x, "conv_out")
        x = nn.GroupNorm(num_groups=min(32, self.width),
                         dtype=self.dtype)(x)
        x = nn.relu(x)
        if self.pool:
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        return x


class Residual(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        y = ConvGN(self.width, dtype=self.dtype)(x)
        y = ConvGN(self.width, dtype=self.dtype)(y)
        return x + y


class ResNet9(nn.Module):
    n_classes: int = 10
    dtype: Any = jnp.float32
    # rematerialization (jax.checkpoint via nn.remat): backward recomputes
    # what does not fit the device instead of stashing it — the standard
    # TPU trade of FLOPs for HBM. Exact (bitwise-equal grads); needed when
    # many agents' ResNet batches are vmapped on one chip: all 40 agents x
    # bs 256 at once stash ~19 GB un-remated, > v5e's 16 GB HBM. The
    # benchmark's shape, ten agents at once, stashes a quarter of that
    # and fits with nothing recomputed (PERF.md section 6, PR 30).
    remat: bool = False
    # remat_policy (active only when remat=True):
    #   "block" — save block inputs only, recompute EVERYTHING in backward:
    #             a fourth forward-equivalent of MXU work per step where
    #             the algorithm needs three
    #   "conv"  — additionally save the named conv (MXU) outputs and
    #             recompute only the cheap elementwise tail (GN, relu,
    #             pool): none of the conv recompute, for `conv_out` bytes
    #             per example in flight
    #   "none"  — recompute nothing: the plain modules, the program
    #             remat=False builds
    # The user's `--remat_policy auto` is resolved to one of the three
    # from the device's memory before a model is built
    # (utils/compile_cache.resolved_remat); this module takes the result.
    remat_policy: str = "block"

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        if not self.remat or self.remat_policy == "none":
            Conv, Res = ConvGN, Residual
        elif self.remat_policy == "conv":
            pol = jax.checkpoint_policies.save_only_these_names("conv_out")
            Conv = nn.remat(ConvGN, policy=pol)
            Res = nn.remat(Residual, policy=pol)
        else:
            Conv, Res = nn.remat(ConvGN), nn.remat(Residual)
        # explicit names: nn.remat prefixes auto-generated module names
        # ("CheckpointConvGN_0"), which would fork the param tree between
        # remat on/off — same tree means checkpoints interchange freely
        x = x.astype(self.dtype)
        x = Conv(64, dtype=self.dtype, name="ConvGN_0")(x)
        x = Conv(128, pool=True, dtype=self.dtype, name="ConvGN_1")(x)
        x = Res(128, dtype=self.dtype, name="Residual_0")(x)
        x = Conv(256, pool=True, dtype=self.dtype, name="ConvGN_2")(x)
        x = Conv(512, pool=True, dtype=self.dtype, name="ConvGN_3")(x)
        x = Res(512, dtype=self.dtype, name="Residual_1")(x)
        x = jnp.max(x, axis=(1, 2))          # global max pool
        x = nn.Dense(self.n_classes, dtype=self.dtype)(x)
        return (x * 0.125).astype(jnp.float32)
