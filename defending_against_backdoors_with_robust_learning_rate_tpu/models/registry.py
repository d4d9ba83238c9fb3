"""Model registry (reference: `get_model`, src/models.py:4-8)."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from defending_against_backdoors_with_robust_learning_rate_tpu.models.cnn import (
    CNN_MNIST, CNN_CIFAR)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.resnet import (
    ResNet9)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils.jaxprs import (
    iter_eqns)

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
REMAT_POLICIES = ("block", "conv", "none")


def get_model(data: str, arch: str = "cnn", dtype: str = "f32",
              n_classes: int = 10, remat: bool = False,
              remat_policy: str = "block", cfg=None):
    """fmnist/fedemnist -> CNN_MNIST; cifar10 -> CNN_CIFAR (src/models.py:4-8);
    arch='resnet9' selects the BASELINE north-star ResNet-9 extension.
    `remat` enables rematerialization (ResNet-9 only; the small CNNs'
    activations never pressure HBM); `remat_policy` picks full blockwise
    ("block") or selective save-conv-outputs ("conv") recompute, or none
    of either ("none": the model `remat=False` builds). It takes
    the RESOLVED policy: `--remat_policy auto` is a rule over the device's
    memory (utils/compile_cache.resolved_remat), not a model property.
    An arch in `TOKEN_ARCHS` is a token task's model (`lfm2_moe`: short
    convolutions, grouped-query attention, a 32-expert mixture; `mla_moe`:
    latent attention, a 256-expert mixture with a shared expert, a
    multi-token-prediction module; `swa_moe`: sliding-window and full
    attention mixed by layer with query heads and rotary embedding by layer
    kind, a 256-expert mixture with a shared expert; `gdn_moe`: Gated
    DeltaNet linear attention beside gated full attention, a 512-expert
    softmax-routed mixture with a gated shared expert): it reads its widths and its cut from
    `cfg`, which it needs, and under `remat` recomputes block by block. This module is the one place that knows a model by its
    name: the engine, the planner and the data layer ask the model (or
    `arch_takes_tokens`, `token_vocab`) what it is."""
    if remat_policy not in REMAT_POLICIES:
        raise ValueError(
            f"remat_policy must be one of {REMAT_POLICIES}, got "
            f"{remat_policy!r} (resolve 'auto' with "
            f"compile_cache.resolved_remat first)")
    dt = _DTYPES[dtype]
    if arch in TOKEN_ARCHS:
        if cfg is None:
            raise ValueError(f"arch {arch!r} reads its widths and its cut "
                             f"from the configuration: pass cfg=")
        return _token_module(arch).from_cfg(cfg, dtype=dt, remat=remat)
    if arch == "resnet9":
        return ResNet9(n_classes=n_classes, dtype=dt, remat=remat,
                       remat_policy=remat_policy)
    if data in ("fmnist", "fedemnist", "synthetic"):
        return CNN_MNIST(n_classes=n_classes, dtype=dt)
    if data == "cifar10":
        return CNN_CIFAR(n_classes=n_classes, dtype=dt)
    raise ValueError(f"no model for data={data!r} arch={arch!r}")


# archs whose batch is `[bs, T + 1]` token ids, and the module of each: it
# has `from_cfg(cfg, dtype, remat)` and `vocab_from_cfg(cfg)`, and its model
# carries `takes_tokens = True`, `pairs_shape`, `dispatch_rows(n_tokens)` and
# `build_counters(n_tokens, seq_len)`. Its forward returns `(logits, pairs)`;
# one that predicts further on than the next token returns, in training, a
# third value, the logits per token ahead, and carries `ahead_weight`
# (fl/task.make_batch_loss)
TOKEN_ARCHS = {"lfm2_moe": "lfm2_moe", "mla_moe": "mla_moe",
               "swa_moe": "swa_moe", "gdn_moe": "gdn_moe"}


def _token_module(arch: str):
    return importlib.import_module(
        f"{__package__}.{TOKEN_ARCHS[arch]}")


def arch_takes_tokens(arch: str) -> bool:
    return arch in TOKEN_ARCHS


def takes_tokens(model) -> bool:
    return bool(getattr(model, "takes_tokens", False))


def token_vocab(cfg) -> int:
    """Vocabulary rows the configured token model holds: what the token
    task's generator draws ids from (data/tokens.py)."""
    if not arch_takes_tokens(cfg.model_arch):
        raise ValueError(
            f"--arch={cfg.model_arch} is no token model: pass one of "
            f"--arch={'|'.join(TOKEN_ARCHS)}")
    return _token_module(cfg.model_arch).vocab_from_cfg(cfg)


def init_params(model, example_shape, key=None, batch: int = 2):
    """Parameters from one abstract batch of the task: `example_shape` is
    an image's [H, W, C], or a token sequence's [T]."""
    key = key if key is not None else jax.random.PRNGKey(0)
    if takes_tokens(model):
        # no shape of a parameter depends on T or on the batch, and the
        # whole init is one program (506M parameters drawn eagerly would
        # be a program a leaf)
        x = jnp.zeros((1, min(8, int(example_shape[0]))), jnp.int32)
        return jax.jit(lambda k: model.init(
            {"params": k}, x, train=False)["params"])(key)
    x = jnp.zeros((batch,) + tuple(example_shape), jnp.float32)
    return model.init({"params": key}, x, train=False)["params"]


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def abstract_params(model, image_shape):
    """The parameter tree as ShapeDtypeStructs: nothing materialized."""
    return jax.eval_shape(lambda: init_params(model, image_shape))


def named_activation_bytes(model, image_shape,
                           name: str = "conv_out") -> int:
    """Bytes per example of the activations the model tags
    `checkpoint_name(x, name)`, read off the jaxpr of one abstract forward
    at batch 1 in the model's own dtype: nothing is compiled or run. They
    are what `remat_policy="conv"` keeps per example in flight on top of
    what "block" keeps; a model that tags nothing (the CNNs) reads 0."""
    params = abstract_params(model, image_shape)
    x = jax.ShapeDtypeStruct((1,) + tuple(image_shape), jnp.float32)
    closed = jax.make_jaxpr(lambda p, x: model.apply(
        {"params": p}, x, train=True,
        rngs={"dropout": jax.random.PRNGKey(0)}))(params, x)
    return sum(v.aval.size * v.aval.dtype.itemsize
               for eqn in iter_eqns(closed)
               if eqn.primitive.name == "name" and eqn.params["name"] == name
               for v in eqn.outvars)


def flops_per_example(data: str, arch: str, image_shape,
                      n_classes: int = 10):
    """Analytic FORWARD FLOPs for one example through the registry's
    model (ISSUE 10: bench.py's MFU trajectory must be computable on any
    backend — XLA cost analysis needs a compile, this is arithmetic).

    Multiply-accumulates count as 2 FLOPs; elementwise tails (relu,
    pool, dropout, bias) are <1% on these architectures and are ignored
    — the same convention as the public MFU formulas. One fwd+bwd
    training step costs ~3x the forward (the standard 2x-backward
    estimate). Returns None for architectures without an analytic model
    here (resnet9) — callers fall back to XLA's cost analysis."""
    h, w, c = image_shape
    if arch == "resnet9":
        return None

    def conv(h, w, cin, cout, k=3):
        # VALID 3x3 conv: output (h-2)x(w-2), 2*k*k*cin*cout MACs/pixel
        ho, wo = h - (k - 1), w - (k - 1)
        return 2 * k * k * cin * cout * ho * wo, ho, wo

    flops = 0
    if data in ("fmnist", "fedemnist", "synthetic"):
        # CNN_MNIST: conv(32) -> conv(64) -> pool2 -> fc128 -> fc10
        f, h, w = conv(h, w, c, 32)
        flops += f
        f, h, w = conv(h, w, 32, 64)
        flops += f
        h, w = h // 2, w // 2
        flat = h * w * 64
        flops += 2 * flat * 128 + 2 * 128 * n_classes
        return float(flops)
    if data == "cifar10":
        # CNN_CIFAR: [conv(width) -> pool2] x (64, 128, 256) -> fc128
        # -> fc256 -> fc10
        cin = c
        for width in (64, 128, 256):
            f, h, w = conv(h, w, cin, width)
            flops += f
            h, w, cin = h // 2, w // 2, width
        flat = h * w * 256
        flops += 2 * flat * 128 + 2 * 128 * 256 + 2 * 256 * n_classes
        return float(flops)
    return None
