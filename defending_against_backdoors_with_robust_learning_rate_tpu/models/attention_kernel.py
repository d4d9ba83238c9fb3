"""The TPU lowering of `token_ops.causal_attention`: JAX's own Pallas
splash-attention kernel (`jax.experimental.pallas.ops.tpu.splash_attention`:
a forward kernel and the backward kernels, dq and dkv or the two fused, under
one `custom_vjp`), which keeps a block's float32 scores, their running
maximum and sum and the accumulators in VMEM and never writes a score to
HBM. The products take the operands' dtype and accumulate in float32, as the
plain path's do. Blocks wholly above the diagonal, or wholly below a window's
band, are never visited; the partial ones are masked inside the kernel.

One kernel path for every token model: the mask (causal, or a band of
`window` keys), the heads, the grouping and the widths are read off the
arguments. Grouped query heads index their key-value head inside the kernel
(k and v are never repeated in HBM), and a head of 192 or 64 widths goes in
as it is: Mosaic pads a tile to the 128 lanes in VMEM, with zeros that add
nothing to a score. What the kernel cannot take (a sequence that is not a
whole number of 128-lane blocks) stays on the plain path: `plan` answers
that from the shape alone, and `token_ops.causal_attention` asks it, and
`on_tpu`, when a program is traced."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as masks)

LANES = 128
# how a layer's backward is made: ONE kernel that makes dq with dk and dv (a
# float32 dq a key block, summed after), or two that each recompute the scores
FUSED, APART = "fused", "apart"


def on_tpu() -> bool:
    """Whether programs traced in this process are built for the TPU."""
    return jax.default_backend() == "tpu"


class Plan(NamedTuple):
    """How the kernel cuts a sequence: the widths of a query block and of a
    key block, the columns of a key block one product takes, and the
    backward (FUSED or APART)."""
    q: int
    k: int
    compute: int
    backward: str


# The most a block is wide under each kind of mask (a sequence takes the
# widest power-of-two multiple of LANES under these that divides it), chosen
# on the chip with the core alone at the cells' four layouts (PERF.md section
# 6, PR 35): on the v5e the kernel is bound by the softmax's vector work, not
# the products, so wide blocks (fewer steps) win wherever the mask leaves
# them full: 1024 x 1024 with products of 512 columns and the fused backward
# under a causal mask (one layer's core, forward + recompute + backward:
# MLA 25.6 -> 15.9 ms, LFM2 13.9 -> 8.3, Laguna full 60.8 -> 18.8).
CAUSAL = Plan(1024, 1024, 512, FUSED)
# Under a band of 512 keys a block of 512 beats 256 and 1024 forward (6.75 ->
# 3.70 ms a layer's core at Laguna's 64 / 8 heads of 128), but every query
# block visits two key blocks to use half of them, and with the backward the
# core alone is 7% behind the plain path's blocks of 256 rows by 768 keys
# (forward + recompute + backward 14.28 -> 15.32 ms; fused 17.27). In the cell
# the layer still gains (`swa_window_attention_ms` and the eval boundary:
# PERF.md section 6, PR 35), and from a band of 1024 keys on the kernels win
# alone too (39.8 -> 19.5 ms), so every window takes the kernel both ways.
# The kernel forward over the plain path's backward read 11.82 ms alone and
# LOST in the cell (three window layers 2748 ms a round against 2631 with the
# kernels and 2722 plain; 0.1466 against 0.1503 rounds/s): not kept.
WINDOW = Plan(512, 512, 512, APART)


def _block(seq_len: int, most: int) -> int:
    b = LANES
    while b * 2 <= most and seq_len % (b * 2) == 0:
        b *= 2
    return b


def plan(seq_len: int, window=None):
    """The Plan the kernel runs a sequence of `seq_len` by, or None where
    it does not take it."""
    if seq_len % LANES:
        return None
    most, k_most = (CAUSAL, CAUSAL.k) if window is None else (
        # no wider than the band, in whole lanes
        WINDOW, max(LANES, min(WINDOW.k, window // LANES * LANES)))
    k_block = _block(seq_len, k_most)
    return most._replace(q=_block(seq_len, most.q), k=k_block,
                         compute=min(most.compute, k_block))


@functools.lru_cache(maxsize=32)
def _kernel(seq_len: int, heads: int, window, took: Plan, interpret: bool):
    """The kernel for one (sequence, heads, mask, plan), forward and
    backward at the same blocks: the mask's block tables are NumPy work
    over the whole [T / q_block, T / k_block] grid, done once and kept."""
    shape = (seq_len, seq_len)
    mask = (masks.CausalMask(shape) if window is None
            else masks.LocalMask(shape, window_size=(window - 1, 0),
                                 offset=0))
    dq = ({} if took.backward == FUSED
          else dict(block_q_dq=took.q, block_kv_dq=took.k))
    sizes = splash.BlockSizes(
        block_q=took.q, block_kv=took.k, block_kv_compute=took.compute,
        block_q_dkv=took.q, block_kv_dkv=took.k,
        block_kv_dkv_compute=took.compute,
        use_fused_bwd_kernel=took.backward == FUSED, **dq)
    # the tables become constants of whichever program is traced first;
    # built outside any trace they are plain arrays every later trace reads
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            masks.MultiHeadMask([mask] * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def _at_default_precision(fn):
    """`fn` with its kernels, forward and backward, traced at the default
    matmul precision whatever the process-wide setting: Mosaic refuses
    bfloat16 operands at float32 precision (as the TPU's grouped product
    does: `token_ops._expert_rows`), and the backward kernels are traced
    when the cotangent arrives, outside any context the forward opened."""
    default = functools.partial(jax.default_matmul_precision, "default")

    @jax.custom_vjp
    def run(*args):
        with default():
            return fn(*args)

    def fwd(*args):
        with default():
            return jax.vjp(fn, *args)

    def bwd(pull, g):
        with default():
            return pull(g)

    run.defvjp(fwd, bwd)
    return run


def attention(q, k, v, window, took: Plan, interpret=False):
    """`token_ops.causal_attention`'s contract through the kernel: q [B, T,
    H, d], k [B, T, KV, d], v [B, T, KV, dv] -> [B, T, H * dv]. The kernel
    takes a sequence head-major and scales nothing, so q is scaled by
    `d ** -0.5` in float32 before it is cast back to its dtype (exact for a
    head of 64; one more rounding of q otherwise)."""
    b, t, h, d = q.shape
    kernel = _kernel(t, h, window, took, interpret)
    qs = (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)
    run = jax.vmap(kernel)
    if q.dtype == jnp.bfloat16:
        run = _at_default_precision(run)
    o = run(*(a.transpose(0, 2, 1, 3) for a in (qs, k, v)))
    return o.transpose(0, 2, 1, 3).reshape(b, t, h * v.shape[-1])
