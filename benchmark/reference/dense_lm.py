"""Plain float32 reference of the dense decoder block the repository runs
(`models.TransformerLM`), written out in `jax.numpy` with no kernel, no
mixed precision and no framework: embedding, then per layer RMSNorm -> q, k,
v -> rotary (adjacent pairs, whole head) -> causal softmax attention -> output
projection -> residual -> RMSNorm -> up -> tanh-GELU -> down -> residual, then
RMSNorm, the untied head and the mean next-token cross-entropy.

It reads the system's own parameter tree (flax names), so both sides start
from the same seeded weights.  The caller traces it under
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul is done
in bfloat16 passes otherwise.

Tolerances (used by benchmark/builders/dense_lm.py), each with its reason:
the system computes in bfloat16 (8 bits of mantissa, 2^-8 = 0.4 % a rounding)
with float32 accumulation and stores the logits in bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Each limit is two to seven times what the chip showed over the seeds of
# PR 22 (PERF.md section 6), and far under what the next lower precision
# would give: an 8-bit float has 3-4 bits of mantissa against bfloat16's 8,
# so its roundings are 16 times larger.
#
# |loss_system - loss_reference| / loss_reference.  The loss is a mean over
# thousands of tokens of a log-softmax over 50k bfloat16-rounded logits: the
# roundings average out.  Measured 1e-6 to 7e-5; seven times that, because
# what is left is a cancellation and varies more than the others.
LOSS_RTOL = 5e-4
# ||g_system - g_reference|| / ||g_reference|| over all parameters.  Every
# matmul operand is rounded to bfloat16 forward and backward through 16
# layers, and the errors add in quadrature.  Measured 0.0061 to 0.0088.
GRAD_RTOL = 2.5e-2
# | ||g_system|| / ||g_reference|| - 1 |: the norm sees only the component
# of the error along the gradient.  Measured 0.0024 to 0.0042.
GRAD_NORM_RTOL = 1e-2
# Flash attention in bfloat16 against `attention` below in float32 on the same
# bfloat16-rounded q, k, v at 8,192 keys.  These are maxima over two million
# elements, so they vary with the seed more than the norms above.  Forward:
# the result is stored in bfloat16, whose half-ulp is 0.0078 for |out| in
# [2, 4) and 0.0156 in [4, 8) (the first rows attend to few keys and keep
# the spread of v), plus one rounding of the probabilities; measured 0.0045
# to 0.0120 absolute over 16 seeds.  Gradients: measured 0.0028 to 0.0094 of
# the reference gradient's maximum.  An 8-bit float would be past 0.1.
FLASH_FWD_ATOL = 3e-2
FLASH_GRAD_RTOL = 3e-2   # max error over the reference gradient's max

RMS_EPS = 1e-6           # flax.linen.RMSNorm's default, which the model uses
ROPE_BASE = 10000.0


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + RMS_EPS) * scale


def rotary(x):
    """(batch, heads, seq, head_dim): pairs (x[2i], x[2i+1]) turn by
    position * base^(-i / (head_dim / 2))."""
    seq, head_dim = x.shape[-2], x.shape[-1]
    half = head_dim // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1)
    return turned.reshape(x.shape)


def attention(q, k, v, block: int = 512):
    """Causal softmax attention, (batch, heads, seq, head_dim), plain softmax
    over whole rows of keys, one block of query rows at a time so that a long
    sequence's scores never exist all at once."""
    seq, head_dim = q.shape[-2], q.shape[-1]
    block = min(block, seq)
    key_pos = jnp.arange(seq)
    outs = []
    for start in range(0, seq, block):
        qb = q[..., start:start + block, :]
        scores = jnp.einsum("bhqd,bhkd->bhqk", qb, k) * head_dim ** -0.5
        rows = start + jnp.arange(qb.shape[-2])
        scores = jnp.where(rows[:, None] >= key_pos[None, :], scores,
                           -jnp.inf)
        outs.append(jnp.einsum("bhqk,bhkd->bhqd",
                               jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs, axis=-2)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def logits(params, tokens):
    """`params`: the flax tree of models.TransformerLM; `tokens`: (batch,
    seq) ids.  Float32 throughout."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    layer = 0
    while f"layer_{layer}" in params:
        p = params[f"layer_{layer}"]
        h = rms_norm(x, f32(p["attn_norm"]["scale"]))
        q, k, v = jnp.einsum("bsd,djhe->jbhse", h,
                             f32(p["attn"]["qkv_kernel"]))
        out = attention(rotary(q), rotary(k), v)
        x = x + jnp.einsum("bhse,hed->bsd", out, f32(p["attn"]["o_kernel"]))
        h = rms_norm(x, f32(p["mlp_norm"]["scale"]))
        h = gelu_tanh(h @ f32(p["up"]["kernel"]))
        x = x + h @ f32(p["down"]["kernel"])
        layer += 1
    x = rms_norm(x, f32(params["final_norm"]["scale"]))
    return x @ f32(params["lm_head_kernel"])


def loss(params, batch):
    """Mean next-token cross-entropy; `batch` is (inputs, targets)."""
    inputs, targets = batch
    logp = jax.nn.log_softmax(logits(params, inputs), axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -picked.mean()
