"""The Kimi-delta linear-attention mixer (KDA: Kimi Linear, arXiv:2510.26692)
as Ling-3.0's layers run it, told which heads it holds.

With ``u`` the layer's normed input and H heads of ``head_dim`` channels
(keys, queries and values alike), ``[q, k, v, a, z, b] = u W_in`` (five of
width H head_dim, ``b`` of H):

    q, k, v = silu(conv(q)), silu(conv(k)), silu(conv(v))     causal,
                                      depthwise, ``conv`` taps, no bias
    q = q / ||q|| * head_dim^-1/2,  k = k / ||k||             a head
    log alpha = lower_bound * sigmoid(exp(A_log) (a + dt_bias))    a channel,
                                      in (lower_bound, 0): the safe gate
    beta = sigmoid(b)                                         a head
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                   (ops/delta_rule.py, over chunks)
    y = RMSNorm(o) * sigmoid(z)       within a head, one learned scale of
                                      ``head_dim`` for every head
    out = y W_out

``head_shard=(i, n)``: this process holds heads ``[i H/n, (i+1) H/n)`` — their
columns of ``W_in``, their channels of the convolution and of ``dt_bias``,
their ``A_log``, their rows of ``W_out`` — the local part of a layer that is
tensor-parallel over ``n`` chips: the norm never crosses a head, so nothing
but the sum of the ``n`` outputs is exchanged, and that sum is the caller's.
``(0, 1)`` is the whole layer.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models.ssm import (_a_log_init, _dt_bias_init,
                                    causal_depthwise_conv)
from horovod_tpu.ops.delta_rule import chunked_delta_rule

L2_EPS = 1e-6     # under the root of q's and k's norms


class DeltaConfig(NamedTuple):
    """Sizes of the whole Kimi-delta mixer of a layer (``TransformerLM(
    delta=)``): ``heads`` of ``head_dim`` channels, ``conv`` taps, the delta
    rule's ``chunk``, the gate's ``lower_bound`` on a step's log-decay."""

    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    lower_bound: float = -5.0


class DeltaMixer(nn.Module):
    """One mixer's share (module docstring), each stage under a
    ``jax.named_scope`` a trace can read: ``hvd_kda_in_proj``,
    ``hvd_kda_conv`` (with the silu and the two norms), ``hvd_kda_gate``,
    ``hvd_kda_scan`` (by stage beneath it: ``ops/delta_rule.py``),
    ``hvd_kda_gate_norm``, ``hvd_kda_out_proj``.  Writes
    ``kda_chunk_log_decay_min`` to the ``intermediates`` collection where the
    caller makes it mutable."""

    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    lower_bound: float = -5.0
    head_shard: Tuple[int, int] = (0, 1)
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, u):
        shard, n_shards = self.head_shard
        if self.heads % n_shards or not 0 <= shard < n_shards:
            raise ValueError(f"head_shard {self.head_shard} does not divide "
                             f"{self.heads} heads")
        heads = self.heads // n_shards
        batch, seq, d = u.shape
        inner = heads * self.head_dim
        by_head = (batch, seq, heads, self.head_dim)
        w_in = self.param("in_proj_kernel", nn.initializers.lecun_normal(),
                          (d, 5 * inner + heads), jnp.float32)
        w_conv = self.param(
            "conv_kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=1),
            (self.conv, 3 * inner), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        scale = self.param("norm_scale", nn.initializers.ones,
                           (self.head_dim,), jnp.float32)
        w_out = self.param("out_proj_kernel", nn.initializers.lecun_normal(),
                           (inner, d), jnp.float32)

        with jax.named_scope("hvd_kda_in_proj"):
            qkv, a, z, b = jnp.split(
                jnp.dot(u.astype(self.dtype), w_in.astype(self.dtype)),
                [3 * inner, 4 * inner, 5 * inner], axis=-1)
        with jax.named_scope("hvd_kda_conv"):
            q, k, v = (t.reshape(by_head) for t in jnp.split(
                nn.silu(causal_depthwise_conv(qkv, w_conv)), 3, axis=-1))

            def unit(t):
                return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                     + L2_EPS)

            q, k = unit(q) * self.head_dim ** -0.5, unit(k)
        with jax.named_scope("hvd_kda_gate"):
            log_alpha = self.lower_bound * nn.sigmoid(
                jnp.exp(a_log)[:, None] * (a.astype(jnp.float32)
                                           + dt_bias).reshape(by_head))
            beta = nn.sigmoid(b.astype(jnp.float32))
        with jax.named_scope("hvd_kda_scan"):
            # The casts are the first of the rule's products inside a chunk:
            # every operation under this scope lies in one of its stages.
            with jax.named_scope("hvd_kda_scan_chunk"):
                q, k, v = (t.astype(self.dtype) for t in (q, k, v))
            o, decay_min = chunked_delta_rule(q, k, v, log_alpha, beta,
                                              min(self.chunk, seq))
            self.sow("intermediates", "kda_chunk_log_decay_min", decay_min)
        with jax.named_scope("hvd_kda_gate_norm"):
            mean_sq = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            gated = o * lax.rsqrt(mean_sq + self.norm_eps) * scale \
                * nn.sigmoid(z.astype(jnp.float32).reshape(by_head))
        with jax.named_scope("hvd_kda_out_proj"):
            return jnp.dot(gated.reshape(batch, seq, inner).astype(self.dtype),
                           w_out.astype(self.dtype))
