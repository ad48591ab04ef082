"""The delta-rule linear-attention mixer, one module under either of two
gates over one rule (``ops/delta_rule.py``), told which heads it holds.

``gate="channel"`` — Kimi delta attention (KDA: Kimi Linear,
arXiv:2510.26692) as Ling-3.0's layers run it.  With ``u`` the layer's normed
input and H heads of ``head_dim`` channels (keys, queries and values alike),
``[q, k, v, a, z, b] = u W_in`` (five of width H head_dim, ``b`` of H):

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

``gate="head"`` — Gated DeltaNet (arXiv:2412.06464) as Qwen3-Next's layers
run it: ``heads`` KEY heads and ``value_heads`` value heads of ``head_dim``
channels, value head j reading the q and k of key head ``j // (value_heads /
heads)``; ``[q, k, v, z, b, a] = u W_in`` (q, k of heads x head_dim; v, z of
value_heads x head_dim; ``b``, ``a`` of value_heads); the lines above but

    log alpha = -exp(A_log) softplus(a + dt_bias)    a value HEAD, unbounded
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    y = RMSNorm(o) * silu(z)

the same recurrence with one decay for a head's every channel, which the rule
computes in its own form (no decay broadcast over channels, ``K K^T`` and ``Q
K^T`` once a key head).  Its stages run under ``hvd_gdn_*`` scopes where the
channel gate's run under ``hvd_kda_*``.  Under this gate alone,
``value_head_dim`` gives v, z, o, the gate norm's scale and ``W_out``'s rows a
width of their own beside q's and k's ``head_dim`` (the state is ``head_dim x
value_head_dim``), and ``beta_scale`` makes the step ``beta = beta_scale
sigmoid(b)``: at 2 the transition ``I - beta k k^T`` has an eigenvalue in (-1,
1) where the plain step keeps it in (0, 1) (flash-linear-attention's
``allow_neg_eigval``: Olmo-Hybrid's layers, 96 and 192 wide).

``head_shard=(i, n)``: this process holds heads ``[i H/n, (i+1) H/n)`` (key
and value heads alike) — their
columns of ``W_in``, their channels of the convolution and of ``dt_bias``,
their ``A_log``, their rows of ``W_out`` — the local part of a layer that is
tensor-parallel over ``n`` chips: the norm never crosses a head, so nothing
but the sum of the ``n`` outputs is exchanged, and that sum is the caller's.
``(0, 1)`` is the whole layer.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models.ssm import (L2_EPS, _a_log_init, _dt_bias_init,
                                    causal_depthwise_conv, mixer_opening)
from horovod_tpu.ops.delta_rule import chunked_delta_rule

# gate -> (the scopes' and the sown counter's prefix, the output gate).
GATES = {"channel": ("kda", nn.sigmoid), "head": ("gdn", nn.silu)}


class DeltaConfig(NamedTuple):
    """Sizes of the whole delta mixer of a layer (``TransformerLM(delta=)``):
    ``heads`` of ``head_dim`` channels, ``conv`` taps, the delta rule's
    ``chunk``, the channel gate's ``lower_bound`` on a step's log-decay, and
    ``value_heads`` where they outnumber the (key) ``heads`` — a ``"delta"``
    layer's (the channel gate) or a ``"gated_delta"`` layer's (the head
    gate).  The head gate's alone: ``value_head_dim``, a value head's width
    where it is not ``head_dim``, and ``beta_scale``, what multiplies the
    step's sigmoid."""

    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    lower_bound: float = -5.0
    value_heads: Optional[int] = None
    value_head_dim: Optional[int] = None
    beta_scale: float = 1.0


class DeltaMixer(nn.Module):
    """One mixer's share (module docstring), each stage under a
    ``jax.named_scope`` a trace can read, ``hvd_kda_`` (``gate="channel"``) or
    ``hvd_gdn_`` (``gate="head"``) and then: ``in_proj``,
    ``conv`` (with the silu and the two norms: under the head gate
    :func:`~horovod_tpu.models.ssm.mixer_opening`, float32 inside, which
    hands q, k and v over ROUNDED to ``dtype``, once, and keeps for its
    written-out backward the projection's output as stored and a float32 a
    token and key head; under the channel gate the plain composition, whose
    float32 q, k and v are rounded as the scan begins), ``gate``,
    ``scan`` (by stage beneath it: ``ops/delta_rule.py``),
    ``gate_norm``, ``out_proj``.  Writes ``kda_chunk_log_decay_min`` or
    ``gdn_chunk_log_decay_min`` to the ``intermediates`` collection where the
    caller makes it mutable, and where ``beta_scale`` is not 1 — a condition
    of Python, so that no other layer's program gains an operation —
    ``gdn_beta_over_one`` and ``gdn_beta_steps`` beside it: the (token,
    value head) steps with ``beta > 1``, and all of them."""

    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    lower_bound: float = -5.0
    value_heads: Optional[int] = None
    value_head_dim: Optional[int] = None
    beta_scale: float = 1.0
    gate: str = "channel"
    head_shard: Tuple[int, int] = (0, 1)
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, u):
        shard, n_shards = self.head_shard
        all_value_heads = self.value_heads or self.heads
        if self.gate not in GATES:
            raise ValueError(f"gate {self.gate!r} is none of {tuple(GATES)}")
        by_head = self.gate == "head"
        value_dim = self.value_head_dim or self.head_dim
        if not by_head and (all_value_heads != self.heads
                            or value_dim != self.head_dim
                            or self.beta_scale != 1.0):
            raise ValueError("value_heads beside heads, value_head_dim beside "
                             "head_dim and beta_scale want gate='head': the "
                             "channel gate's decay is a key channel's")
        if self.heads % n_shards or not 0 <= shard < n_shards \
                or all_value_heads % self.heads:
            raise ValueError(f"head_shard {self.head_shard} does not divide "
                             f"{self.heads} heads, or they do not divide "
                             f"{all_value_heads} value heads")
        heads, value_heads = (self.heads // n_shards,
                              all_value_heads // n_shards)
        prefix, out_gate = GATES[self.gate]
        batch, seq, d = u.shape
        key_inner, inner = heads * self.head_dim, value_heads * value_dim
        mixed = 2 * key_inner + inner                 # q, k, v: what conv sees
        of_values = (batch, seq, value_heads, value_dim)
        # The decay's pre-activation: a channel's or a value head's.
        decays = value_heads if by_head else inner
        w_in = self.param("in_proj_kernel", nn.initializers.lecun_normal(),
                          (d, mixed + inner + decays + value_heads),
                          jnp.float32)
        w_conv = self.param(
            "conv_kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=1),
            (self.conv, mixed), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (decays,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (value_heads,), jnp.float32)
        scale = self.param("norm_scale", nn.initializers.ones,
                           (value_dim,), jnp.float32)
        w_out = self.param("out_proj_kernel", nn.initializers.lecun_normal(),
                           (inner, d), jnp.float32)

        def scoped(stage):
            return jax.named_scope(f"hvd_{prefix}_{stage}")

        with scoped("in_proj"):
            projected = jnp.dot(u.astype(self.dtype), w_in.astype(self.dtype))
            if by_head:                               # [q, k, v | z | b | a]
                qkv, z, b, a = jnp.split(
                    projected, [mixed, mixed + inner,
                                mixed + inner + value_heads], axis=-1)
            else:                                     # [q, k, v | a | z | b]
                qkv, a, z, b = jnp.split(
                    projected, [mixed, mixed + inner, mixed + 2 * inner],
                    axis=-1)
        with scoped("conv"):
            if by_head:
                q, k, v = mixer_opening(
                    qkv, w_conv, None,
                    ((heads, self.head_dim, self.head_dim ** -0.5),
                     (heads, self.head_dim, 1.0),
                     (value_heads, value_dim, None)))
            else:
                # The composition mixer_opening replaces, float32 until the
                # casts below: the channel form's rule cuts q, k and v into
                # sub-blocks, and handed one stored array each it compiles
                # to 12.8 MB more code a mixer, which Ling's step and its
                # other programs no longer fit the machine's compile cache
                # with (PERF.md section 7, "From PR 59").
                q, k, v = jnp.split(
                    nn.silu(causal_depthwise_conv(qkv, w_conv)),
                    [key_inner, 2 * key_inner], axis=-1)
                q, k, v = (t.reshape(batch, seq, -1, self.head_dim)
                           for t in (q, k, v))

                def unit(t):
                    return t * lax.rsqrt(
                        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)

                q, k = unit(q) * self.head_dim ** -0.5, unit(k)
        with scoped("gate"):
            pre = a.astype(jnp.float32) + dt_bias
            if by_head:
                log_alpha = -jnp.exp(a_log) * nn.softplus(pre)
            else:
                log_alpha = self.lower_bound * nn.sigmoid(
                    jnp.exp(a_log)[:, None] * pre.reshape(of_values))
            beta = nn.sigmoid(b.astype(jnp.float32))
            if self.beta_scale != 1.0:
                beta = self.beta_scale * beta
                self.sow("intermediates", f"{prefix}_beta_over_one",
                         jnp.sum(beta > 1.0))
                self.sow("intermediates", f"{prefix}_beta_steps",
                         jnp.int32(beta.size))
        with scoped("scan"):
            # The channel gate's casts are the first of the rule's products
            # inside a chunk (the head gate's q, k, v arrive rounded): every
            # operation under this scope lies in one of its stages.
            with scoped("scan_chunk"):
                q, k, v = (t.astype(self.dtype) for t in (q, k, v))
            o, decay_min = chunked_delta_rule(
                q, k, v, log_alpha, beta, min(self.chunk, seq),
                scope=f"hvd_{prefix}_scan")
            self.sow("intermediates", f"{prefix}_chunk_log_decay_min",
                     decay_min)
        with scoped("gate_norm"):
            mean_sq = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            gated = o * lax.rsqrt(mean_sq + self.norm_eps) * scale \
                * out_gate(z.astype(jnp.float32).reshape(of_values))
        with scoped("out_proj"):
            return jnp.dot(gated.reshape(batch, seq, inner).astype(self.dtype),
                           w_out.astype(self.dtype))
