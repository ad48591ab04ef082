"""The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as Nemotron-H's layers run
it, told which heads it holds.

With ``u`` the layer's normed input, H heads of P channels, G groups of N
states (``[z, xBC, dt] = u W_in``: ``z`` of width HP, ``xBC`` of HP + 2GN,
``dt`` of H):

    xBC = silu(conv(xBC))          causal, depthwise, ``conv`` taps, a bias
    x, B, C = split(xBC)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)            a head, float32
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,  y_t = h_t C_t + D x_t
    y = RMSNorm_grouped(y * silu(z))   within each group of HP/G channels
    out = y W_out

``head_shard=(i, n)``: this process holds heads ``[i H/n, (i+1) H/n)`` and
groups ``[i G/n, (i+1) G/n)`` — their columns of ``W_in``, their channels of
the convolution and of the norm, their rows of ``W_out`` — the local part of a
layer that is tensor-parallel over ``n`` chips (what ``n_groups`` is for: the
norm never crosses a group, so nothing but the sum of the ``n`` outputs is
exchanged, and that sum is the caller's).  ``(0, 1)`` is the whole layer.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.ssm import chunked_scan


class Mamba2Config(NamedTuple):
    """Sizes of the whole Mamba-2 mixer of a layer (``TransformerLM(ssm=)``):
    ``heads`` of ``head_dim`` channels, ``groups`` of ``state`` states,
    ``conv`` taps, the scan's ``chunk``."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int = 4
    chunk: int = 128


# Mamba-2's seeding of the step size: softplus(dt_bias) log-uniform over
# DT_RANGE and at least DT_FLOOR (Nemotron-H's time_step_min, _max, _floor).
DT_RANGE = (0.001, 0.1)
DT_FLOOR = 1e-4


def _dt_bias_init(key, shape, dtype=jnp.float32):
    low, high = (jnp.log(t) for t in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (high - low) + low)
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))              # softplus's inverse


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def causal_depthwise_conv(x, taps, bias=None):
    """The short convolution both recurrent mixers open with (this one and
    :class:`~horovod_tpu.models.delta.DeltaMixer`): ``x`` (batch, seq,
    channels), ``taps`` (conv, channels), each channel its own filter over the
    token and the ``conv - 1`` before it.  Tap k multiplies the token
    ``conv - 1 - k`` places back; the last tap the token itself.  Products
    and sum in float32 (``taps`` and ``bias`` are)."""
    conv, seq = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (conv - 1, 0), (0, 0)))
    mixed = sum(taps[k] * lax.dynamic_slice_in_dim(padded, k, seq, axis=1)
                for k in range(conv))
    return mixed if bias is None else bias + mixed


class Mamba2Mixer(nn.Module):
    """One mixer's share (module docstring), each stage under a
    ``jax.named_scope`` a trace can read: ``hvd_ssm_in_proj``,
    ``hvd_ssm_conv``, ``hvd_ssm_scan``, ``hvd_ssm_gate_norm``,
    ``hvd_ssm_out_proj``.  Writes ``ssm_chunk_log_decay_min`` to the
    ``intermediates`` collection where the caller makes it mutable."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int = 4
    chunk: int = 128
    head_shard: Tuple[int, int] = (0, 1)
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, u):
        shard, n_shards = self.head_shard
        if self.heads % n_shards or self.groups % n_shards \
                or not 0 <= shard < n_shards:
            raise ValueError(f"head_shard {self.head_shard} does not divide "
                             f"{self.heads} heads in {self.groups} groups")
        heads, groups = self.heads // n_shards, self.groups // n_shards
        batch, seq, d = u.shape
        inner, bc = heads * self.head_dim, groups * self.state
        w_in = self.param("in_proj_kernel", nn.initializers.lecun_normal(),
                          (d, 2 * inner + 2 * bc + heads), jnp.float32)
        w_conv = self.param(
            "conv_kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=1),
            (self.conv, inner + 2 * bc), jnp.float32)
        b_conv = self.param("conv_bias", nn.initializers.zeros,
                            (inner + 2 * bc,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                           jnp.float32)
        w_out = self.param("out_proj_kernel", nn.initializers.lecun_normal(),
                           (inner, d), jnp.float32)

        with jax.named_scope("hvd_ssm_in_proj"):
            zxbcdt = jnp.dot(u.astype(self.dtype), w_in.astype(self.dtype))
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc],
                                   axis=-1)
        with jax.named_scope("hvd_ssm_conv"):
            xbc = nn.silu(causal_depthwise_conv(xbc, w_conv, b_conv)).astype(
                self.dtype)
            x, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
        with jax.named_scope("hvd_ssm_scan"):
            y, decay_min = chunked_scan(
                x.reshape(batch, seq, heads, self.head_dim),
                nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                B.reshape(batch, seq, groups, self.state),
                C.reshape(batch, seq, groups, self.state), skip,
                min(self.chunk, seq))
            self.sow("intermediates", "ssm_chunk_log_decay_min", decay_min)
        with jax.named_scope("hvd_ssm_gate_norm"):
            gated = (y.reshape(batch, seq, groups, -1)
                     * nn.silu(z.astype(jnp.float32)).reshape(
                         batch, seq, groups, -1))
            mean_sq = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
            normed = (gated * lax.rsqrt(mean_sq + self.norm_eps)).reshape(
                batch, seq, inner) * scale
        with jax.named_scope("hvd_ssm_out_proj"):
            return jnp.dot(normed.astype(self.dtype),
                           w_out.astype(self.dtype))
