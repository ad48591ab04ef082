"""The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as the published hybrids'
layers run it (Nemotron-H's and Granite-4.0-H's alike: the equations below,
at their own sizes), told which heads it holds.

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
A layer of ONE group (``Mamba2Config(groups=1)``: every head reads the same
``B_t``, ``C_t``, and the gated norm is over all HP channels) has no such
share: ``groups % n`` refuses it, because B and C would have to be held by
every chip and the norm's statistic exchanged.

What a layer costs a token, at the two points the benchmark runs
(``ops/ssm.py`` has the scan's): 16 heads of 64 on one group of 128 states at
d_model 4,096 (an eighth of Nemotron-3-Super's layer) 13.7 M multiply-adds in
the two projections, 1,280 channels through the convolution and 1,024 through
the gated norm; 64 heads of 64 on one group of 128 at d_model 2,048
(Granite-4.0-H-Micro's whole layer) 25.8 M, 4,352 and 4,096.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.ops.moe import reduced_to_vma_of
from horovod_tpu.ops.ssm import chunked_scan, held_for_the_scan


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
# DT_RANGE and at least DT_FLOOR (the published layers' time_step_min, _max,
# _floor).
DT_RANGE = (0.001, 0.1)
DT_FLOOR = 1e-4


# A chunk and head CARRIES where more than this much of the state that entered
# the chunk leaves it (``exp`` of the chunk's summed ``dt A``).
CARRY_LIVE = 1e-3


def _dt_bias_init(key, shape, dtype=jnp.float32):
    low, high = (jnp.log(t) for t in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (high - low) + low)
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))              # softplus's inverse


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def causal_depthwise_conv(x, taps, bias=None):
    """The short convolution both recurrent mixers open with, as
    :func:`mixer_opening`'s forward and its tests' reference run it: ``x``
    (batch, seq, channels), ``taps`` (conv, channels), each channel its own
    filter over the token and the ``conv - 1`` before it.  Tap k multiplies
    the token ``conv - 1 - k`` places back; the last tap the token itself.
    Products and sum in float32 (``taps`` and ``bias`` are), and the result
    is float32: nothing here rounds, the caller does, once."""
    conv, seq = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (conv - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    mixed = sum(taps[k] * lax.dynamic_slice_in_dim(padded, k, seq, axis=1)
                for k in range(conv))
    return mixed if bias is None else bias + mixed


L2_EPS = 1e-6     # under the root of a unit-normed head's sum of squares


def _by_part(parts, *arrays):
    """Each part's channels of every array (channels last; None stays) as
    its heads: (..., heads, width)."""
    edges = np.cumsum([0] + [heads * width for heads, width, _ in parts])
    return [tuple(None if t is None else
                  t[..., lo:hi].reshape(t.shape[:-1] + (heads, width))
                  for t in arrays)
            for (heads, width, _), lo, hi in zip(parts, edges[:-1],
                                                 edges[1:])]


@functools.partial(jax.jit, static_argnums=(3,))
def _opening_forward(x, taps, bias, parts):
    """(:func:`mixer_opening`'s outputs, the normed parts' ``rsqrt``s):
    float32 throughout, each output rounded once at its end.  A normed part
    is two passes over its channels of ``x``, the sums of squares and then
    the outputs, with the activation computed in each: held apart, or XLA
    writes it out in float32 between them.  Jitted, as its backward is, so
    that a model's layers of one shape trace and lower each once
    (``ops.moe._tiled_call``)."""
    outputs, roots = [], []
    for (_, _, unit), (x_p, taps_p, bias_p) in zip(
            parts, _by_part(parts, x, taps, bias)):
        active = nn.silu(causal_depthwise_conv(x_p, taps_p, bias_p))
        if unit is not None:
            root = lax.rsqrt(jnp.sum(active * active, axis=-1, keepdims=True)
                             + L2_EPS)
            x_p, root = lax.optimization_barrier((x_p, root))
            active = nn.silu(causal_depthwise_conv(x_p, taps_p, bias_p))
            active = active * root * unit
            roots.append(root)
        outputs.append(active.astype(x.dtype))
    return tuple(outputs), tuple(roots)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def mixer_opening(x, taps, bias, parts):
    """What a recurrent mixer does to its projected input before its rule:
    the short causal depthwise convolution (:func:`causal_depthwise_conv`,
    ``bias`` or None), SiLU, a split into ``parts`` and the unit norm of the
    parts that have one.  ``x`` (batch, seq, channels) in the layer's dtype,
    ``taps`` (conv, channels) and ``bias`` (channels,) float32; ``parts`` is
    static, a ``(heads, width, unit)`` a part, ``heads * width`` channels
    each in order: ``unit`` None leaves the part as SiLU gave it (Mamba-2's
    x, B, C; a delta mixer's v), a number makes every head of ``width``
    channels ``t * rsqrt(sum(t * t) + L2_EPS) * unit`` (a delta mixer's k
    with 1.0, its q with ``head_dim ** -0.5``).  Returns a (batch, seq, heads,
    width) array a part, in ``x``'s dtype.

    Every product and sum is float32 and each output is rounded ONCE, as it
    leaves.  Plain autodiff keeps nine float32 arrays of the activation's
    size a layer for this (the four shifted slices, their sum, two out of
    SiLU, the reshaped q and k) and hands ``x`` a cotangent of four
    separately rounded arrays; written out, the backward keeps ``x`` AS
    STORED, the parameters and the norms' ``rsqrt`` (a float32 a token and
    head), computes the rest again in float32 and rounds ``dx`` once."""
    return _opening_forward(x, taps, bias, parts)[0]


def _mixer_opening_fwd(x, taps, bias, parts):
    outputs, roots = _opening_forward(x, taps, bias, parts)
    return outputs, (x, taps, bias, roots)


@functools.partial(jax.jit, static_argnums=(5,))
def _opening_backward(x, taps, bias, roots, cotangents, parts):
    """(``dx``, ``d_taps``, ``d_bias`` or None) from what the forward kept
    and the outputs' cotangents: float32 throughout, ``dx`` rounded once."""
    conv, seq = taps.shape[0], x.shape[1]
    # Computed again from x as stored, once the cotangents are there: the
    # forward's float32 activation is not kept for this.
    x, cotangents = lax.optimization_barrier((x, cotangents))
    roots, dx, d_taps, d_bias = iter(roots), [], [], []
    for (_, _, unit), g, (x_p, taps_p, bias_p) in zip(
            parts, cotangents, _by_part(parts, x, taps, bias)):
        if unit is not None:
            root = next(roots)
            pre = causal_depthwise_conv(x_p, taps_p, bias_p)
            along = jnp.sum(g.astype(jnp.float32)
                            * (pre * nn.sigmoid(pre) * root), axis=-1,
                            keepdims=True)
            x_p, g, along = lax.optimization_barrier((x_p, g, along))
        g = g.astype(jnp.float32)
        pre = causal_depthwise_conv(x_p, taps_p, bias_p)
        gate = nn.sigmoid(pre)
        if unit is not None:
            g = root * unit * (g - pre * gate * root * along)
        d_pre = g * (gate * (1.0 + pre * (1.0 - gate)))
        behind = ((0, 0),) * (x_p.ndim - 2)
        later = jnp.pad(d_pre, ((0, 0), (0, conv - 1)) + behind)
        dx.append(sum(
            taps_p[k] * lax.dynamic_slice_in_dim(later, conv - 1 - k, seq,
                                                 axis=1)
            for k in range(conv)).astype(x.dtype).reshape(
                x.shape[:2] + (-1,)))
        earlier = jnp.pad(x_p, ((0, 0), (conv - 1, 0)) + behind)
        d_taps.append(jnp.stack([
            jnp.sum(d_pre * lax.dynamic_slice_in_dim(earlier, k, seq, axis=1),
                    axis=(0, 1)) for k in range(conv)]).reshape(conv, -1))
        if bias is not None:
            d_bias.append(jnp.sum(d_pre, axis=(0, 1)).reshape(-1))
    return (jnp.concatenate(dx, axis=-1), jnp.concatenate(d_taps, axis=-1),
            jnp.concatenate(d_bias) if d_bias else None)


def _mixer_opening_bwd(parts, res, cotangents):
    x, taps, bias, roots = res
    dx, d_taps, d_bias = _opening_backward(x, taps, bias, roots, cotangents,
                                           parts)
    # Under shard_map the cotangents vary over the mapped axes and the
    # parameters do not (``transformer._qkv_project_bwd``).
    return (reduced_to_vma_of(x, dx), reduced_to_vma_of(taps, d_taps),
            None if bias is None else reduced_to_vma_of(bias, d_bias))


mixer_opening.defvjp(_mixer_opening_fwd, _mixer_opening_bwd)


class Mamba2Mixer(nn.Module):
    """One mixer's share (module docstring), each stage under a
    ``jax.named_scope`` a trace can read: ``hvd_ssm_in_proj``,
    ``hvd_ssm_conv`` (:func:`mixer_opening` with the bias and no norm:
    float32 inside, x, B and C leave it ROUNDED to ``dtype``, once, and its
    written-out backward keeps the projection's output as stored and nothing
    else of a token's), ``hvd_ssm_scan``, ``hvd_ssm_gate_norm``,
    ``hvd_ssm_out_proj``; the scan's four stages under ``hvd_ssm_scan_<stage>``
    beneath its own (``ops.ssm.STAGES``).  Writes to the ``intermediates``
    collection, where the caller makes it mutable, ``ssm_chunk_log_decay_min``
    (the most negative summed ``dt A`` of any chunk and head), and of all
    (sequence, chunk, head) triples ``ssm_chunks`` how many pass on more than
    :data:`CARRY_LIVE` of the state that entered, ``ssm_chunks_carried``
    (:func:`~horovod_tpu.models.record_ssm_carry` reads them a layer)."""

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
            zxbcdt = held_for_the_scan(
                jnp.dot(u.astype(self.dtype), w_in.astype(self.dtype)),
                heads, groups, self.head_dim, self.state,
                min(self.chunk, seq))
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc],
                                   axis=-1)
        with jax.named_scope("hvd_ssm_conv"):
            # Nothing is normed: a part is one head as wide as it comes,
            # and the scan below cuts it into its heads and groups.
            x, B, C = mixer_opening(
                xbc, w_conv, b_conv,
                ((1, inner, None), (1, bc, None), (1, bc, None)))
        with jax.named_scope("hvd_ssm_scan"):
            y, whole = chunked_scan(
                x.reshape(batch, seq, heads, self.head_dim),
                nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                B.reshape(batch, seq, groups, self.state),
                C.reshape(batch, seq, groups, self.state), skip,
                min(self.chunk, seq))
            self.sow("intermediates", "ssm_chunk_log_decay_min", whole.min())
            self.sow("intermediates", "ssm_chunks_carried",
                     jnp.sum(jnp.exp(whole) > CARRY_LIVE))
            self.sow("intermediates", "ssm_chunks", jnp.int32(whole.size))
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
