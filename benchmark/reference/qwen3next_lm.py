"""Plain float32 reference of the decoder the repository runs for
Qwen3-Next-80B-A3B (`models.TransformerLM(layers=...)` with the kinds
`gated_delta`, `attention` and `experts`), in `jax.numpy` with no kernel, no
chunks, no solve, no sorting, no grouped matmul, no mixed precision and no
framework.  Every pattern entry is `x <- x + Mixer(N(x))` (a published layer is
two of them: a mixer, then the experts), `N` an RMSNorm with a learned scale,
eps 1e-6; then `N`, the untied head and the mean next-token cross-entropy.  The
mixers on the normed input `u`, with the experts the parameter tree holds:

`gated_delta`, Gated DeltaNet (arXiv:2412.06464): `[q | k | v | z | b | a] = u
    W_in` (key heads x d, key heads x d, value heads x d, value heads x d, value
    heads, value heads); `[q | k | v] <- silu(conv4(.))`, causal, depthwise, no
    bias; a head's `q <- q / sqrt(sum q^2 + 1e-6) d^-1/2`, `k <- k / sqrt(sum
    k^2 + 1e-6)`; q and k REPEATED for the value heads that read them (value
    head j reads key head `j // (value heads / key heads)`), as the published
    code repeats them; a value head's `g_t = -exp(A_log) softplus(a_t +
    dt_bias)`, `alpha_t = exp(g_t)`, `beta_t = sigmoid(b_t)`; `S_t = alpha_t
    S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T`, `o_t = S_t^T q_t` —
    ONE `lax.scan` STEP A TOKEN, the update written as it stands; `o / rms(o)
    * w_n * silu(z)` within a head, one learned scale of the head's width;
    `W_out`.
`attention`, gated grouped-query attention: `q_h, gate_h = u W_q, u W_g` (a
    head of e each); `k, v = u W_k, u W_v` (key/value heads of e); `N` over
    each head's e of q and of k, one e-wide scale each; rotary (adjacent
    pairs, base `rope_theta`) on the first `rotary_dim` channels of a head,
    the others untouched; causal softmax at e^-1/2 over whole rows of keys,
    query head j reading key/value head `j // (heads / key/value heads)`;
    `o * sigmoid(gate)` elementwise; `W_o`.
`experts`: `p = softmax(u W_r)` over ALL experts; the k largest
    (`lax.top_k`); their weights divided by their sum; `sum_chosen p_e W2_e
    (silu(W1_e u) * W3_e u)`, a loop over THIS SHARD's experts, each applied to
    every token and masked by its weight; plus `sigmoid(u w_sg) * W2_s
    (silu(W1_s u) * W3_s u)`, the shared expert under its output gate, one
    scalar a token.

The shares (`expert_shard`, a sliced vocabulary) are what the system computes:
the experts the parameter tree holds, the router over all experts.  With the
whole layer's parameters it is the whole layer (tests/test_qwen3next.py adds
the shares up to it).

Departures from the published model, each of which the system shares, are
listed under `departures` in benchmark/configs/qwen3next80b.json (no
multi-token-prediction module, norms stored as a scale from ones, rotary on
adjacent pairs, weight layouts); the readings the config does not settle under
`assumed` there.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a
pattern entry, an expert, a block of 64 tokens of the recurrence, a block of
query rows and a block of the head only bounds what autodiff keeps (this runs
beside 10 GB of training state on a 16 GB chip; the recurrence's states alone
would be 8.6 GB a layer); it changes no arithmetic.  `operand_dtype` rounds
every matmul operand, and the q, k, v that the recurrence and the attention
read, to a narrower type and back: how the tolerances below were shown to
refuse the next precision under bfloat16 (float8_e4m3fn).

Tolerances (used by benchmark/builders/qwen3next_lm.py), each with its reason:
the system computes in bfloat16 with float32 accumulation, stores the logits in
bfloat16, and ROUTES on bfloat16 activations, 10 of 512 experts a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ling_lm import (delta_recurrence, gated_mlp, rotary,
                                         _round)
from benchmark.reference.moe_lm import (_f32, _mm, cross_entropy,
                                        experts_of_shard, rms_norm)
from benchmark.reference.trinity_lm import band_attention

# Each limit from two readings on the chip at the published widths and the
# timed sizes (PERF.md section 6, PR 46): the largest the system gave over the
# seeds named there (thirteen: 4600000001 to ...003, ...005, ...006, ...008,
# ...009, ...031 to ...034, 2147483999), and what THIS reference gives against itself with every matmul
# operand, forward and backward, and the q, k, v of the recurrence and of the
# attention rounded to float8_e4m3fn — the nearest precision under the
# configuration's bfloat16 (seeds 4600000021, ...022) — which has to be
# refused.
#
# |loss_system - loss_reference| / loss_reference: a mean over 4,096 tokens of
# a log-softmax over 18,992 rounded logits; the roundings average out.  System
# 6.3e-6 to 3.3e-5; the limit of the harness's accepted expert cells (Ling's,
# Trinity's), seven times the largest reading: float8 reads 1.8e-4 and
# 7.7e-5, the loss does not tell it from bfloat16, the three rows below do.
LOSS_RTOL = 2.5e-4
# ||g_system - g_reference|| / ||g_reference|| over all parameters.  System
# 0.0197 to 0.0214 (Trinity's 0.014 to 0.015, Nemotron's 0.034 to 0.042);
# float8 0.781 and 0.778.
GRAD_RTOL = 1e-1
# | ||g_system|| / ||g_reference|| - 1 |: only the error's component along
# the gradient.  System 5.2e-7 to 5.9e-5; float8 0.372 and 0.368 (small
# cotangents underflow its 4-bit exponent).
GRAD_NORM_RTOL = 5e-3
# Share of (token, choice) pairs, over the four expert layers, whose expert the
# float32 reference did not choose for that token: near-ties between the 10th
# and 11th of 512 probabilities that a bfloat16 rounding of the activations
# flips (Nemotron's plain top-22 of 512 reads 0.011 to 0.013).  System 0.0097
# to 0.0110; float8 0.0615 and 0.0616.
ROUTING_MISMATCH_MAX = 2.5e-2

L2_EPS = 1e-6            # under the root of q's and k's norms (`assumed`)


def gated_delta(u, p, *, head_dim, norm_eps, operand_dtype=None):
    """The Gated DeltaNet mixer on the normed input `u` (batch, seq, hidden),
    with the heads that `p` holds: `A_log` counts the value heads, the
    convolution's channels the key heads."""
    batch, seq, _ = u.shape
    value_heads = p["A_log"].shape[0]
    inner = value_heads * head_dim
    key_inner = (p["conv_kernel"].shape[1] - inner) // 2
    key_heads = key_inner // head_dim
    mixed = 2 * key_inner + inner
    qkv, z, b, a = jnp.split(
        _mm(u, _f32(p["in_proj_kernel"]), operand_dtype),
        [mixed, mixed + inner, mixed + inner + value_heads], axis=-1)
    taps = _f32(p["conv_kernel"])
    padded = jnp.pad(qkv, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(taps[i] * padded[:, i:i + seq]
                          for i in range(taps.shape[0])))
    q, k, v = jnp.split(qkv, [key_inner, 2 * key_inner], axis=-1)
    q, k = (t.reshape(batch, seq, key_heads, head_dim) for t in (q, k))
    v = v.reshape(batch, seq, value_heads, head_dim)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) \
        * head_dim ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    q, k = (jnp.repeat(t, value_heads // key_heads, axis=2) for t in (q, k))
    log_alpha = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        a + _f32(p["dt_bias"]))                       # (batch, seq, heads)
    o = delta_recurrence(
        *(_round(t, operand_dtype) for t in (q, k, v)),
        jnp.broadcast_to(log_alpha[..., None], q.shape), jax.nn.sigmoid(b))
    normed = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                               + norm_eps) * _f32(p["norm_scale"])
    gated = normed * jax.nn.silu(z.reshape(v.shape))
    return _mm(gated.reshape(batch, seq, inner), _f32(p["out_proj_kernel"]),
               operand_dtype)


def partly_rotated(x, theta, rotary_dim):
    """(..., seq, width): the first `rotary_dim` channels turn as a head of
    that width would, the others pass."""
    return jnp.concatenate([rotary(x[..., :rotary_dim], theta),
                            x[..., rotary_dim:]], axis=-1)


def gated_attention(u, p, *, rope_theta, rotary_dim, norm_eps,
                    operand_dtype=None):
    """Gated grouped-query attention with per-head q/k norms and a partial
    rotation on the normed input `u` (batch, seq, hidden).  `p` holds
    `q_kernel` (hidden, heads, e), `kv_kernel` (hidden, 2, key/value heads,
    e), `q_head_norm_scale` and `k_head_norm_scale` (e,), `gate_kernel`
    (hidden, heads, e) and `o_kernel` (heads, e, hidden)."""
    batch, seq, hidden = u.shape
    w_q, w_kv, w_g, w_o = (_f32(p[n]) for n in (
        "q_kernel", "kv_kernel", "gate_kernel", "o_kernel"))
    width = w_q.shape[2]

    def per_head(w):
        return _mm(u, w.reshape(hidden, -1), operand_dtype).reshape(
            batch, seq, -1, width).transpose(0, 2, 1, 3)

    q = rms_norm(per_head(w_q), _f32(p["q_head_norm_scale"]), norm_eps)
    k = rms_norm(per_head(w_kv[:, 0]), _f32(p["k_head_norm_scale"]), norm_eps)
    v = per_head(w_kv[:, 1])
    q, k = (partly_rotated(t, rope_theta, rotary_dim) for t in (q, k))
    out = band_attention(*(_round(t, operand_dtype) for t in (q, k, v)))
    gate = jax.nn.sigmoid(per_head(w_g))
    return _mm((out * gate).transpose(0, 2, 1, 3).reshape(batch, seq, -1),
               w_o.reshape(-1, hidden), operand_dtype)


def router(u, w_router, *, experts_per_token, operand_dtype=None):
    """(weights (tokens, k), experts (tokens, k)): the k largest of a softmax
    over all experts, their weights over their sum."""
    probs = jax.nn.softmax(_mm(u, w_router, operand_dtype), axis=-1)
    chosen, experts = jax.lax.top_k(probs, experts_per_token)
    return chosen / (chosen.sum(-1, keepdims=True) + 1e-20), experts


def sparse_experts(u, p, *, num_experts, expert_shard, experts_per_token,
                   operand_dtype=None):
    """(the layer's output for `u` (tokens, hidden), chosen experts)."""
    weights, experts = router(u, _f32(p["router_kernel"]),
                              experts_per_token=experts_per_token,
                              operand_dtype=operand_dtype)
    first = expert_shard[0] * (num_experts // expert_shard[1])
    routed = experts_of_shard(u, p, weights, experts, first, operand_dtype)
    shared = gated_mlp(u, *(p[n]["kernel"] for n in (
        "shared_gate", "shared_up", "shared_down")), operand_dtype)
    open_ = jax.nn.sigmoid(_mm(u, _f32(p["shared_output_gate_kernel"]),
                               operand_dtype))
    return routed + open_ * shared, experts


def layer(x, p, kind, *, head_dim, rope_theta, rotary_dim, norm_eps,
          num_experts, experts_per_token, expert_shard, operand_dtype=None):
    """(x out, chosen experts or None) of one pattern entry of `kind`;
    `head_dim` is the Gated DeltaNet heads' (the attention's is its
    kernels')."""
    u = rms_norm(x, _f32(p["norm"]["scale"]), norm_eps)
    m, experts = p["mixer"], None
    if kind == "gated_delta":
        out = gated_delta(u, m, head_dim=head_dim, norm_eps=norm_eps,
                          operand_dtype=operand_dtype)
    elif kind == "attention":
        out = gated_attention(u, m, rope_theta=rope_theta,
                              rotary_dim=rotary_dim, norm_eps=norm_eps,
                              operand_dtype=operand_dtype)
    else:
        out, experts = sparse_experts(
            u.reshape(-1, u.shape[-1]), m, num_experts=num_experts,
            expert_shard=expert_shard, experts_per_token=experts_per_token,
            operand_dtype=operand_dtype)
        out = out.reshape(x.shape)
    return x + out, experts


def forward(params, tokens, *, layers, **config):
    """(final hidden states, [chosen experts of each expert layer]);
    `layers`: the kinds in order; `config`: `layer`'s other keywords."""
    x = _f32(params["embed"]["embedding"])[tokens]
    chosen = []
    for index, kind in enumerate(layers):
        x, experts = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, kind, **config))(
                x, params[f"layer_{index}"])
        if experts is not None:
            chosen.append(experts)
    return rms_norm(x, _f32(params["final_norm"]["scale"]),
                    config["norm_eps"]), chosen


def loss_and_chosen(params, batch, **config):
    """(mean next-token cross-entropy, the experts routed to: (expert layers,
    tokens, k)) from ONE forward pass; `batch` is (inputs, targets)."""
    inputs, targets = batch
    hidden, chosen = forward(params, inputs, **config)
    return cross_entropy(hidden, _f32(params["lm_head_kernel"]), targets,
                         config.get("operand_dtype")), jnp.stack(chosen)


def loss(params, batch, **config):
    return loss_and_chosen(params, batch, **config)[0]
