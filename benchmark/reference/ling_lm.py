"""Plain float32 reference of the decoder the repository runs for
Ling-3.0-flash's language model (`models.TransformerLM(layers=...)` with the
kinds `delta`, `latent_attention`, `gated_mlp` and `experts`), in `jax.numpy`
with no kernel, no chunks, no solve, no sorting, no grouped matmul, no mixed
precision and no framework.  Every pattern entry is `x <- x +
Mixer(RMSNorm(x))` (a published layer is two of them: a mixer, then an MLP or
the experts), every norm an RMSNorm with a learned scale; then RMSNorm, the
untied head and the mean next-token cross-entropy.  The mixers on the normed
input `u`, with the heads and experts the parameter tree holds:

`delta`, Kimi delta attention (arXiv:2510.26692): `[q, k, v, a, z, b] = u
    W_in`; `q, k, v = silu(conv4(.))`, causal, depthwise, no bias; a head's
    `q <- q / ||q|| d_k^-1/2`, `k <- k / ||k||`; a channel's log-decay
    `lower_bound * sigmoid(exp(A_log) (a + dt_bias))`, a head's `beta =
    sigmoid(b)`; `S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t
    k_t v_t^T`, `o_t = S_t^T q_t` — ONE `lax.scan` STEP A TOKEN, the update
    written as it stands; `RMSNorm_head(o) * sigmoid(z)`, one learned scale of
    the head's width; `W_out`.
`latent_attention` (arXiv:2405.04434): `[q_nope | q_rope]_h = u W_q`;
    `[c | k_rope] = u W_kva`, `c <- RMSNorm(c)`; `[k_nope | v]_h = c W_kvb`;
    rotary (adjacent pairs, base `rope_theta`) on `q_rope` and on the ONE
    `k_rope` every head shares; causal softmax at (nope + rope)^-1/2 over whole
    rows of keys; `o_h * sigmoid(u W_g)_h`; `W_o`.
`gated_mlp`: `W_down (silu(W_gate u) * W_up u)`.
`experts` (arXiv:2412.19437's router): `s = sigmoid(u W_r)`; `s' = s + bias`;
    the experts in `n_group` consecutive groups, a group's score the sum of
    its 2 largest `s'`, the `topk_group` best groups stay; the k largest `s'`
    among their experts are chosen (`lax.top_k`); `g_e = scale * s_e /
    sum_chosen s`; `sum_chosen g_e W2_e (silu(W1_e u) * W3_e u)`, a loop over
    THIS SHARD's experts, each applied to every token and masked by its
    weight; plus the shared expert of the same gated form, unweighted.

The shares (`tensor_shard`, `expert_shard`, a sliced vocabulary) are what the
system computes: the heads and experts the parameter tree holds, the router
over all experts.  With the whole layer's parameters it is the whole layer
(tests/test_ling.py adds the shares up to it).

Departures from the published model, each of which the system shares: text
tokens only (no vision tower), no multi-token-prediction module, the selection
bias is what the caller passes; the readings the config does not settle are
listed under `assumed` in benchmark/configs/ling3flash.json.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a
pattern entry, an expert, a block of 64 tokens of the recurrence, a block of
query rows and a block of the head only bounds what autodiff keeps (this runs
beside 8.4 GB of training state on a 16 GB chip; the recurrence's states alone
would be 2 GB a layer); it changes no arithmetic.  `operand_dtype` rounds
every matmul operand, and the q, k, v the recurrence reads, to a narrower type
and back: how the tolerances below were shown to refuse the next precision
under bfloat16 (float8_e4m3fn).

Tolerances (used by benchmark/builders/ling_lm.py), each with its reason: the
system computes in bfloat16 with float32 accumulation, stores the logits in
bfloat16, and ROUTES on bfloat16 activations, 8 of 512 experts a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.moe_lm import (_f32, _mm, attention, cross_entropy,
                                        experts_of_shard, rms_norm)

# Each limit from two readings on the chip at the published widths and the
# timed sizes (PERF.md section 6, PR 32): the largest the system gave over the
# seeds named there (fifteen: 3200000001 to ...007, 2147483999, 3200000101 to
# ...106, 2147484100), and what THIS reference gives against itself with every matmul
# operand, forward and backward, and the q, k, v of the recurrence rounded to
# float8_e4m3fn — the nearest precision under the configuration's bfloat16
# (seeds 3200000021, ...022) — which has to be refused.
#
# |loss_system - loss_reference| / loss_reference: a mean over 8,192 tokens of
# a log-softmax over 19,648 rounded logits; the roundings average out.  System
# 5.1e-6 to 7.1e-5; three and a half times that, the rule for a loss the
# precision hardly moves: float8 reads 1.5e-5 and 3.0e-4, the loss does not
# tell it from bfloat16, the three rows below do.
LOSS_RTOL = 2.5e-4
# ||g_system - g_reference|| / ||g_reference|| over all parameters.  System
# 0.0543 to 0.0676 (Nemotron's 0.034 to 0.042, OLMoE's 0.010 to 0.012); float8
# 0.936 and 0.939.  Why over Nemotron's: 2.6 to 2.9 % of the (token, choice)
# pairs route elsewhere than in float32 (below), each swapping one whole
# expert term of weight ~2.5/8, through six expert layers, and every layer's
# cotangent inherits it.
GRAD_RTOL = 2e-1
# | ||g_system|| / ||g_reference|| - 1 |: only the error's component along
# the gradient.  System 2.0e-5 to 4.0e-4; float8 0.637 and 0.646 (small
# cotangents underflow its 4-bit exponent).
GRAD_NORM_RTOL = 5e-3
# Share of (token, choice) pairs, over the six expert layers, whose expert the
# float32 reference did not choose for that token: near-ties that a bfloat16
# rounding of the activations flips — between the 8th and 9th of the 256
# scores of the kept groups, and between the 4th and 5th of 8 groups, which
# moves up to all 8 of a token's choices at once (Nemotron's plain top-22 of
# 512 reads 0.011 to 0.013).  System 0.0264 to 0.0294; float8 0.180 and 0.181.
ROUTING_MISMATCH_MAX = 7e-2

RECURRENCE_BLOCK = 64    # tokens between the states autodiff keeps
L2_EPS = 1e-6            # under the root of q's and k's norms (`assumed`)


def _round(a, operand_dtype):
    """What `_mm` does to an operand, for the q, k, v the recurrence reads."""
    return a if operand_dtype is None else _f32(a.astype(operand_dtype))


def rotary(x, theta):
    """(..., seq, width): pairs (x[2i], x[2i+1]) turn by
    position * theta^(-i / (width / 2))."""
    seq, half = x.shape[-2], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def delta_recurrence(q, k, v, log_alpha, beta):
    """`o_t = S_t^T q_t` with `S_t = (I - beta_t k_t k_t^T) Diag(alpha_t)
    S_{t-1} + beta_t k_t v_t^T`, one step a token from `S_0 = 0`.  `q`, `k`,
    `log_alpha` (batch, seq, heads, d_k), `v` (batch, seq, heads, d_v), `beta`
    (batch, seq, heads)."""
    batch, seq, heads, d_k = q.shape

    def token(state, inputs):
        q_t, k_t, v_t, log_alpha_t, beta_t = inputs       # (batch, heads, ...)
        decayed = jnp.exp(log_alpha_t)[..., None] * state
        seen = jnp.einsum("bhc,bhcv->bhv", k_t, decayed)  # k^T Diag(alpha) S
        state = decayed + beta_t[..., None, None] * k_t[..., :, None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhcv,bhc->bhv", state, q_t)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    span = RECURRENCE_BLOCK if seq % RECURRENCE_BLOCK == 0 else seq
    by_time = [t.swapaxes(0, 1).reshape(seq // span, span, *t.shape[:1],
                                        *t.shape[2:])
               for t in (q, k, v, log_alpha, beta)]
    start = jnp.zeros((batch, heads, d_k, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, start, tuple(by_time))
    return o.reshape(seq, batch, heads, -1).swapaxes(0, 1)


def kda(u, p, *, head_dim, lower_bound, norm_eps, operand_dtype=None):
    """The Kimi-delta mixer on the normed input `u` (batch, seq, hidden),
    with the heads that `p` holds."""
    batch, seq, _ = u.shape
    heads = p["A_log"].shape[0]
    inner = heads * head_dim
    by_head = (batch, seq, heads, head_dim)
    qkv, a, z, b = jnp.split(
        _mm(u, _f32(p["in_proj_kernel"]), operand_dtype),
        [3 * inner, 4 * inner, 5 * inner], axis=-1)
    taps = _f32(p["conv_kernel"])
    padded = jnp.pad(qkv, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(taps[i] * padded[:, i:i + seq]
                          for i in range(taps.shape[0])))
    q, k, v = (t.reshape(by_head) for t in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) \
        * head_dim ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    log_alpha = lower_bound * jax.nn.sigmoid(
        jnp.exp(_f32(p["A_log"]))[:, None]
        * (a + _f32(p["dt_bias"])).reshape(by_head))
    o = delta_recurrence(*(_round(t, operand_dtype) for t in (q, k, v)),
                         log_alpha, jax.nn.sigmoid(b))
    normed = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                               + norm_eps) * _f32(p["norm_scale"])
    gated = normed * jax.nn.sigmoid(z.reshape(by_head))
    return _mm(gated.reshape(batch, seq, inner), _f32(p["out_proj_kernel"]),
               operand_dtype)


def latent_attention(u, p, *, nope_dim, rope_theta, norm_eps,
                     operand_dtype=None):
    """Latent attention with a head-wise output gate; `p` holds `q_kernel`
    (hidden, heads, nope + rope), `kv_a_kernel` (hidden, rank + rope),
    `kv_norm_scale` (rank,), `kv_b_kernel` (rank, heads, nope + value),
    `gate_kernel` (hidden, heads) and `o_kernel` (heads, value, hidden)."""
    batch, seq, hidden = u.shape
    w_q, w_kvb, w_o = (_f32(p[n]) for n in ("q_kernel", "kv_b_kernel",
                                            "o_kernel"))
    heads, rank = w_q.shape[1], w_kvb.shape[0]

    def per_head(x, w):
        return _mm(x, w.reshape(w.shape[0], -1), operand_dtype).reshape(
            batch, seq, heads, -1).transpose(0, 2, 1, 3)

    q = per_head(u, w_q)
    latent, k_rope = jnp.split(_mm(u, _f32(p["kv_a_kernel"]), operand_dtype),
                               [rank], axis=-1)
    kv = per_head(rms_norm(latent, _f32(p["kv_norm_scale"]), norm_eps), w_kvb)
    k_rope = jnp.broadcast_to(rotary(k_rope, rope_theta)[:, None],
                              (batch, heads, seq, k_rope.shape[-1]))
    q = jnp.concatenate([q[..., :nope_dim],
                         rotary(q[..., nope_dim:], rope_theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope_dim], k_rope], axis=-1)
    out = attention(q, k, kv[..., nope_dim:])           # (b, heads, s, value)
    gate = jax.nn.sigmoid(_mm(u, _f32(p["gate_kernel"]), operand_dtype))
    out = out.transpose(0, 2, 1, 3) * gate[..., None]
    return _mm(out.reshape(batch, seq, -1),
               w_o.reshape(-1, hidden), operand_dtype)


def gated_mlp(u, gate, up, down, operand_dtype=None):
    return _mm(jax.nn.silu(_mm(u, _f32(gate), operand_dtype))
               * _mm(u, _f32(up), operand_dtype), _f32(down), operand_dtype)


def router(u, w_router, *, experts_per_token, weight_scale, n_group,
           topk_group, selection_bias=None, operand_dtype=None):
    """(weights (tokens, k), experts (tokens, k), groups kept (tokens,
    topk_group)) of the group-limited sigmoid router."""
    scores = jax.nn.sigmoid(_mm(u, w_router, operand_dtype))
    biased = scores if selection_bias is None else scores + selection_bias
    grouped = biased.reshape(biased.shape[0], n_group, -1)
    group_score = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0].sum(-1)
    groups = jax.lax.top_k(group_score, topk_group)[1]
    kept = (groups[..., None] == jnp.arange(n_group)).any(axis=-2)
    limited = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
        biased.shape)
    experts = jax.lax.top_k(limited, experts_per_token)[1]
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return weight_scale * chosen / (chosen.sum(-1, keepdims=True)
                                    + 1e-20), experts, groups


def sparse_experts(u, p, *, num_experts, expert_shard, operand_dtype=None,
                   **routing):
    """(the layer's output for `u` (tokens, hidden), chosen experts)."""
    weights, experts, _ = router(u, _f32(p["router_kernel"]),
                                 operand_dtype=operand_dtype, **routing)
    first = expert_shard[0] * (num_experts // expert_shard[1])
    routed = experts_of_shard(u, p, weights, experts, first, operand_dtype)
    shared = gated_mlp(u, *(p[n]["kernel"] for n in (
        "shared_gate", "shared_up", "shared_down")), operand_dtype)
    return routed + shared, experts


def layer(x, p, kind, *, head_dim, lower_bound, nope_dim, rope_theta,
          norm_eps, num_experts, experts_per_token, expert_shard,
          weight_scale, n_group, topk_group, selection_bias=None,
          operand_dtype=None):
    """(x out, chosen experts or None) of one pattern entry of `kind`."""
    u = rms_norm(x, _f32(p["norm"]["scale"]), norm_eps)
    m, experts = p["mixer"], None
    if kind == "delta":
        out = kda(u, m, head_dim=head_dim, lower_bound=lower_bound,
                  norm_eps=norm_eps, operand_dtype=operand_dtype)
    elif kind == "latent_attention":
        out = latent_attention(u, m, nope_dim=nope_dim, rope_theta=rope_theta,
                               norm_eps=norm_eps, operand_dtype=operand_dtype)
    elif kind == "gated_mlp":
        out = gated_mlp(u, *(m[n]["kernel"] for n in ("gate", "up", "down")),
                        operand_dtype)
    else:
        out, experts = sparse_experts(
            u.reshape(-1, u.shape[-1]), m, num_experts=num_experts,
            expert_shard=expert_shard, experts_per_token=experts_per_token,
            weight_scale=weight_scale, n_group=n_group, topk_group=topk_group,
            selection_bias=selection_bias, operand_dtype=operand_dtype)
        out = out.reshape(x.shape)
    return x + out, experts


def forward(params, tokens, *, layers, selection_bias=None, **config):
    """(final hidden states, [chosen experts of each expert layer]);
    `layers`: the kinds in order; `selection_bias`: (expert layers, experts)
    or None; `config`: `layer`'s other keywords."""
    x = _f32(params["embed"]["embedding"])[tokens]
    chosen = []
    for index, kind in enumerate(layers):
        bias = None if selection_bias is None or kind != "experts" \
            else selection_bias[len(chosen)]
        x, experts = jax.checkpoint(
            lambda x, p, bias, kind=kind: layer(
                x, p, kind, selection_bias=bias, **config))(
                    x, params[f"layer_{index}"], bias)
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


def chosen_experts(params, tokens, **config):
    return loss_and_chosen(params, (tokens, tokens), **config)[1]
