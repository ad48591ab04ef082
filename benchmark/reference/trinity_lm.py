"""Plain float32 reference of the decoder the repository runs for Trinity-Mini
(`models.TransformerLM(layers=...)` with the kinds `window_attention`,
`attention`, `gated_mlp` and `experts`, `post_norm`, `embed_scale`), in
`jax.numpy` with no kernel, no block skipping, no sorting, no grouped matmul,
no mixed precision and no framework.  `x_0 = embed_scale * Embed(ids)`; every
pattern entry is `x <- x + RMSNorm_post(Mixer(RMSNorm(x)))` (a published layer
is two of them: attention, then an MLP or the experts), every norm an RMSNorm
with a learned scale; then RMSNorm, the untied head and the mean next-token
cross-entropy.  The mixers on the normed input `u`, with the experts the
parameter tree holds:

`window_attention`, `attention`: `q_h = RMSNorm_e(u W_q)_h`, `k_g =
    RMSNorm_e(u W_k)_g` (ONE learned scale of the head's width for q, one for
    k), `v_g = (u W_v)_g`; query head h reads key/value head `h // (H / H_kv)`,
    WRITTEN AS AN INDEX (no repeat); a `window_attention` layer turns q and k
    (adjacent pairs, base `rope_theta`, the whole head) and query t sees the
    keys s with `0 <= t - s < window`; an `attention` layer turns nothing and
    sees `s <= t`; softmax at `e^-1/2` over whole rows of keys under the
    EXPLICIT mask; `concat_h(o_h) * sigmoid(u W_g)`; `W_o`.
`gated_mlp`: `W_down (silu(W_gate u) * W_up u)`.
`experts`: `s = sigmoid(u W_r)`; the k largest `s + bias` are chosen
    (`lax.top_k`, one group); `g_e = scale * s_e / sum_chosen s`; `sum_chosen
    g_e W2_e (silu(W1_e u) * W3_e u)`, a loop over THIS SHARD's experts, each
    applied to every token and masked by its weight; plus the shared expert of
    the same gated form, unweighted.

The shares (`expert_shard`, a sliced vocabulary) are what the system computes:
the experts the parameter tree holds, the router over all experts.  With the
whole layer's parameters it is the whole layer (tests/test_trinity.py adds the
shares up to it).

Departures from the published model, each of which the system shares: the
selection bias is what the caller passes; the loss is the cross-entropy alone
(`load_balance_coeff`'s term is not in it); the readings the config does not
settle are listed under `assumed` in benchmark/configs/trinitymini.json.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a
pattern entry, an expert, a head's block of query rows and a block of the head
only bounds what autodiff keeps (this runs beside 8.5 GB of training state on a
16 GB chip; one head's 8,192 x 8,192 scores are 268 MB); it changes no
arithmetic, nor does `lax.map` over the heads.  `operand_dtype` rounds every
matmul operand, and the q, k, v the attention reads, to a narrower type and
back: how the tolerances below were shown to refuse the next precision under
bfloat16 (float8_e4m3fn).  `window_error` widens (+1) or narrows (-1) every
window by one key and `causal_for_window` drops the window: the wrong programs
the attention rows below must refuse.

Tolerances (used by benchmark/builders/trinity_lm.py), each with its reason:
the system computes in bfloat16 with float32 accumulation, stores the logits
in bfloat16, and ROUTES on bfloat16 activations, 8 of 128 experts a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ling_lm import gated_mlp, rotary
from benchmark.reference.moe_lm import (_f32, _mm, cross_entropy,
                                        experts_of_shard, rms_norm)

# Each limit from two readings on the chip at the published widths and the
# timed sizes (PERF.md section 6, PR 36): the largest the system gave over the
# seeds named there (thirteen at the configuration as it stands: 3600000002,
# 3600000011 to ...013, 2147483999 twice, 3600000015, ...016, ...031, ...032,
# 2147488888, 3600000034 and the traced 3600000002),
# and what THIS reference gives against itself with every matmul operand,
# forward and backward, and the q, k, v the attention reads rounded to
# float8_e4m3fn — the nearest precision under the configuration's bfloat16
# (seeds 3600000021, ...022) — which has to be refused.
#
# |loss_system - loss_reference| / loss_reference: a mean over 8,192 tokens of
# a log-softmax over 25,024 rounded logits; the roundings average out.  System
# 2e-6 to 1.9e-5; the limit of the harness's accepted expert cells (Ling's),
# thirteen times the largest reading: float8 reads 3.6e-5 and 1.3e-4, the loss
# does not tell it from bfloat16, the three rows below do.
LOSS_RTOL = 2.5e-4
# ||g_system - g_reference|| / ||g_reference|| over all parameters.  System
# 0.0136 to 0.0148 (OLMoE's 0.010 to 0.012, Nemotron's 0.034 to 0.042); float8
# 0.543 and 0.549.
GRAD_RTOL = 6e-2
# | ||g_system|| / ||g_reference|| - 1 |: only the error's component along
# the gradient.  System 2.2e-5 to 1.3e-4; float8 0.181 and 0.185 (small
# cotangents underflow its 4-bit exponent).
GRAD_NORM_RTOL = 2e-3
# Share of (token, choice) pairs, over the four expert layers, whose expert the
# float32 reference did not choose for that token: near-ties between the 8th
# and 9th of 128 scores that a bfloat16 rounding of the activations flips.
# System 0.0059 to 0.0064; float8 0.0365 and 0.0366.
ROUTING_MISMATCH_MAX = 1.8e-2
# The banded kernels alone against `band_attention` on the same rounded q, k,
# v at the cell's length and head width, two heads, forward and gradients
# (compare.kernel_against), with the softmax SHARP_SCALE times sharper than
# the model's: at the model's own scale a seeded row spreads its weight over
# ~2,000 keys and one key more or fewer at the window's edge moves an output
# by less than bfloat16's rounding of the probabilities does; sharpened, a few
# keys carry a row, and in the rows where the edge key is one of them a window
# one key off moves the output by a large part of a value.  Forward, the
# largest absolute error of an output element (values are N(0, 1)): the banded
# kernels 0.040 to 0.063 over fifteen seeds (bfloat16 probabilities against
# float32 ones, and largest where few keys carry a row); a window one key too
# wide 1.82 and 1.82, one too narrow 2.46 and 1.92, none at all (the causal
# kernel) 3.92 and 4.46 (seeds 3600000021, ...022).  Gradients, the largest
# error over the reference gradient's largest element: the banded kernels at
# most 0.018 (dq), 0.013 (dk), 0.008 (dv); one key off 0.166 at the least, the
# causal kernel 0.61 at the least.
SHARP_SCALE = 4.0
WINDOW_FWD_ATOL = 2.5e-1
WINDOW_GRAD_RTOL = 5e-2

QUERY_BLOCK = 512        # query rows of one head whose scores exist at once


def _round(a, operand_dtype):
    """What `_mm` does to an operand, for the q, k, v the attention reads."""
    return a if operand_dtype is None else _f32(a.astype(operand_dtype))


def band_attention(q, k, v, *, window=None, sm_scale=None):
    """Softmax attention under an explicit mask, `q` (batch, heads, seq, e),
    `k` and `v` (batch, key/value heads, seq, e): query head h reads key/value
    head `h // (heads / key/value heads)`; query t sees key s where `s <= t`
    and, with a `window`, `t - s < window`.  One head and QUERY_BLOCK query
    rows at a time, whole rows of keys."""
    batch, heads, seq, width = q.shape
    group = heads // k.shape[1]
    scale = width ** -0.5 if sm_scale is None else sm_scale
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def rows(q_rows, start, k_h, v_h):           # (batch, block, e)
        scores = jnp.einsum("bqe,bke->bqk", q_rows, k_h) * scale
        t = start + jnp.arange(block)
        seen = key_pos[None, :] <= t[:, None]
        if window is not None:
            seen = seen & (t[:, None] - key_pos[None, :] < window)
        return jnp.einsum("bqk,bke->bqe", jax.nn.softmax(
            jnp.where(seen, scores, -jnp.inf), axis=-1), v_h)

    def head(h):
        q_h, k_h, v_h = q[:, h], k[:, h // group], v[:, h // group]
        blocks = q_h.reshape(batch, seq // block, block, width).swapaxes(0, 1)
        out = jax.lax.map(
            lambda xs: rows(xs[0], xs[1], k_h, v_h),
            (blocks, jnp.arange(0, seq, block)))
        return out.swapaxes(0, 1).reshape(batch, seq, v.shape[-1])

    return jax.lax.map(head, jnp.arange(heads)).swapaxes(0, 1)


def attention_layer(u, p, *, window, rope_theta, norm_eps,
                    operand_dtype=None):
    """Gated attention with per-head q/k norms on the normed input `u`
    (batch, seq, hidden); `window` None: every earlier key and NO rotation.
    `p` holds `q_kernel` (hidden, heads, e), `kv_kernel` (hidden, 2, key/value
    heads, e), `q_head_norm_scale` and `k_head_norm_scale` (e,), `gate_kernel`
    (hidden, heads, e) and `o_kernel` (heads, e, hidden)."""
    batch, seq, hidden = u.shape
    w_q, w_kv, w_g, w_o = (_f32(p[n]) for n in (
        "q_kernel", "kv_kernel", "gate_kernel", "o_kernel"))
    heads, width = w_q.shape[1:]

    def per_head(w):
        return _mm(u, w.reshape(hidden, -1), operand_dtype).reshape(
            batch, seq, -1, width).transpose(0, 2, 1, 3)

    q = rms_norm(per_head(w_q), _f32(p["q_head_norm_scale"]), norm_eps)
    k = rms_norm(per_head(w_kv[:, 0]), _f32(p["k_head_norm_scale"]), norm_eps)
    v = per_head(w_kv[:, 1])
    if window is not None:
        q, k = rotary(q, rope_theta), rotary(k, rope_theta)
    out = band_attention(*(_round(t, operand_dtype) for t in (q, k, v)),
                         window=window)               # (b, heads, seq, e)
    gate = jax.nn.sigmoid(per_head(w_g))
    return _mm((out * gate).transpose(0, 2, 1, 3).reshape(batch, seq, -1),
               w_o.reshape(-1, hidden), operand_dtype)


def router(u, w_router, *, experts_per_token, weight_scale,
           selection_bias=None, operand_dtype=None):
    """(weights (tokens, k), experts (tokens, k)) of the sigmoid router: the
    k largest `score + bias`, weighted by their own scores over their sum."""
    scores = jax.nn.sigmoid(_mm(u, w_router, operand_dtype))
    biased = scores if selection_bias is None else scores + selection_bias
    experts = jax.lax.top_k(biased, experts_per_token)[1]
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return weight_scale * chosen / (chosen.sum(-1, keepdims=True)
                                    + 1e-20), experts


def sparse_experts(u, p, *, num_experts, expert_shard, operand_dtype=None,
                   **routing):
    """(the layer's output for `u` (tokens, hidden), chosen experts)."""
    weights, experts = router(u, _f32(p["router_kernel"]),
                              operand_dtype=operand_dtype, **routing)
    first = expert_shard[0] * (num_experts // expert_shard[1])
    routed = experts_of_shard(u, p, weights, experts, first, operand_dtype)
    shared = gated_mlp(u, *(p[n]["kernel"] for n in (
        "shared_gate", "shared_up", "shared_down")), operand_dtype)
    return routed + shared, experts


def layer(x, p, kind, *, window, rope_theta, norm_eps, num_experts,
          experts_per_token, expert_shard, weight_scale, selection_bias=None,
          operand_dtype=None, window_error=0, causal_for_window=False):
    """(x out, chosen experts or None) of one pattern entry of `kind`."""
    u = rms_norm(x, _f32(p["norm"]["scale"]), norm_eps)
    m, experts = p["mixer"], None
    if kind in ("window_attention", "attention"):
        span = None                   # a full layer: every key, no rotation
        if kind == "window_attention":
            span = x.shape[1] if causal_for_window else window + window_error
        out = attention_layer(u, m, window=span, rope_theta=rope_theta,
                              norm_eps=norm_eps, operand_dtype=operand_dtype)
    elif kind == "gated_mlp":
        out = gated_mlp(u, *(m[n]["kernel"] for n in ("gate", "up", "down")),
                        operand_dtype)
    else:
        out, experts = sparse_experts(
            u.reshape(-1, u.shape[-1]), m, num_experts=num_experts,
            expert_shard=expert_shard, experts_per_token=experts_per_token,
            weight_scale=weight_scale, selection_bias=selection_bias,
            operand_dtype=operand_dtype)
        out = out.reshape(x.shape)
    return x + rms_norm(out, _f32(p["post_norm"]["scale"]), norm_eps), experts


def forward(params, tokens, *, layers, embed_scale, selection_bias=None,
            **config):
    """(final hidden states, [chosen experts of each expert layer]);
    `layers`: the kinds in order; `selection_bias`: (expert layers, experts)
    or None; `config`: `layer`'s other keywords."""
    x = embed_scale * _f32(params["embed"]["embedding"])[tokens]
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
