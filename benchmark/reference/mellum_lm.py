"""Plain float32 reference of the decoder the repository runs for
Mellum2-12B-A2.5B (`models.TransformerLM(layers=...)` with the kinds
`window_attention`, `attention` and `experts`, a rotation a kind), in
`jax.numpy` with no kernel, no block skipping, no sorting, no grouped matmul,
no mixed precision, no framework and no import from `horovod_tpu` (the masked
softmax is reference/trinity_lm.py's `band_attention`, the softmax router
reference/sdar_lm.py's, the experts' loop and the head's loss
reference/moe_lm.py's).  `h_0 =
Embed(ids)`; a published layer `l` is

    u = RMSNorm(h);  q = u W_q -> H heads of e;  k = u W_k, v = u W_v -> H_kv
    q = RMSNorm_e(q) g_q;  k = RMSNorm_e(k) g_k       (one e-wide scale each)
    q, k = R_l(q, pos), R_l(k, pos)
    o_t = sum_s softmax_s(q_t . k_s / sqrt(e)) v_s    over s <= t and, in a
          `window_attention` layer, t - s < window (itself and window - 1
          before); query head j reads key/value head j // (H / H_kv), WRITTEN
          AS AN INDEX (no repeat)
    h = h + concat_heads(o) W_o
    x = RMSNorm(h);  p = softmax_float32(x W_r) over ALL experts
    E = the k largest;  w_e = p_e / sum_E p           (`norm_topk_prob`)
    h = h + sum_{e in E, held here} w_e W_d^e (silu(W_g^e x) * W_u^e x)

two pattern entries of the system's tree (attention, then the experts), every
norm an RMSNorm with a learned scale, no bias anywhere; then RMSNorm, the
untied head and the mean next-token cross-entropy.

`R_l`: pair i of e / 2 — ADJACENT columns (x[2i], x[2i+1]), the repository's
pairing — turns by `pos * f_i`, cosine and sine multiplied by `a`.  A
`window_attention` layer: `f_i = theta ** (-i / (e / 2))`, `a = 1`.  An
`attention` layer, YaRN (arXiv:2309.00071) as the source's
`rope_parameters.full_attention` states it: `plain_i = theta ** (-i / (e /
2))`, `c(r) = (e / 2) ln(P / (2 pi r)) / ln(theta)` with `P` the original
positions, `low = floor(c(beta_fast))`, `high = ceil(c(beta_slow))`, both
clipped into `[0, e - 1]`, `ramp_i = clip((i - low) / (high - low), 0, 1)`,
`f_i = plain_i / factor * ramp_i + plain_i (1 - ramp_i)`, `a =
attention_factor` (the scores carry `a ** 2`).  The table depends on no
sequence length.  At the published numbers (theta 500,000, e 128, P 8,192,
factor 16, beta 32 and 1) `c` reads 18.08 and 34.98: low 18, high 35.

The shares (`expert_shard`, a sliced vocabulary) are what the system computes:
the experts the parameter tree holds, the router over all experts.  With the
whole layer's parameters it is the whole layer (tests/test_mellum.py adds the
shares up to it).

Departures from the published model, each of which the system shares, and the
reading the config does not settle (the q/k norm) are in
benchmark/configs/mellum2.json under `departures` and `assumed`.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a
published layer, an expert, a head's block of query rows and a block of the
head only bounds what autodiff keeps (this runs beside 9.5 GB of training
state on a 16 GB chip; one head's 16,384 x 16,384 scores are 1.07 GB, a block
of 512 query rows of them 34 MB); it changes no arithmetic, nor does `lax.map`
over the heads.  `operand_dtype` rounds every matmul operand, and the q, k, v
the attention reads, to a narrower type and back: how the tolerances below
were shown to refuse the next precision under bfloat16 (float8_e4m3fn).
`drop` names one term left out or changed — `"attention_factor"` (`a = 1` in
the full layers), `"yarn"` (the full layers turn at the plain frequencies,
`a` kept), `"window"` (the windowed layers see every earlier key),
`"renormalize"` (the division by the chosen probabilities' sum) — and
`window_error` widens (+1) or narrows (-1) every window by one key: the wrong
programs the rows below must refuse.

Tolerances (used by benchmark/builders/mellum_lm.py), each with its reason:
the system computes in bfloat16 with float32 accumulation, stores the logits
in bfloat16, and ROUTES on bfloat16 activations, 8 of 64 experts a token.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.moe_lm import (_f32, _mm, cross_entropy,
                                        experts_of_shard, rms_norm)
from benchmark.reference.sdar_lm import router
from benchmark.reference.trinity_lm import _round, band_attention

# Each limit from two readings on the chip at the published widths and the
# timed sizes (PERF.md section 6, PR 49): the largest the system gave over the
# seeds named there (thirteen runs), and what THIS reference gives against
# itself with every matmul operand, forward and backward, and the q, k, v the
# attention reads rounded to float8_e4m3fn — the nearest precision under the
# configuration's bfloat16 — or with one term dropped (seed 4900000021), each
# of which has to be refused by one row at least.
#
# |loss_system - loss_reference| / loss_reference: a mean over 16,384 tokens
# of a log-softmax over 24,576 rounded logits; the roundings average out.
# System 2.4e-5 at most; the limit of the harness's accepted expert cells
# (Ling's, Trinity's), ten times the largest reading: float8 reads 2.0e-5, a
# full layer without its attention factor 2.8e-5, at the plain frequencies
# 3.6e-5 — the loss tells none of them from bfloat16, the rows below do.
LOSS_RTOL = 2.5e-4
# ||g_system - g_reference|| / ||g_reference|| over all parameters.  System
# 0.0237-0.0255 (Trinity's 0.0148 at half the rows: four layers of 16,384
# rounded rows each); float8 0.412; a full layer without its attention factor
# (scores 1.63 times too flat) 0.112, at the plain frequencies 0.138.
GRAD_RTOL = 6e-2
# | ||g_system|| / ||g_reference|| - 1 |: only the error's component along
# the gradient.  System 8.6e-5 at most; float8 0.0877 (small cotangents
# underflow its 4-bit exponent), no attention factor 0.0090; the plain
# frequencies in the full layer leave the norm where it was (3.3e-4: the row
# above is what refuses them).
GRAD_NORM_RTOL = 2e-3
# Share of (token, choice) pairs, over the four expert layers, whose expert the
# float32 reference did not choose for that token: near-ties between the 8th
# and 9th of 64 probabilities that a bfloat16 rounding of the activations
# flips.  System 0.0042-0.0045; float8 0.0109 (no attention factor 0.0067,
# the plain frequencies 0.0091: they move the router's input less than a
# precision does).
ROUTING_MISMATCH_MAX = 7e-3
# The banded and the causal kernels alone, as the plan runs them at the cell's
# length (the split pair in 1,024-blocks), against `band_attention` on the
# same rounded q, k, v at the cell's head width, two heads, forward and
# gradients (compare.kernel_against), with the softmax SHARP_SCALE times
# sharper than the model's: at the model's own scale a seeded row spreads its
# weight over ~1,000 keys and one key more or fewer at the window's edge moves
# an output by less than bfloat16's rounding of the probabilities does;
# sharpened, a few keys carry a row, and in the rows where the edge key is one
# of them a window one key off moves the output by a large part of a value.
# Forward, the largest absolute error of an output element (values are N(0,
# 1)): the banded kernels 0.058 at most, the causal ones 0.064
# (bfloat16 probabilities against float32 ones); a window one key too wide
# 2.54, one too narrow 2.74, none at all (the causal kernels for the band)
# 4.51, a band for the causal mask 4.51.  Gradients, the largest error over
# the reference gradient's largest element: the kernels at most 0.0182;
# one key off 0.31 at the least, the wrong mask 0.74 at the least.
SHARP_SCALE = 4.0
FLASH_FWD_ATOL = 2.5e-1
FLASH_GRAD_RTOL = 5e-2

def yarn_table(theta, pairs, *, factor, original_positions, beta_fast,
               beta_slow):
    """(frequencies [pairs] as Python floats, low, high): the module
    docstring's `f_i`, in double precision, a pair at a time."""
    def pair_turning(times):
        return pairs * math.log(original_positions / (2 * math.pi * times)) \
            / math.log(theta)

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), 2 * pairs - 1)
    table = []
    for i in range(pairs):
        plain = theta ** (-i / pairs)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        table.append(plain / factor * ramp + plain * (1.0 - ramp))
    return table, low, high


def rotary(x, theta, yarn=None, drop=None):
    """(..., seq, e): pairs (x[2i], x[2i+1]) of row t turn by `t * f_i`;
    `yarn` None: the plain frequencies and `a = 1`; else the dictionary of
    YaRN's numbers (`factor`, `original_positions`, `beta_fast`, `beta_slow`,
    `attention_factor`)."""
    seq, pairs = x.shape[-2], x.shape[-1] // 2
    scale = 1.0
    if yarn is None or drop == "yarn":
        freqs = [theta ** (-i / pairs) for i in range(pairs)]
    else:
        freqs = yarn_table(theta, pairs, **{
            name: yarn[name] for name in (
                "factor", "original_positions", "beta_fast", "beta_slow")})[0]
    if yarn is not None and drop != "attention_factor":
        scale = yarn["attention_factor"]
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]
    cos, sin = scale * jnp.cos(angles), scale * jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention_layer(u, p, *, window, rope_theta, yarn, norm_eps,
                    operand_dtype=None, drop=None):
    """Grouped-query attention with per-head q/k norms on the normed input
    `u` (batch, seq, hidden); `window` None: every earlier key.  `p` holds
    `q_kernel` (hidden, heads, e), `kv_kernel` (hidden, 2, key/value heads,
    e), `q_head_norm_scale` and `k_head_norm_scale` (e,) and `o_kernel`
    (heads, e, hidden)."""
    batch, seq, hidden = u.shape
    w_q, w_kv, w_o = (_f32(p[n]) for n in ("q_kernel", "kv_kernel",
                                           "o_kernel"))
    width = w_q.shape[2]

    def per_head(w):
        return _mm(u, w.reshape(hidden, -1), operand_dtype).reshape(
            batch, seq, -1, width).transpose(0, 2, 1, 3)

    q = rms_norm(per_head(w_q), _f32(p["q_head_norm_scale"]), norm_eps)
    k = rms_norm(per_head(w_kv[:, 0]), _f32(p["k_head_norm_scale"]), norm_eps)
    v = per_head(w_kv[:, 1])
    q, k = (rotary(t, rope_theta, yarn, drop) for t in (q, k))
    out = band_attention(*(_round(t, operand_dtype) for t in (q, k, v)),
                         window=window)               # (b, heads, seq, e)
    return _mm(out.transpose(0, 2, 1, 3).reshape(batch, seq, -1),
               w_o.reshape(-1, hidden), operand_dtype)


def sparse_experts(u, p, *, num_experts, experts_per_token, expert_shard,
                   operand_dtype=None, drop=None):
    """(the layer's output for `u` (tokens, hidden), chosen experts): this
    shard's experts' part, each applied to every token and masked by its
    weight."""
    weights, experts = router(u, _f32(p["router_kernel"]),
                              experts_per_token=experts_per_token,
                              operand_dtype=operand_dtype, drop=drop)
    first = expert_shard[0] * (num_experts // expert_shard[1])
    return experts_of_shard(u, p, weights, experts, first,
                            operand_dtype), experts


def layer(x, p_attention, p_experts, kind, *, window, rope_theta, yarn,
          norm_eps, num_experts, experts_per_token, expert_shard,
          operand_dtype=None, drop=None, window_error=0):
    """(x out, chosen experts) of one published layer of `kind`
    (`window_attention` or `attention`): two pattern entries of the system's
    tree, its attention and its experts."""
    u = rms_norm(x, _f32(p_attention["norm"]["scale"]), norm_eps)
    windowed = kind == "window_attention"
    span = window + window_error if windowed and drop != "window" else None
    x = x + attention_layer(
        u, p_attention["mixer"], window=span, rope_theta=rope_theta,
        yarn=None if windowed else yarn, norm_eps=norm_eps,
        operand_dtype=operand_dtype, drop=drop)
    u = rms_norm(x, _f32(p_experts["norm"]["scale"]), norm_eps).reshape(
        -1, x.shape[-1])
    mixed, experts = sparse_experts(
        u, p_experts["mixer"], num_experts=num_experts,
        experts_per_token=experts_per_token, expert_shard=expert_shard,
        operand_dtype=operand_dtype, drop=drop)
    return x + mixed.reshape(x.shape), experts


def forward(params, tokens, *, layers, **config):
    """(final hidden states, [chosen experts of each published layer]);
    `layers`: the attention kind of each published layer, in order; `config`:
    `layer`'s keywords."""
    x = _f32(params["embed"]["embedding"])[tokens]
    chosen = []
    for index, kind in enumerate(layers):
        x, experts = jax.checkpoint(
            lambda x, p_a, p_e, kind=kind: layer(x, p_a, p_e, kind, **config))(
                x, params[f"layer_{2 * index}"],
                params[f"layer_{2 * index + 1}"])
        chosen.append(experts)
    return rms_norm(x, _f32(params["final_norm"]["scale"]),
                    config["norm_eps"]), chosen


def loss_and_chosen(params, batch, **config):
    """(mean next-token cross-entropy, the experts routed to: (layers, tokens,
    k)) from ONE forward pass; `batch` is (inputs, targets)."""
    inputs, targets = batch
    hidden, chosen = forward(params, inputs, **config)
    return cross_entropy(hidden, _f32(params["lm_head_kernel"]), targets,
                         config.get("operand_dtype")), jnp.stack(chosen)


def loss(params, batch, **config):
    return loss_and_chosen(params, batch, **config)[0]
