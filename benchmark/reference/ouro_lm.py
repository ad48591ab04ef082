"""Plain float32 reference of the looped language model the repository runs
for Ouro-2.6B (`models.TransformerLM(layers=..., loops=T, exit_gate=True,
post_norm=True)` with `models.looped_exit_loss`), in `jax.numpy` with no
kernel, no rolled loop, no mixed precision and no framework: a Python loop over
the passes and, inside it, over the layers, ONE set of weights.

    h_0 = Embed(ids);  for t = 1..T:  h_t = N_f(M(h_{t-1}))
    a layer of M, two pattern entries:  x <- x + N_2(Attn(N_1(x))),
                                        x <- x + N_4(MLP(N_3(x)))
    Attn: q, k, v = u W_qkv a head; q and k turned (adjacent pairs, the whole
        head, base `rope_theta`); softmax at e^-1/2 over the earlier keys under
        an explicit mask (`trinity_lm.band_attention`, no window); W_o
    MLP:  W_down (silu(W_gate u) * W_up u)
    gate: z_t = w_g . h_t + b_g;  lambda_t = sigmoid(z_t);  p_1 = lambda_1,
        p_t = lambda_t prod_{j<t} (1 - lambda_j), p_T = prod_{j<T} (1 -
        lambda_j) — written as PRODUCTS of sigmoids, not as the system's sums
        of log-sigmoids
    CE_t: the per-token cross-entropy of h_t W_head against the next token
    loss: mean over positions of [sum_t p_t CE_t - beta H(p)],
        H(p) = -sum_t p_t log p_t

Every norm is an RMSNorm with a learned scale.  It reads the system's own
parameter tree (flax names).  The caller traces it under
`jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a pattern
entry, around a head's block of query rows and around a block of the head's
tokens only bounds what autodiff keeps (this runs beside 9.8 GB of training
state and the system's 2.4 GB of gradients on a 16 GB chip): memory, not
arithmetic.  `operand_dtype` rounds every matmul operand, the q, k, v the
attention reads and the state the gate reads to a narrower type and back
(`_round`: the values rounded, the gradient as through no rounding): how the
tolerances below were shown to refuse the next precision under bfloat16
(float8_e4m3fn's mantissa).  `gate_dtype` rounds the gate alone — the state
it reads, its weight and its logit, cotangents too (`_kept`).  `passes_run`
runs fewer passes than the gate is told of (the last one's state standing in
for the skipped ones') and `untied` gives each pass layers of its own: the
wrong program the rows below are read against, and what tests/test_ouro.py
sums a looped weight's gradient from.

Tolerances (used by benchmark/builders/ouro_lm.py), each with its reason: the
system computes in bfloat16 with float32 accumulation, stores a pass's logits
in bfloat16, and reads the gate from the bfloat16 state in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ling_lm import rotary
from benchmark.reference.moe_lm import _f32, rms_norm
from benchmark.reference.trinity_lm import band_attention

# Each limit from two readings on the chip at the published widths and the
# timed sizes (PERF.md section 6, PR 54; my chip runs), between them with room
# on both sides: the largest the SYSTEM gave over 27 runs of 27 seeds, and
# what THIS reference gives against itself with every matmul operand, the q,
# k, v the attention reads and the gate's input at float8_e4m3's mantissa
# (`operand_dtype`, seeds 5400000201 and ...202), the nearest precision under
# the configuration's bfloat16, which every row has to refuse.  (At bfloat16's
# mantissa the same control reads inside the system's range in every row.)
# Beside them what it gives with the fourth pass skipped (seed 5400000201).
#
# |loss_system - loss_reference| / |loss_reference|: a mean over 4,096 tokens
# of four weighed log-softmaxes over 49,152 rounded logits, less a tenth of an
# entropy; the roundings average out.  System 5e-7 to 4.3e-5; float8 1.98e-4
# and 2.00e-4; a pass skipped 1.1e-4.  Twice the one, under half the other.
LOSS_RTOL = 9e-5
# max over the T passes of |mean CE_t system - reference| / reference: each a
# mean over 4,096 tokens, as the loss.  System 1.9e-5 to 8.2e-5; float8 3.3e-4
# and 4.5e-4; a pass skipped 7.1e-4 (the skipped pass's own).
PASS_CE_RTOL = 1.6e-4
# max over the T passes of |mean p_t system - reference|, absolute (p_t lies
# in [0, 1] and sums to 1 over t): the gate reads a state that has come
# through up to 32 layer applications in bfloat16, and the part of its error
# that every position shares does not average out over 4,096 positions.
# System 2.8e-4 to 2.7e-3; float8 0.020 and 0.021.
EXIT_P_ATOL = 6e-3
# ||g_system - g_reference|| / ||g_reference|| over a GROUP of parameters: the
# looped layers' (each the sum of T passes' contributions), the head's, the
# embedding's.  Every matmul operand is rounded to bfloat16 forward and
# backward through T x 8 layer applications, twice the depth of the cells
# whose limit is 6e-2.  System: layers 0.017 to 0.039, head 0.014 to 0.024,
# embedding 0.018 to 0.038; float8 0.20 and 0.30, 0.17 and 0.21, 0.20 and
# 0.29; a pass skipped 0.20, 0.13, 0.18.
GRAD_RTOL = 1e-1
# The same over the gate's `w_g` and `b_g`: their gradient is a sum over 4,096
# positions of float32 arithmetic on (CE_t, p_t) times the bfloat16 state.
# System 0.008 to 0.037; float8 0.11 and 0.19; a pass skipped 0.069.
#
# What NO row refuses is the gate ALONE in bfloat16 (`gate_dtype`: its state,
# `w_g` and logit at 8 mantissa bits, seeds 5400000301 and ...302): exit_p
# 2.0e-4 and 1.6e-4, this row 1.8e-3 and 2.2e-3, every other under 1.8e-3 — a
# tenth of what the bfloat16 state already does to the same logit, under any
# limit the system passes.  That the gate is float32 is held by its types
# (tests/test_ouro.py), not by this comparison.
GATE_GRAD_RTOL = 6.5e-2
# | ||g_system|| / ||g_reference|| - 1 | over all parameters.  A pass's
# gradient is weighed by p_t, so the error of a MEAN p_t — 2.3e-3 of a p_t near
# a quarter is 1 % — scales that pass's whole contribution and moves the norm
# as no unbiased rounding does: the seed with the largest norm error (8.55e-3,
# 2154000304) has an `exit_p` error of 2.3e-3; rounding errors stand at right
# angles to the gradient and move its length by half their square.  So this
# row parts the two by less than any other: system 1.5e-4 to 8.6e-3; float8
# 0.0155 and 0.049; a pass skipped 0.020.  1.5 times the one, 0.84 of the
# other: every group's row above bounds the norm's error from above, and if a
# sound seed ever reads past this limit the row is the one to take out, not to
# widen (PERF.md section 7).
GRAD_NORM_RTOL = 1.3e-2

TOKEN_BLOCK = 512        # tokens of the head whose logits exist at once


def _kept(a, dtype):
    """`a` at `dtype`'s mantissa, float32 still (and float32's range: the
    controls are of precision — an unscaled float8_e4m3fn would flush half of
    the weights, 0.022 a standard deviation against a smallest normal of
    0.0156).  By `lax.reduce_precision`, which XLA keeps: on the chip it takes
    an `astype` there and back out of an elementwise expression as excess
    precision (a gate rounded so read 0.0 in every row, PERF.md section 6)."""
    if dtype is None:
        return a
    return jax.lax.reduce_precision(a, 8, jnp.finfo(dtype).nmant)


def _round(a, operand_dtype):
    """`_kept` with the gradient as through no rounding: the transpose of a
    rounding rounds the COTANGENT too, which is no part of "operands in
    float8" (by `astype` to float8_e4m3fn, smallest subnormal 2^-9, it flushed
    the gradient of a mean over 4,096 tokens to zero: a control that read "no
    gradient")."""
    if operand_dtype is None:
        return a
    return a + jax.lax.stop_gradient(_kept(a, operand_dtype) - a)


def _mm(a, b, operand_dtype):
    return _round(a, operand_dtype) @ _round(b, operand_dtype)


def attention_layer(u, p, *, rope_theta, operand_dtype=None):
    """Causal full attention on the normed input `u` (batch, seq, hidden);
    `p` holds `qkv_kernel` (hidden, 3, heads, e) and `o_kernel` (heads, e,
    hidden)."""
    batch, seq, hidden = u.shape
    w_qkv, w_o = _f32(p["qkv_kernel"]), _f32(p["o_kernel"])
    heads, width = w_qkv.shape[2:]

    def per_head(j):
        return _mm(u, w_qkv[:, j].reshape(hidden, -1), operand_dtype).reshape(
            batch, seq, heads, width).transpose(0, 2, 1, 3)

    q, k, v = rotary(per_head(0), rope_theta), rotary(per_head(1),
                                                      rope_theta), per_head(2)
    out = band_attention(*(_round(t, operand_dtype) for t in (q, k, v)))
    return _mm(out.transpose(0, 2, 1, 3).reshape(batch, seq, -1),
               w_o.reshape(-1, hidden), operand_dtype)


def entry(x, p, kind, *, rope_theta, norm_eps, operand_dtype=None):
    """One pattern entry: `x + N_post(Mixer(N(x)))`."""
    u = rms_norm(x, _f32(p["norm"]["scale"]), norm_eps)
    if kind == "attention":
        out = attention_layer(u, p["mixer"], rope_theta=rope_theta,
                              operand_dtype=operand_dtype)
    else:
        gate, up, down = (_f32(p["mixer"][n]["kernel"])
                          for n in ("gate", "up", "down"))
        out = _mm(jax.nn.silu(_mm(u, gate, operand_dtype))
                  * _mm(u, up, operand_dtype), down, operand_dtype)
    return x + rms_norm(out, _f32(p["post_norm"]["scale"]), norm_eps)


def states(params, tokens, *, layers, passes, passes_run=None, untied=None,
           **config):
    """[h_1 .. h_T], each (batch, seq, hidden): the normed state after every
    pass.  `untied`: a list of T parameter trees, pass t's layers read from
    `untied[t]` (embedding, `final_norm`, head and gate stay `params`')."""
    x = _f32(params["embed"]["embedding"])[tokens]
    out = []
    for t in range(passes if passes_run is None else passes_run):
        weights = params if untied is None else untied[t]
        for index, kind in enumerate(layers):
            x = jax.checkpoint(
                lambda x, p, kind=kind: entry(x, p, kind, **config))(
                    x, weights[f"layer_{index}"])
        x = rms_norm(x, _f32(params["final_norm"]["scale"]),
                     config["norm_eps"])
        out.append(x)
    return out + out[-1:] * (passes - len(out))


def token_cross_entropy(hidden, w_head, targets, operand_dtype=None):
    """Per-token cross-entropy (batch, seq) of the untied head, TOKEN_BLOCK
    tokens at a time so that 4,096 x 49,152 float32 logits and their
    log-softmax (1.6 GB) never exist at once."""
    shape = targets.shape
    hidden = hidden.reshape(-1, hidden.shape[-1])
    targets = targets.reshape(-1)
    block = TOKEN_BLOCK if hidden.shape[0] % TOKEN_BLOCK == 0 \
        else hidden.shape[0]

    @jax.checkpoint
    def rows(ht):
        logp = jax.nn.log_softmax(_mm(ht[0], w_head, operand_dtype), axis=-1)
        return -jnp.take_along_axis(logp, ht[1][:, None], axis=-1)[:, 0]

    return jax.lax.map(rows, (hidden.reshape(-1, block, hidden.shape[-1]),
                              targets.reshape(-1, block))).reshape(shape)


def exit_probabilities(params, hidden_states, operand_dtype=None,
                       gate_dtype=None):
    """(T, batch, seq): the exit distribution from the passes' states, as
    products of sigmoids; the last pass takes the remainder."""
    w_g, b_g = _f32(params["exit_gate_kernel"]), _f32(params["exit_gate_bias"])
    w_g = _kept(w_g, gate_dtype)
    left, p = 1.0, []
    for h in hidden_states[:-1]:
        h = _kept(_round(h, operand_dtype), gate_dtype)
        lam = jax.nn.sigmoid(_kept((h * w_g).sum(-1) + b_g, gate_dtype))
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left * jnp.ones(hidden_states[-1].shape[:-1])])


def loss_terms(params, batch, *, beta, **config):
    """(loss, per-pass mean cross-entropies (T,), mean exit probabilities
    (T,)); `batch` is (inputs, targets); `config`: layers, passes, rope_theta,
    norm_eps[, operand_dtype, gate_dtype, passes_run, untied]."""
    inputs, targets = batch
    operand_dtype = config.get("operand_dtype")
    gate_dtype = config.pop("gate_dtype", None)
    hidden = states(params, inputs, **config)
    w_head = _f32(params["lm_head_kernel"])
    ce = jnp.stack([token_cross_entropy(h, w_head, targets, operand_dtype)
                    for h in hidden])
    p = exit_probabilities(params, hidden, operand_dtype, gate_dtype)
    entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(axis=0)
    loss = ((p * ce).sum(axis=0) - beta * entropy).mean()
    return loss, ce.mean(axis=(1, 2)), p.mean(axis=(1, 2))


def loss(params, batch, **config):
    return loss_terms(params, batch, **config)[0]
