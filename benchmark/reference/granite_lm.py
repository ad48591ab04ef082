"""Plain float32 reference of the decoder the repository runs for
Granite-4.0-H-Micro (`models.TransformerLM(layers=..., tie_head=True)` with the
kinds `ssm`, `attention` and `gated_mlp` under the family's four muP
multipliers), in `jax.numpy` with no kernel, no chunks, no decay matrix, no
mixed precision and no framework.  With `e` the ONE table the embedding and the
head share, `m_e`, `m_r`, `m_a`, `m_l` the embedding, residual, attention and
logits multipliers and `N` an RMSNorm with a learned scale:

    x0 = m_e * e[ids]
    a pattern entry:  x <- x + m_r * Mixer(N(x))     (a published layer is two:
                                                      its mixer, then its MLP)
    logits = (N(x_L) e^T) / m_l;  mean next-token cross-entropy

`ssm`, Mamba-2 (H heads of P channels, G groups of N states, as the parameter
    tree's shapes give them): `[z | xBC | dt] = u W_in`; `xBC = silu(conv(xBC))`,
    a causal depthwise convolution WITH a bias; `x, B, C = split(xBC)`;
    `dt = softplus(dt + dt_bias)`, no upper clamp; `A = -exp(A_log)`; per head,
    with the B, C of its group (ONE group: every head the same),
    `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`, `y_t = h_t C_t + D x_t` —
    ONE `lax.scan` STEP A TOKEN, the update written as it stands;
    `y = N(y * silu(z))` within each group of HP/G channels (one group: all
    of them); `y W_out`.
`attention`: query head j reads key/value head j // (query heads / key/value
    heads); NO position embedding; causal softmax of `m_a q k^T` over whole
    rows of keys, `m_a` the configuration's and NOT head_dim^-1/2; `W_o`.
`gated_mlp`: `W_down (silu(W_gate u) * W_up u)`, no bias.

It reads the system's own parameter tree (flax names) and imports nothing from
`horovod_tpu`.  The caller traces it under
`jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a pattern
entry, a block of 64 tokens of the recurrence, a block of query rows and a
block of the head only bounds what autodiff keeps (this runs beside 9.3 GB of
training state on a 16 GB chip; the recurrence's states alone would be 17 GB a
layer); it changes no arithmetic.  Three knobs make the controls the
tolerances below were set by: `operand_dtype` rounds every matmul operand, and
the x, B, C and q, k, v that the recurrence and the attention read, to a
narrower type and back; `decay_dtype` rounds a token's decay `exp(dt_t A)`;
`state_dtype` rounds the recurrence's state every `state_every` tokens (what a
chunked form whose states BETWEEN chunks were narrow would do).

Departures from the published model, each of which the system shares, are
listed under `departures` in benchmark/configs/granite4hmicro.json; the forms
the config does not key under `assumed` there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ling_lm import RECURRENCE_BLOCK, _round, gated_mlp
from benchmark.reference.moe_lm import _f32, _mm, rms_norm
from benchmark.reference.trinity_lm import band_attention

# Each limit from two readings on the chip at the published widths and 8,192
# tokens (PERF.md section 6, PR 64; my chip runs), between them with room on
# both sides: the largest the SYSTEM gave over fourteen seeds (2164000002 to
# ...015, a process each), and what THIS reference gives against
# itself with every matmul operand, forward and backward, and the x, B, C of
# the recurrence and the q, k, v of the attention rounded to float8_e4m3fn —
# the nearest precision under the configuration's bfloat16 (`operand_dtype`,
# seed 2164000021) — which has to be refused.  Beside them what it gives with
# bfloat16 operands (the system's own precision, for scale), with a token's
# decay rounded to bfloat16 (`decay_dtype`) and with the recurrence's state
# rounded to bfloat16 every 256 tokens (`state_dtype`), same seed.
#
# |loss_system - loss_reference| / loss_reference: a mean over 8,192 tokens of
# a log-softmax over 12,544 rounded logits; the roundings average out.  System
# 1.2e-5 to 2.8e-5; float8 reads 2.2e-5, the loss does not tell it from
# bfloat16 (4.5e-6), the rows below do.  The limit of the harness's accepted
# cells (Ling's, Trinity's, Qwen3-Next's, Olmo-Hybrid's), nine times the
# largest reading.
LOSS_RTOL = 2.5e-4
# ||g_system - g_reference|| / ||g_reference|| over a GROUP of parameters
# (benchmark/builders/granite_lm.py `GROUPS`): every matmul operand is rounded
# to bfloat16 forward and backward through twenty pattern entries.  One limit
# for the four groups, near the geometric middle of the two readings: five
# times over the system's, eight times under float8's.
#   ssm:        system 0.0180 to 0.0186; float8 0.922; bfloat16 operands
#     0.0108.
#   attention:  system 0.0129 to 0.0146; float8 0.889; bfloat16 0.0080.
#   gated_mlp:  system 0.0176 to 0.0181; float8 0.876; bfloat16 0.0106.
#   embedding (the tied table, both uses, with final_norm): system 0.0163 to
#     0.0169; float8 0.838; bfloat16 0.0095.
# What NO row refuses is a token's decay ALONE in bfloat16, nor the state
# between chunks: the decay reads ssm 2.0e-4, attention 1.1e-4, gated_mlp
# 1.5e-4, embedding 1.9e-4 (on seeded weights four fifths of the (chunk, head)
# pairs decay to under a thousandth within a chunk, so a rounding of 2^-9 a
# token has a few tokens to compound over), the state 5.7e-7 in every group —
# a fiftieth and a ten-thousandth of what the bfloat16 operands already do to
# the same gradients, under any limit the system passes.  That the cumulative
# sums, their exponentials and the states between chunks are float32 is held
# by their types (tests/test_granite.py
# ::test_the_scans_decays_and_states_are_float32_under_bfloat16_operands) and
# by the float32 comparisons of tests/test_granite.py to 1e-4, not by this
# comparison.
GRAD_RTOL = {"ssm": 1e-1, "attention": 1e-1, "gated_mlp": 1e-1,
             "embedding": 1e-1}
# | ||g_system|| / ||g_reference|| - 1 | over all parameters: only the error's
# component along the gradient.  System 8.96e-4 to 9.54e-4 (a steady -0.09 %:
# the logits stored in bfloat16), ten times under the limit; float8
# 0.563 (small cotangents underflow its 4-bit exponent); bfloat16 operands
# 2.8e-7.
GRAD_NORM_RTOL = 1e-2

STATE_ROUNDED_EVERY = 256     # tokens, `state_every`'s default: the chunk


def recurrence(x, dt, A, B, C, D, decay_dtype=None, state_dtype=None,
               state_every=STATE_ROUNDED_EVERY):
    """`y_t = h_t C_t + D x_t` with `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x)
    B_t` from `h_0 = 0`, one step a token.  `x` (batch, seq, heads, head_dim),
    `dt` (batch, seq, heads), `A`, `D` (heads,), `B`, `C` (batch, seq, groups,
    state); a head reads its group's B and C, which are never repeated: the
    state is (batch, groups, heads per group, head_dim, state)."""
    batch, seq, heads, head_dim = x.shape
    groups, state = B.shape[2:]
    per_group = heads // groups
    by_group = (batch, groups, per_group)
    A, D = A.reshape(groups, per_group), D.reshape(groups, per_group, 1)

    def token(carry, inputs):
        h, index = carry
        x_t, dt_t, B_t, C_t = inputs      # (batch, heads, ...), (batch, g, n)
        x_t, dt_t = x_t.reshape(*by_group, head_dim), dt_t.reshape(by_group)
        decay = _round(jnp.exp(dt_t * A), decay_dtype)
        h = decay[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, None, :]
        if state_dtype is not None:
            h = jnp.where((index + 1) % state_every == 0,
                          _round(h, state_dtype), h)
        y_t = (h * C_t[:, :, None, None, :]).sum(-1) + D * x_t
        return (h, index + 1), y_t.reshape(batch, heads, head_dim)

    @jax.checkpoint
    def block(carry, inputs):
        return jax.lax.scan(token, carry, inputs)

    span = RECURRENCE_BLOCK if seq % RECURRENCE_BLOCK == 0 else seq
    by_time = [t.swapaxes(0, 1).reshape(seq // span, span, *t.shape[:1],
                                        *t.shape[2:])
               for t in (x, dt, B, C)]
    start = (jnp.zeros((*by_group, head_dim, state), jnp.float32),
             jnp.int32(0))
    _, y = jax.lax.scan(block, start, tuple(by_time))
    return y.reshape(seq, batch, heads, head_dim).swapaxes(0, 1)


def mamba2(u, p, *, head_dim, state, norm_eps, operand_dtype=None,
           decay_dtype=None, state_dtype=None,
           state_every=STATE_ROUNDED_EVERY):
    """The Mamba-2 mixer on the normed input `u` (batch, seq, hidden), with
    the heads and groups that `p` holds."""
    batch, seq, _ = u.shape
    heads = p["A_log"].shape[0]
    inner = heads * head_dim
    bc = (p["conv_kernel"].shape[1] - inner) // 2
    groups = bc // state
    z, xbc, dt = jnp.split(_mm(u, _f32(p["in_proj_kernel"]), operand_dtype),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    taps = _f32(p["conv_kernel"])
    padded = jnp.pad(xbc, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    xbc = _f32(p["conv_bias"]) + sum(taps[k] * padded[:, k:k + seq]
                                     for k in range(taps.shape[0]))
    xbc = _round(jax.nn.silu(xbc), operand_dtype)
    x, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
    y = recurrence(
        x.reshape(batch, seq, heads, head_dim),
        jax.nn.softplus(dt + _f32(p["dt_bias"])), -jnp.exp(_f32(p["A_log"])),
        B.reshape(batch, seq, groups, state),
        C.reshape(batch, seq, groups, state), _f32(p["D"]), decay_dtype,
        state_dtype, state_every)
    gated = (y.reshape(batch, seq, inner) * jax.nn.silu(z)).reshape(
        batch, seq, groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + norm_eps)
    return _mm(normed.reshape(batch, seq, inner) * _f32(p["norm_scale"]),
               _f32(p["out_proj_kernel"]), operand_dtype)


def attention_layer(u, p, *, attention_multiplier, operand_dtype=None):
    """Causal softmax attention of `attention_multiplier * q k^T` without a
    position embedding; `p` holds `q_kernel` (hidden, query heads, e),
    `kv_kernel` (hidden, 2, key/value heads, e), `o_kernel` (query heads, e,
    hidden)."""
    batch, seq, hidden = u.shape
    w_q, w_kv, w_o = (_f32(p[n]) for n in ("q_kernel", "kv_kernel",
                                            "o_kernel"))
    width = w_q.shape[2]

    def per_head(w):
        return _mm(u, w.reshape(hidden, -1), operand_dtype).reshape(
            batch, seq, -1, width).transpose(0, 2, 1, 3)

    q, k, v = (_round(per_head(w), operand_dtype)
               for w in (w_q, w_kv[:, 0], w_kv[:, 1]))
    out = band_attention(q, k, v, sm_scale=attention_multiplier)
    return _mm(out.transpose(0, 2, 1, 3).reshape(batch, seq, -1),
               w_o.reshape(-1, hidden), operand_dtype)


def mixer(u, p, kind, *, ssm_head_dim, ssm_state, norm_eps,
          attention_multiplier, operand_dtype=None, decay_dtype=None,
          state_dtype=None, state_every=STATE_ROUNDED_EVERY):
    """What the mixer of `kind` with the parameters `p` makes of `u`."""
    if kind == "ssm":
        return mamba2(u, p, head_dim=ssm_head_dim, state=ssm_state,
                      norm_eps=norm_eps, operand_dtype=operand_dtype,
                      decay_dtype=decay_dtype, state_dtype=state_dtype,
                      state_every=state_every)
    if kind == "attention":
        return attention_layer(u, p, attention_multiplier=attention_multiplier,
                               operand_dtype=operand_dtype)
    return gated_mlp(u, *(p[n]["kernel"] for n in ("gate", "up", "down")),
                     operand_dtype)


def layer(x, p, kind, *, residual_multiplier, norm_eps, **config):
    """One pattern entry of `kind`: `x + m_r * Mixer(N(x))`."""
    u = rms_norm(x, _f32(p["norm"]["scale"]), norm_eps)
    return x + residual_multiplier * mixer(u, p["mixer"], kind,
                                           norm_eps=norm_eps, **config)


def forward(params, tokens, *, layers, embedding_multiplier, **config):
    """The final normed hidden states; `layers`: the kinds in order;
    `config`: `layer`'s other keywords."""
    x = embedding_multiplier * _f32(params["embed"]["embedding"])[tokens]
    for index, kind in enumerate(layers):
        x = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, kind, **config))(
                x, params[f"layer_{index}"])
    return rms_norm(x, _f32(params["final_norm"]["scale"]),
                    config["norm_eps"])


def tied_cross_entropy(hidden, table, targets, logits_scaling,
                       operand_dtype=None, block: int = 512):
    """Mean next-token cross-entropy of `(hidden table^T) / logits_scaling`,
    `table` (vocab, hidden) the embedding's own, a block of tokens at a time
    so that the float32 logits never exist all at once."""
    hidden = hidden.reshape(-1, hidden.shape[-1])
    targets = targets.reshape(-1)

    @jax.checkpoint
    def block_sum(h, t):
        logp = jax.nn.log_softmax(
            _mm(h, table.T, operand_dtype) / logits_scaling, axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1).sum()

    if hidden.shape[0] % block:
        block = hidden.shape[0]
    blocks = hidden.shape[0] // block
    total, _ = jax.lax.scan(
        lambda total, ht: (total + block_sum(*ht), None), jnp.float32(0.0),
        (hidden.reshape(blocks, block, -1), targets.reshape(blocks, block)))
    return total / hidden.shape[0]


def loss(params, batch, *, logits_scaling, **config):
    """Mean next-token cross-entropy; `batch` is (inputs, targets).  The tree
    has no head of its own: the table is read twice."""
    inputs, targets = batch
    return tied_cross_entropy(
        forward(params, inputs, **config),
        _f32(params["embed"]["embedding"]), targets, logits_scaling,
        config.get("operand_dtype"))
