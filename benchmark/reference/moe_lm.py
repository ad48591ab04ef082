"""Plain float32 reference of the sparse-expert decoder the repository runs
for OLMoE (`models.TransformerLM(moe=..., qk_norm=True)`), in `jax.numpy`
with no kernel, no sorting, no grouped matmul, no mixed precision and no
framework.  The layer, as Hugging Face's `modeling_olmoe.py` has it:

    q = RMSNorm(x W_q), k = RMSNorm(x W_k)   (one learned scale over the whole
    hidden-wide projection, before the split into heads), v = x W_v, rotary
    on the whole head, causal softmax attention, W_o;
    x <- x + attn(RMSNorm(x));  x <- x + moe(RMSNorm(x));
    p = softmax_float32(x W_r) over all experts; the k largest p are the
    weights, NOT renormalised;
    moe(x) = sum_{e in top-k} p_e * W_down,e (silu(W_gate,e x) * W_up,e x)

and the training loss of the OLMoE paper (arXiv:2409.02060): cross-entropy
+ 0.01 * load balancing (E * sum_e f_e P_e over all layers' tokens, f_e the
share of (token, choice) pairs that chose e, P_e the mean router probability)
+ 0.001 * router z-loss (mean over tokens of logsumexp(x W_r)^2).

The experts are a loop over THIS SHARD's experts (`expert_shard=(i, n)`:
experts [i*E/n, (i+1)*E/n)), each applied to every token and masked by its
weight: the chip's share of an expert-parallel deployment, the same share the
system computes.  With (0, 1) it is the whole model.

Departures from `modeling_olmoe.py`, each of which the system shares:
* rotary pairs adjacent columns (x[2i], x[2i+1]) where Hugging Face pairs
  column i with i + head/2: the same function up to a fixed permutation of
  the q/k projections' columns and of the two norms' scales;
* the load-balancing loss is normalised so that a uniform router gives 1
  (Hugging Face's `load_balancing_loss_func` sums the k slots and gives k);
* nothing else: no biases, `clip_qkv` null, RMSNorm eps as configured.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a
layer, an expert, a block of query rows and a block of the head only bounds
what autodiff keeps (this runs beside 8 GB of training state on a 16 GB chip);
it changes no arithmetic; nor does `lax.scan` over the shard's experts and
over the head's blocks, which keeps the traced program and its compile small
(a cold run of the cell compiles for 161 s in all on the chip's host, 283 s
with the loops unrolled, and the executables are half the size: PERF.md
section 6, PR 26).  `operand_dtype` rounds every matmul operand to a
narrower type and back: how the tolerances below were shown to refuse the
next precision under bfloat16 (float8_e4m3fn).

Tolerances (used by benchmark/builders/moe_lm.py), each with its reason:
the system computes in bfloat16 with float32 accumulation, stores the logits
in bfloat16, and ROUTES on bfloat16 activations — a token whose k-th and
(k+1)-th router probabilities are closer than the rounding picks another
expert than the reference, which changes that token's output by one whole
expert term, so gradient errors are larger than a dense model's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.dense_lm import attention as _attention_blocks
from benchmark.reference.dense_lm import rotary

LOAD_BALANCE_COEF = 0.01
ROUTER_Z_COEF = 0.001

# Each limit from two readings on the chip at the published widths (PERF.md
# section 6, PR 26): the largest the system gave over 13 seeds (2600000107 to
# ...119), and what THIS reference gives against itself with every matmul
# operand, forward and backward, rounded to float8_e4m3fn — the nearest
# precision under the configuration's bfloat16 (seeds 2600000120, ...121) —
# which has to be refused.
#
# |loss_system - loss_reference| / loss_reference: a mean over 4,096 tokens
# of a log-softmax over 50k rounded logits, plus two router terms that are
# sums over every token; the roundings average out.  System 3.4e-7 to
# 1.8e-5; six times that.  The loss alone does not tell float8 from bfloat16
# (4.5e-5 and 8.0e-5: under this limit); the gradient rows below do.
LOSS_RTOL = 1e-4
# ||g_system - g_reference|| / ||g_reference|| over all parameters: every
# operand rounded to bfloat16 through four layers, and the 0.4 % of pairs
# that route elsewhere each swap one whole expert term.  System 0.0103 to
# 0.0115 (the dense LM reads 0.006 to 0.009); float8 0.950.
GRAD_RTOL = 2.5e-2
# | ||g_system|| / ||g_reference|| - 1 |: only the error's component along
# the gradient.  System 1.4e-5 to 7.7e-5; float8 0.684 (small cotangents
# underflow its 4-bit exponent).
GRAD_NORM_RTOL = 5e-4
# Share of (token, choice) pairs, over all layers, whose expert the float32
# reference did not choose for that token: near-ties between the 8th and 9th
# router probability that a bfloat16 rounding of the activations flips.
# System 0.0038 to 0.0046; float8 0.0251 and 0.0255.
ROUTING_MISMATCH_MAX = 1.2e-2


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _mm(a, b, operand_dtype):
    if operand_dtype is not None:
        a, b = (_f32(t.astype(operand_dtype)) for t in (a, b))
    return a @ b


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def attention(q, k, v, block: int = 512):
    """dense_lm's causal attention, one block of query rows at a time; the
    probabilities of a block are recomputed in the backward pass."""
    return jax.checkpoint(
        lambda q, k, v: _attention_blocks(q, k, v, block))(q, k, v)


def router(x, w_router, experts_per_token: int, operand_dtype=None):
    """(router logits, weights (tokens, k), experts (tokens, k))."""
    logits = _mm(x, w_router, operand_dtype)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     experts_per_token)
    return logits, weights, experts


def experts_of_shard(x, p, weights, experts, first_expert: int,
                     operand_dtype=None):
    """sum over this shard's experts of weight_e * expert_e(x), every expert
    applied to every token; `x` (tokens, hidden)."""

    @jax.checkpoint
    def one(x, gate, up, down, weight):
        h = jax.nn.silu(_mm(x, gate, operand_dtype)) \
            * _mm(x, up, operand_dtype)
        return weight[:, None] * _mm(h, down, operand_dtype)

    # One traced body for all of the shard's experts, in order.
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.where(experts == first_expert + e, weights, 0.0).sum(-1)
        return out + one(x, _f32(gate), _f32(up), _f32(down), weight), None

    local = p["gate_kernel"].shape[0]
    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(local), p["gate_kernel"], p["up_kernel"],
         p["down_kernel"]))
    return out


def layer(x, p, *, num_experts, experts_per_token, expert_shard, norm_eps,
          operand_dtype=None):
    """One block: (x out, router logits, chosen experts)."""
    batch, seq, hidden = x.shape
    h = rms_norm(x, _f32(p["attn_norm"]["scale"]), norm_eps)
    w_qkv = _f32(p["attn"]["qkv_kernel"])            # (d, 3, heads, head)
    heads, head = w_qkv.shape[2:]
    q, k, v = (_mm(h, w_qkv[:, j].reshape(hidden, hidden), operand_dtype)
               for j in range(3))
    q = rms_norm(q, _f32(p["attn"]["q_norm_scale"]).reshape(-1), norm_eps)
    k = rms_norm(k, _f32(p["attn"]["k_norm_scale"]).reshape(-1), norm_eps)
    q, k, v = (t.reshape(batch, seq, heads, head).transpose(0, 2, 1, 3)
               for t in (q, k, v))
    out = attention(rotary(q), rotary(k), v)
    out = out.transpose(0, 2, 1, 3).reshape(batch, seq, hidden)
    x = x + _mm(out, _f32(p["attn"]["o_kernel"]).reshape(hidden, hidden),
                operand_dtype)
    h = rms_norm(x, _f32(p["mlp_norm"]["scale"]), norm_eps).reshape(
        -1, hidden)
    logits, weights, experts = router(h, _f32(p["moe"]["router_kernel"]),
                                      experts_per_token, operand_dtype)
    first = expert_shard[0] * (num_experts // expert_shard[1])
    mixed = experts_of_shard(h, p["moe"], weights, experts, first,
                             operand_dtype)
    return x + mixed.reshape(x.shape), logits, experts


def forward(params, tokens, **config):
    """(final hidden states, [router logits per layer], [chosen experts per
    layer]); `config`: num_experts, experts_per_token, expert_shard,
    norm_eps[, operand_dtype]."""
    x = _f32(params["embed"]["embedding"])[tokens]
    all_logits, all_experts = [], []
    index = 0
    while f"layer_{index}" in params:
        x, logits, experts = jax.checkpoint(
            lambda x, p: layer(x, p, **config))(x, params[f"layer_{index}"])
        all_logits.append(logits)
        all_experts.append(experts)
        index += 1
    x = rms_norm(x, _f32(params["final_norm"]["scale"]), config["norm_eps"])
    return x, all_logits, all_experts


def cross_entropy(hidden, w_head, targets, operand_dtype=None,
                  block: int = 512):
    """Mean next-token cross-entropy of the untied head, a block of tokens
    at a time so that the float32 logits never exist all at once."""
    hidden = hidden.reshape(-1, hidden.shape[-1])
    targets = targets.reshape(-1)

    @jax.checkpoint
    def block_sum(h, t):
        logp = jax.nn.log_softmax(_mm(h, w_head, operand_dtype), axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1).sum()

    if hidden.shape[0] % block:
        block = hidden.shape[0]
    blocks = hidden.shape[0] // block
    total, _ = jax.lax.scan(
        lambda total, ht: (total + block_sum(*ht), None), jnp.float32(0.0),
        (hidden.reshape(blocks, block, -1), targets.reshape(blocks, block)))
    return total / hidden.shape[0]


def router_losses(all_logits, all_experts, num_experts: int):
    """(load balancing, z-loss) over all layers' tokens together."""
    logits = jnp.concatenate(all_logits)             # (layers * tokens, E)
    experts = jnp.concatenate(all_experts)           # (layers * tokens, k)
    chosen = jax.nn.one_hot(experts, num_experts).sum(axis=1)
    share = chosen.sum(axis=0) / chosen.sum()
    mean_prob = jax.nn.softmax(logits, axis=-1).mean(axis=0)
    balance = num_experts * (share * mean_prob).sum()
    z = jnp.square(jax.nn.logsumexp(logits, axis=-1)).mean()
    return balance, z


def loss_terms(params, batch, **config):
    """(cross-entropy, load-balancing loss, router z-loss)."""
    inputs, targets = batch
    hidden, all_logits, all_experts = forward(params, inputs, **config)
    xent = cross_entropy(hidden, _f32(params["lm_head_kernel"]), targets,
                         config.get("operand_dtype"))
    return (xent,) + router_losses(all_logits, all_experts,
                                   config["num_experts"])


def loss(params, batch, **config):
    xent, balance, z = loss_terms(params, batch, **config)
    return xent + LOAD_BALANCE_COEF * balance + ROUTER_Z_COEF * z


def chosen_experts(params, tokens, **config):
    """(layers, tokens, k): the experts the reference routes each token to."""
    return jnp.stack(forward(params, tokens, **config)[2])
