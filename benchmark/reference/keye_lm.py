"""Plain float32 reference of the language model the repository runs for
Keye-VL-2.0-30B-A3B (`models.TransformerLM(layers=("selected_attention",
"experts") * depth, indexer=IndexerConfig(16, 64, 2048))` with
`models.next_token_loss + models.indexer_loss`), in `jax.numpy` with no kernel,
no tile, no bisection, no sorting of rows into expert buffers, no mixed
precision and no framework.

`h_0 = Embed(ids)`; every published layer is `h <- h + Attn(RMSNorm(h))`, `h
<- h + Experts(RMSNorm(h))` (pre-norm, no biases); then RMSNorm and the untied
head.  On the normed input `u` of `S` positions, `t` a query and `s` a key:

1. Indexer, on `ud = stop_gradient(u)`: `qI = rope(ud W_qI)` as 16 heads of
   64; `kI = rope(LayerNorm(ud W_kI))`, one head of 64 (scale and bias);
   `w = ud W_w * 16^-1/2 * 64^-1/2`; `I[t, s] = sum_j w[t, j] relu(qI[t, j] .
   kI[s])`.
2. Selection: `n_t = min(t + 1, topk)`; `tau[t]` the `n_t`-th largest of
   `I[t, :t + 1]` BY A SORT; `M[t, s] = (s <= t) and (I[t, s] >= tau[t])`.  No
   gradient passes through `M`.
3. Attention: `q_h = RMSNorm_e(u W_q)_h`, `k_g = RMSNorm_e(u W_k)_g` (one
   learned scale of the head's width for q, one for k), `v_g = (u W_v)_g`;
   both turned (adjacent pairs, base `rope_theta`, the whole head); query
   head h reads key/value head `h // (H / H_kv)`, WRITTEN AS AN INDEX (no
   repeat); `softmax over M[t]` at `e^-1/2`, the same `M` for every head;
   `W_o`.
4. Losses: the mean next-token cross-entropy, and a layer `mean_t KL(p[t] ||
   softmax over M[t] of I[t])` with `p[t, s] = stop_gradient((1 / H) sum_h
   P_h[t, s])`, summed over the layers.  By the two stop-gradients the
   indexer's parameters take their gradient from the second term only and
   every other parameter from the first only; gradients by autodiff.

`Experts`: `p = softmax_float32(u W_r)` over all experts; the k largest are
chosen; `g_e = p_e / sum_chosen p` (`norm_topk_prob`); `sum_chosen g_e
W_down,e (silu(W_gate,e u) * W_up,e u)`, a loop over THIS SHARD's experts,
each applied to every row and masked by its weight.

The shares (`expert_shard`, a sliced vocabulary) are what the system computes;
with the whole layer's parameters it is the whole layer (tests/test_keye.py
adds the shares up to it).  Departures and the readings the config does not
settle are in benchmark/configs/keyevl2.json under `departures` and `assumed`.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.  Everything between a
layer's projections and its attention output is computed a block of
QUERY_BLOCK query rows at a time, a head at a time inside it, under
`jax.checkpoint`: a block's scores, its sort and its heads' probabilities
exist once (this runs beside 10.5 GB of training state on a 16 GB chip; one
`[S, S]` float32 array is 268 MB at 8,192 positions); it changes no
arithmetic.  `operand_dtype` rounds every matmul operand, the indexer's
`qI`, `kI` and the q, k, v the attention reads to a narrower type's mantissa
(`ouro_lm._round`: values rounded, the gradient as through no rounding).

Tolerances (used by benchmark/builders/keye_lm.py), each with its reason: the
system computes in bfloat16 with float32 accumulation, stores the logits in
bfloat16, routes on bfloat16 activations and SELECTS on scores of bfloat16
operands — a score within rounding of `tau` flips a key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.moe_lm import _f32, rms_norm
from benchmark.reference.ouro_lm import _mm, _round
from benchmark.reference.sdar_lm import rotary

# Each limit from two readings on the chip at the published widths and the
# timed sizes (PERF.md section 6, PR 56; my chip runs), between them with room
# on both sides: the largest the SYSTEM gave over its seeds (5600000101 to
# ...107 and, from the final tree, ...301 to ...307 and ...311 to ...316:
# twenty), and what the two controls give on
# seeds 5600000201 and ...202 — FLOAT8: this reference against itself with
# every matmul operand, q_I, k_I and the q, k, v the attention reads at
# float8_e4m3's mantissa, the nearest precision under the configuration's
# bfloat16; OFF: the system built with `topk` at the sequence length (every
# earlier key kept: a parameter of the model) against this reference at the
# configuration's `topk`.  Each control has to be refused by one row at least;
# five rows refuse each.  The system's readings hardly move with the seed
# (the body's gradient row within 7 %, the indexers' within 40 %), so a limit
# at about twice the largest leaves a fresh seed room.
#
# |loss_system - loss_reference| / loss_reference, the next-token term alone:
# a mean over 8,192 tokens of log-softmaxes over 18,992 rounded logits.
# System 3.7e-7 to 4.3e-5.  The accepted expert cells' limit leaves the
# largest reading six times of room, so it is theirs.  Neither control is
# told apart by it (FLOAT8 1.8e-5 and 5.9e-5, OFF 7.6e-5 and 1.2e-4): a seeded
# model's loss is log(vocabulary) whatever its attention sees.
LOSS_RTOL = 2.5e-4
# The same for the indexers' term, the layers' KL summed (0.61 here): a mean
# over 8,192 rows a layer of float32 arithmetic on bfloat16 scores and
# probabilities.  System 4.1e-6 to 1.25e-4; FLOAT8 2.3e-3 and 2.6e-3; OFF 1.0
# (no layer selects, the term is 0).
KL_RTOL = 6e-4
# ||g_system - g_reference|| / ||g_reference|| over every parameter but the
# indexers': the cross-entropy's gradient alone.  Every matmul operand is
# rounded to bfloat16 forward and backward, 0.8 % of the routed pairs and
# 0.5 % of the selected ones fall the other way and each swaps a term.
# System 0.0269 to 0.0290; FLOAT8 0.0924 and 0.0932; OFF 0.117 and 0.119.
GRAD_RTOL = 5.5e-2
# The same over the indexers' parameters, whose gradient is the KL terms'
# alone: the closed form (softmax(I) - p) / S through the score product's
# backward kernels against autodiff through the dense scores.  System 0.0111
# to 0.0169; FLOAT8 0.0760 and 0.0761; OFF 1.0 (no gradient at all).
INDEXER_GRAD_RTOL = 3.2e-2
# | ||g_system|| / ||g_reference|| - 1 | over all parameters: only the
# error's component along the gradient.  System 6.3e-6 to 4.8e-4; OFF 0.0526
# and 0.0527 (the indexers' third of the squared norm is gone); FLOAT8 3.6e-4
# and 1.8e-3, inside the system's range: rounding stands at right angles to
# the gradient, and the rows above are what refuses it.
GRAD_NORM_RTOL = 5e-3
# Share of (row, choice) pairs, over the expert layers, whose expert the
# float32 reference did not choose for that row: near-ties between the 8th and
# 9th of 128 probabilities that a bfloat16 rounding of the activations flips.
# System 0.0074 to 0.0084 (Trinity's 0.006); FLOAT8 0.0448 and 0.0457; OFF
# 0.0383 and 0.0389 (another attention output, other near-ties).
ROUTING_MISMATCH_MAX = 1.8e-2
# Share of the (query, key) pairs the system's layers selected, in the rows
# past `topk`, that the float32 reference's sort did not: a score of bfloat16
# operands within rounding of its row's threshold.  System 0.00497 to 0.00528
# (10 of a row's 2,048 keys); FLOAT8 0.0355 and 0.0357; OFF selects nothing
# and reads 0.  A selection that ignored the scores would read 0.75.
SELECTION_MISMATCH_MAX = 1.3e-2

QUERY_BLOCK = 512        # query rows whose scores exist at once


def layer_norm(x, scale, bias, eps):
    x = x - x.mean(axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale + bias


def router(u, w_router, experts_per_token, operand_dtype=None):
    """(weights (rows, k), experts (rows, k)): the k largest probabilities of
    the softmax over all experts, divided by their sum."""
    probs = jax.nn.softmax(_mm(u, w_router, operand_dtype), axis=-1)
    chosen, experts = jax.lax.top_k(probs, experts_per_token)
    return chosen / (chosen.sum(-1, keepdims=True) + 1e-20), experts


def experts_of_shard(u, p, weights, experts, first_expert, operand_dtype=None):
    """sum over this shard's experts of weight_e * expert_e(u), every expert
    applied to every row; `u` (rows, hidden)."""

    @jax.checkpoint
    def one(u, gate, up, down, weight):
        h = jax.nn.silu(_mm(u, gate, operand_dtype)) \
            * _mm(u, up, operand_dtype)
        return weight[:, None] * _mm(h, down, operand_dtype)

    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.where(experts == first_expert + e, weights, 0.0).sum(-1)
        return out + one(u, _f32(gate), _f32(up), _f32(down), weight), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.arange(p["gate_kernel"].shape[0]), p["gate_kernel"],
         p["up_kernel"], p["down_kernel"]))
    return out


def cross_entropy(hidden, w_head, targets, operand_dtype=None,
                  block: int = 512):
    """Mean next-token cross-entropy of the untied head, a block of tokens at
    a time so that the float32 logits never exist all at once."""
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


def selection(scores, rows, topk):
    """`M` of step 2 for the query rows `rows` (n,) of `scores` (n, S):
    causal, and at least the row's `min(t + 1, topk)`-th largest score."""
    causal = jnp.arange(scores.shape[-1])[None, :] <= rows[:, None]
    ordered = -jnp.sort(-jnp.where(causal, scores, -jnp.inf), axis=-1)
    nth = jnp.minimum(rows + 1, topk) - 1
    tau = jnp.take_along_axis(ordered, nth[:, None], axis=-1)
    return causal & (scores >= tau)


def selected_attention(q, k, v, q_i, k_i, w, system_selection, *, topk):
    """Steps 1's product to 4's term for ONE sequence: `q` (H, S, e), `k`,
    `v` (H_kv, S, e), `q_i` (H_I, S, e_I), `k_i` (S, e_I), `w` (S, H_I),
    `system_selection` (S, S) int8.
    Returns (out (H, S, e), the sum over rows of KL(p || softmax_M I), pairs
    the system's selection (S, S) keeps and this one does not, pairs this
    one keeps in the rows past `topk`, `M` as int8 (S, S))."""
    heads, seq, width = q.shape
    group = heads // k.shape[0]
    step = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    scale = width ** -0.5

    @jax.checkpoint
    def some_rows(start, q_rows, q_i_rows, w_rows, theirs):
        rows = start + jnp.arange(step)
        scores = sum(
            w_rows[:, j, None] * jax.nn.relu(q_i_rows[j] @ k_i.T)
            for j in range(q_i.shape[0]))
        kept = selection(jax.lax.stop_gradient(scores), rows, topk)

        @jax.checkpoint
        def head(total, h_and_q):
            h, q_h = h_and_q
            logits = jnp.where(kept, (q_h @ k[h // group].T) * scale,
                               -jnp.inf)
            p = jax.nn.softmax(logits, axis=-1)
            return total + jax.lax.stop_gradient(p), p @ v[h // group]

        total, outs = jax.lax.scan(head, jnp.zeros_like(scores),
                                   (jnp.arange(heads), q_rows))
        target = total / heads
        log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
        kl = jnp.where(target > 0.0, target * (
            jnp.log(jnp.where(target > 0.0, target, 1.0))
            - jnp.where(kept, log_q, 0.0)), 0.0).sum()
        past = (rows >= topk)[:, None]
        return (outs, kl, ((theirs != 0) & ~kept & past).sum(),
                (kept & past).sum(), kept.astype(jnp.int8))

    blocks = seq // step
    out, kl, differ, kept, chosen = jax.lax.map(
        lambda xs: some_rows(*xs),
        (jnp.arange(0, seq, step),
         q.reshape(heads, blocks, step, width).swapaxes(0, 1),
         q_i.reshape(q_i.shape[0], blocks, step, -1).swapaxes(0, 1),
         w.reshape(blocks, step, -1),
         system_selection.reshape(blocks, step, seq)))
    return (out.swapaxes(0, 1).reshape(heads, seq, width), kl.sum(),
            differ.sum(), kept.sum(), chosen.reshape(seq, seq))


def attention_layer(u, p, *, topk, rope_theta, norm_eps, operand_dtype=None,
                    system_selection=None):
    """(output (batch, S, hidden), mean over rows of the KL term, pairs the
    system selected that this did not, pairs selected in the rows past
    `topk`, the selection (batch, S, S) int8) on the normed input `u`; `p`
    the system's `Attention(indexer=)` parameters."""
    batch, seq, hidden = u.shape
    w_q, w_kv, w_o = (_f32(p[n]) for n in ("q_kernel", "kv_kernel",
                                           "o_kernel"))
    width = w_q.shape[2]
    positions = jnp.arange(seq)

    def per_head(x, w, heads_width):
        return _mm(x, w.reshape(hidden, -1), operand_dtype).reshape(
            batch, seq, -1, heads_width).transpose(0, 2, 1, 3)

    q = rms_norm(per_head(u, w_q, width), _f32(p["q_head_norm_scale"]),
                 norm_eps)
    k = rms_norm(per_head(u, w_kv[:, 0], width),
                 _f32(p["k_head_norm_scale"]), norm_eps)
    v = per_head(u, w_kv[:, 1], width)
    q, k = rotary(q, positions, rope_theta), rotary(k, positions, rope_theta)
    ud = jax.lax.stop_gradient(u)
    w_qi = _f32(p["index_q_kernel"])
    e_i = w_qi.shape[2]
    q_i = rotary(per_head(ud, w_qi, e_i), positions, rope_theta)
    k_i = rotary(layer_norm(
        _mm(ud, _f32(p["index_k_kernel"]), operand_dtype),
        _f32(p["index_k_norm_scale"]), _f32(p["index_k_norm_bias"]),
        norm_eps), positions, rope_theta)
    w = _mm(ud, _f32(p["index_w_kernel"]), operand_dtype) \
        * (w_qi.shape[1] ** -0.5 * e_i ** -0.5)
    q, k, v, q_i, k_i = (_round(t, operand_dtype)
                         for t in (q, k, v, q_i, k_i))
    if system_selection is None:
        system_selection = jnp.zeros((batch, seq, seq), jnp.int8)
    out, kl, differ, kept, chosen = jax.vmap(
        lambda *a: selected_attention(*a, topk=topk))(
        q, k, v, q_i, k_i, w, system_selection)
    out = _mm(out.transpose(0, 2, 1, 3).reshape(batch, seq, -1),
              w_o.reshape(-1, hidden), operand_dtype)
    return out, kl.sum() / (batch * seq), differ.sum(), kept.sum(), chosen


def layer(x, p_attention, p_experts, system_selection, *, topk, rope_theta,
          norm_eps, num_experts, experts_per_token, expert_shard,
          operand_dtype=None):
    """(x out, the layer's KL term, selection pairs that differ, selected
    pairs past `topk`, chosen experts, the selection) of one published layer:
    two pattern entries of the system's tree."""
    u = rms_norm(x, _f32(p_attention["norm"]["scale"]), norm_eps)
    out, kl, differ, kept, selected = attention_layer(
        u, p_attention["mixer"], topk=topk, rope_theta=rope_theta,
        norm_eps=norm_eps, operand_dtype=operand_dtype,
        system_selection=system_selection)
    x = x + out
    u = rms_norm(x, _f32(p_experts["norm"]["scale"]), norm_eps).reshape(
        -1, x.shape[-1])
    m = p_experts["mixer"]
    weights, experts = router(u, _f32(m["router_kernel"]),
                              experts_per_token, operand_dtype)
    first = expert_shard[0] * (num_experts // expert_shard[1])
    mixed = experts_of_shard(u, m, weights, experts, first, operand_dtype)
    return x + mixed.reshape(x.shape), kl, differ, kept, experts, selected


def loss_terms(params, batch, system_selections=None, **config):
    """(next-token cross-entropy, the layers' KL terms summed, the share of
    the pairs the system selected past `topk` that this reference did not,
    the experts routed to: (layers, rows, k), this reference's selections:
    (layers, batch, S, S) int8); `batch` is (inputs, targets),
    `system_selections` shaped as the last, or None."""
    inputs, targets = batch
    x = _f32(params["embed"]["embedding"])[inputs]
    kls, differ, kept, chosen, selected, index = [], 0, 0, [], [], 0
    while f"layer_{2 * index}" in params:
        theirs = None if system_selections is None \
            else system_selections[index]
        x, kl, d, n, experts, kept_here = jax.checkpoint(
            lambda x, p_a, p_e, s: layer(x, p_a, p_e, s, **config))(
                x, params[f"layer_{2 * index}"],
                params[f"layer_{2 * index + 1}"], theirs)
        kls.append(kl)
        differ, kept = differ + d, kept + n
        chosen.append(experts)
        selected.append(kept_here)
        index += 1
    hidden = rms_norm(x, _f32(params["final_norm"]["scale"]),
                      config["norm_eps"])
    xent = cross_entropy(hidden, _f32(params["lm_head_kernel"]), targets,
                         config.get("operand_dtype"))
    return (xent, sum(kls), differ / jnp.maximum(kept, 1), jnp.stack(chosen),
            jnp.stack(selected))


def loss(params, batch, **config):
    xent, kl, *_ = loss_terms(params, batch, **config)
    return xent + kl
