"""Plain float32 reference of the decoder the repository runs for
JoyAI-LLM-Flash (`models.TransformerLM(layers=..., mtp=...)` with the kinds
`latent_attention`, `gated_mlp` and `experts`; DeepSeek-V3's layers,
arXiv:2412.19437, key for key), in `jax.numpy` with no kernel, no sorting, no
row buffer, no grouped matmul, no mixed precision and no framework.  Every
pattern entry is `x <- x + Mixer(RMSNorm(x))` (a published layer is two of
them: latent attention, then the dense MLP or the experts), every norm an
RMSNorm with a learned scale, no bias anywhere.  On the normed input `u`, with
the experts the parameter tree holds:

`latent_attention`, 32 heads: `c_q = RMSNorm(u W_qa)`; `[q_nope | q_rope]_h =
    c_q W_qb`; `[c_kv | k_rope] = u W_kva`, `c_kv <- RMSNorm(c_kv)`;
    `[k_nope | v]_h = c_kv W_kvb`; rotary (adjacent pairs, base `rope_theta`,
    no scaling) on `q_rope` and on the ONE `k_rope` every head shares; causal
    softmax at (nope + rope)^-1/2 over whole rows of keys; `concat_h(o_h)
    W_o`.  NO output gate.
`gated_mlp`: `W_down (silu(W_gate u) * W_up u)`.
`experts`: `s = sigmoid(u W_r)`, all experts wide; the k largest of `s + bias`
    are chosen (`lax.top_k`; one group, no group limit); `g_e = scale * s_e /
    sum_chosen s`; `sum_chosen g_e W2_e (silu(W1_e u) * W3_e u)`, a loop over
    THIS SHARD's experts, each applied to every token and masked by its
    weight; plus the shared expert of the same gated form, unweighted.

Then `s_i = RMSNorm_f(x_L)_i`, `logits_i = s_i W_head`, and the
multi-token-prediction module (the report's section 2.2, depth 1), with the
SAME table `E` and the SAME `W_head`:

    h'_i = [RMSNorm_e(E[t_{i+1}]) | RMSNorm_h(s_i)] W_eh
    h''  = one latent-attention entry and one experts entry over h', causal,
           weights of their own (`mtp_0_layer_0`, `mtp_0_layer_1`)
    logits'_i = RMSNorm_f'(h''_i) W_head

    L_main = mean_{i < S-1} CE(logits_i, t_{i+1})
    L_mtp  = mean_{i < S-2} CE(logits'_i, t_{i+2})
    L      = L_main + mtp_weight * L_mtp

All `S` positions go through the module: position `S-1` reads `E[t_0]` as its
"next token" (any row would do: the block is causal, the position reaches no
other and has no target).  `batch` is `(inputs, targets)` as every builder's
is; `targets[:, i]` is `t_{i+1}` and its LAST column is not read.

The shares (`expert_shard`, a sliced vocabulary) are what the system computes:
the experts the parameter tree holds, the router over all experts.  With the
whole layer's parameters it is the whole layer (tests/test_joyai.py adds the
shares up to it).  None of the model's equations is left out; the three
readings the config does not settle (the weight of `L_mtp`, the module's
input, its block's kind) and the selection bias are listed under `assumed` in
benchmark/configs/joyaiflash.json.

It reads the system's own parameter tree (flax names) and imports nothing from
`horovod_tpu`.  The caller traces it under
`jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a pattern
entry, an expert, a block of query rows (one after another: `attention`) and
a block of the head only bounds
what autodiff keeps (this runs beside 8.2 GB of weights and AdamW state and
2.7 GB of the system's gradients on a 16 GB chip); it changes no arithmetic.
`operand_dtype` rounds every matmul operand to a narrower type and back,
`score_dtype` the router's scores before the choice, and `latent_dtype` is
the type the two latents' norms are COMPUTED in (square, mean, root and scale):
how the tolerances below were tried against the next precision under the
configuration's.

Tolerances (used by benchmark/builders/joyai_lm.py), each with its reason: the
system computes in bfloat16 with float32 accumulation, stores the logits in
bfloat16, and ROUTES on bfloat16 activations, 8 of 256 experts a token; the
router's scores, every RMSNorm and the losses are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ling_lm import gated_mlp, rotary
from benchmark.reference.moe_lm import (_f32, _mm, experts_of_shard,
                                        rms_norm)

# Each limit from two readings on the chip at the published widths and the
# timed sizes (my chip runs, PR 66; PERF.md section 6): the largest the system
# gave over its seeds (2166000001, ...003 to ...007, 3200000008, and ...002
# with every entry recomputed), and what THIS reference gives against itself in
# the nearest precision below the configuration's — every matmul operand,
# forward and backward, rounded to float8_e4m3fn (`operand_dtype`, seed
# 2166000021) — which has to be refused; beside them, for the three things the
# issue names, the router's scores rounded to bfloat16 before the choice
# (`score_dtype`), the two latents' norms computed in bfloat16
# (`latent_dtype`) and lambda dropped or doubled (`mtp_weight`), the same seed.
#
# |L_system - L_reference| / L_reference, for L = L_main + lambda L_mtp as the
# STEP's loss function returns it and for L_main and L_mtp apart: each a mean
# over 8,191 / 8,190 tokens of a log-softmax over 16,160 rounded logits; the
# roundings average out.  System 9.4e-7 to 4.0e-5 over the three rows; the
# accepted cells' limit (Ling's, Granite's), six times that.  float8 reads
# 3.6e-5 to 1.2e-4: the loss does not tell it from bfloat16, the rows below
# do.  A lambda dropped or doubled reads 0.230 in the first row, and 0 in the
# other two.
LOSS_RTOL = 2.5e-4
# ||g_system - g_reference|| / ||g_reference|| over all parameters: every
# operand rounded to bfloat16 through six attention blocks, and the 1.0-1.1 %
# of pairs that route elsewhere than in float32 (below) each swap one whole
# expert term.  System 0.0371 to 0.0411 (the reference on bfloat16 operands
# alone 0.0290); float8 0.848; lambda dropped or doubled 0.318.
GRAD_RTOL = 1e-1
# The same over the table and the head alone, the two parameters that take the
# SUM of two uses' gradients: a use left out of either is lambda's 0.287 or
# more here.  System 0.0267 to 0.0294; float8 0.0948.
SHARED_GRAD_RTOL = 6e-2
# | ||g_system|| / ||g_reference|| - 1 |: only the error's component along
# the gradient.  System 1.5e-4 to 2.6e-4; float8 0.468 (small cotangents
# underflow its 4-bit exponent); lambda dropped 0.052, doubled 0.142.
GRAD_NORM_RTOL = 2e-3
# Share of (token, choice) pairs, over the five expert layers (the module's
# among them), whose expert the float32 reference did not choose for that
# token: near-ties between the 8th and 9th of 256 scores that a bfloat16
# rounding of the activations flips.  System 0.0103 to 0.0111 over eight seeds
# (327,680 pairs a reading: the seeds differ by 0.0003); float8 0.0624; the
# router's scores rounded to bfloat16 before the choice, and nothing else,
# 0.0148 — the one row that tells them from float32 scores (their gradient
# row reads 0.0439 beside the system's 0.0411), so the limit stands between
# the two, eight of the seeds' deviations over the system's largest.
ROUTING_MISMATCH_MAX = 1.3e-2
#
# What no row refuses: the two latents' norms COMPUTED in bfloat16 (square,
# mean, root and scale each rounded as it is made) read 0.0100 in the
# gradient row, 0.0069 in the table's and the head's, 1.6e-5 in the norm and
# 0.0009 in the routing — under the 0.029 that bfloat16 matmul operands alone
# read, because the system rounds what those norms return to bfloat16 anyway.


def _rounded(a, dtype):
    """`a` rounded to `dtype`'s exponent and mantissa and kept in float32, by
    `lax.reduce_precision`: a cast there and back is what XLA removes where it
    may keep excess precision (on the chip a control written as two casts
    read 0.0 in every row: my chip run, PR 66)."""
    if dtype is None:
        return a
    kind = jnp.finfo(dtype)
    return jax.lax.reduce_precision(a, kind.nexp, kind.nmant)


def attention(q, k, v, block: int = 256):
    """Causal softmax attention, q and k (batch, heads, seq, d_qk), v (...,
    d_v): a plain softmax over whole rows of keys at d_qk^-1/2.  One block of
    query rows at a time, the blocks one after another (`lax.map`) and a
    block's probabilities computed again in the backward pass: blocks
    scheduled side by side would not fit beside the training state (a head's
    row against 8,192 keys is 32 KB in float32)."""
    batch, heads, seq, width = q.shape
    block = block if seq % block == 0 else seq
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def rows(q_block, start):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_block, k) * width ** -0.5
        place = start + jnp.arange(block)
        scores = jnp.where(place[:, None] >= key_pos[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                          v)

    blocks = q.reshape(batch, heads, seq // block, block, width).transpose(
        2, 0, 1, 3, 4)
    out = jax.lax.map(lambda one: rows(*one),
                      (blocks, jnp.arange(0, seq, block)))
    return out.transpose(1, 2, 0, 3, 4).reshape(batch, heads, seq, -1)


HEADS_AT_ONCE = 4       # heads whose q, k and v exist at a time


def latent_attention(u, p, *, nope_dim, rope_theta, norm_eps,
                     operand_dtype=None, latent_dtype=None):
    """Latent attention with a query latent and no output gate; `p` holds
    `q_a_kernel` (hidden, q rank), `q_norm_scale`, `q_b_kernel` (q rank,
    heads, nope + rope), `kv_a_kernel` (hidden, rank + rope), `kv_norm_scale`
    (rank,), `kv_b_kernel` (rank, heads, nope + value) and `o_kernel` (heads,
    value, hidden).  The heads `HEADS_AT_ONCE` at a time, one group after
    another, each group's part of `concat_h(o_h) W_o` added up (32 heads' q,
    k and v at 8,192 tokens are 0.6 GB in float32, and autodiff keeps several
    of each)."""
    batch, seq, hidden = u.shape
    w_qb, w_kvb, w_o = (_f32(p[n]) for n in ("q_b_kernel", "kv_b_kernel",
                                             "o_kernel"))
    heads, rank = w_qb.shape[1], w_kvb.shape[0]
    held = HEADS_AT_ONCE if heads % HEADS_AT_ONCE == 0 else heads

    def normed(latent, scale):
        if latent_dtype is None:
            return rms_norm(latent, _f32(scale), norm_eps)
        # The control: every value the norm makes, rounded as it is made.
        def r(a):
            return _rounded(a, latent_dtype)

        latent = r(latent)
        root = r(jax.lax.rsqrt(r(jnp.mean(r(latent * latent), axis=-1,
                                          keepdims=True)) + norm_eps))
        return r(r(latent * root) * r(_f32(scale)))

    c_q = normed(_mm(u, _f32(p["q_a_kernel"]), operand_dtype),
                 p["q_norm_scale"])
    c_kv, k_rope = jnp.split(_mm(u, _f32(p["kv_a_kernel"]), operand_dtype),
                             [rank], axis=-1)
    c_kv = normed(c_kv, p["kv_norm_scale"])
    k_rope = rotary(k_rope, rope_theta)[:, None]       # one for every head

    @jax.checkpoint
    def group(c_q, c_kv, k_rope, w_qb, w_kvb, w_o):
        def per_head(x, w):
            return _mm(x, w.reshape(w.shape[0], -1), operand_dtype).reshape(
                batch, seq, held, -1).transpose(0, 2, 1, 3)

        q, kv = per_head(c_q, w_qb), per_head(c_kv, w_kvb)
        q = jnp.concatenate([q[..., :nope_dim],
                             rotary(q[..., nope_dim:], rope_theta)], axis=-1)
        k = jnp.concatenate([kv[..., :nope_dim], jnp.broadcast_to(
            k_rope, (batch, held, seq, k_rope.shape[-1]))], axis=-1)
        out = attention(q, k, kv[..., nope_dim:])       # (b, held, s, value)
        return _mm(out.transpose(0, 2, 1, 3).reshape(batch, seq, -1),
                   w_o.reshape(-1, hidden), operand_dtype)

    def grouped(w, axis):
        shape = w.shape[:axis] + (heads // held, held) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    out, _ = jax.lax.scan(
        lambda out, w: (out + group(c_q, c_kv, k_rope, *w), None),
        jnp.zeros_like(u), (grouped(w_qb, 1), grouped(w_kvb, 1),
                            grouped(w_o, 0)))
    return out


def router(u, w_router, *, experts_per_token, weight_scale,
           selection_bias=None, operand_dtype=None, score_dtype=None):
    """(weights (tokens, k), experts (tokens, k)) of the sigmoid router: the k
    largest of score + bias, weighted by their own scores renormalised."""
    scores = _rounded(jax.nn.sigmoid(_mm(u, w_router, operand_dtype)),
                      score_dtype)
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


def layer(x, p, kind, *, nope_dim, rope_theta, norm_eps, num_experts,
          experts_per_token, expert_shard, weight_scale, selection_bias=None,
          operand_dtype=None, score_dtype=None, latent_dtype=None):
    """(x out, chosen experts or None) of one pattern entry of `kind`."""
    u = rms_norm(x, _f32(p["norm"]["scale"]), norm_eps)
    m, experts = p["mixer"], None
    if kind == "latent_attention":
        out = latent_attention(u, m, nope_dim=nope_dim, rope_theta=rope_theta,
                               norm_eps=norm_eps, operand_dtype=operand_dtype,
                               latent_dtype=latent_dtype)
    elif kind == "gated_mlp":
        out = gated_mlp(u, *(m[n]["kernel"] for n in ("gate", "up", "down")),
                        operand_dtype)
    elif kind == "experts":
        out, experts = sparse_experts(
            u.reshape(-1, u.shape[-1]), m, num_experts=num_experts,
            expert_shard=expert_shard, experts_per_token=experts_per_token,
            weight_scale=weight_scale, selection_bias=selection_bias,
            operand_dtype=operand_dtype, score_dtype=score_dtype)
        out = out.reshape(x.shape)
    else:
        raise ValueError(f"no {kind!r} layer in this model")
    return x + out, experts


def _entries(x, params, names, kinds, biases, config):
    """`x` through the pattern entries `names` of `kinds`, each under
    `jax.checkpoint`; (x out, [chosen experts of each expert entry])."""
    chosen = []
    for name, kind in zip(names, kinds):
        bias = next(biases) if kind == "experts" else None
        x, experts = jax.checkpoint(
            lambda x, p, bias, kind=kind: layer(
                x, p, kind, selection_bias=bias, **config))(
                    x, params[name], bias)
        if experts is not None:
            chosen.append(experts)
    return x, chosen


def states(params, tokens, *, layers, mtp_layers, selection_bias=None,
           **config):
    """(the main model's normed state, the module's normed state, [chosen
    experts of each expert entry, the module's last]); `layers` and
    `mtp_layers`: the kinds in order; `selection_bias`: (expert entries,
    experts), the module's last, or None; `config`: `layer`'s other
    keywords."""
    table = _f32(params["embed"]["embedding"])
    eps, operand_dtype = config["norm_eps"], config.get("operand_dtype")
    experts = (layers + mtp_layers).count("experts")
    biases = iter([None] * experts if selection_bias is None
                  else selection_bias)

    def norm(name, x):
        return rms_norm(x, _f32(params[name]["scale"]), eps)

    x, chosen = _entries(table[tokens], params,
                         [f"layer_{i}" for i in range(len(layers))], layers,
                         biases, config)
    main = norm("final_norm", x)
    following = jnp.roll(tokens, -1, axis=1)          # t_{i+1}; the last: t_0
    joined = jnp.concatenate([norm("mtp_0_embed_norm", table[following]),
                              norm("mtp_0_state_norm", main)], axis=-1)
    x = _mm(joined, _f32(params["mtp_0_proj"]["kernel"]), operand_dtype)
    x, more = _entries(x, params,
                       [f"mtp_0_layer_{j}" for j in range(len(mtp_layers))],
                       mtp_layers, biases, config)
    return main, norm("mtp_0_final_norm", x), chosen + more


def cross_entropy(hidden, w_head, targets, valid, operand_dtype=None,
                  block: int = 512):
    """Mean cross-entropy of the untied head over the positions where `valid`
    (all shaped (batch, seq[, hidden])), a block of tokens at a time so that
    the float32 logits never exist all at once."""
    hidden = hidden.reshape(-1, hidden.shape[-1])
    targets, valid = targets.reshape(-1), valid.reshape(-1)

    @jax.checkpoint
    def block_sum(h, t, keep):
        logp = jax.nn.log_softmax(_mm(h, w_head, operand_dtype), axis=-1)
        picked = jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]
        return -jnp.where(keep, picked, 0.0).sum()

    if hidden.shape[0] % block:
        block = hidden.shape[0]
    blocks = hidden.shape[0] // block
    total, _ = jax.lax.scan(
        lambda total, htk: (total + block_sum(*htk), None), jnp.float32(0.0),
        (hidden.reshape(blocks, block, -1), targets.reshape(blocks, block),
         valid.reshape(blocks, block)))
    return total / valid.sum()


def loss_and_parts(params, batch, *, mtp_weight, **config):
    """(L_main + mtp_weight * L_mtp, ((L_main, L_mtp), the experts routed to:
    (expert entries, tokens, k), the module's last)) from ONE forward pass;
    `batch` is (inputs, targets), the targets' last column unread."""
    inputs, targets = batch
    main, module, chosen = states(params, inputs, **config)
    w_head = _f32(params["lm_head_kernel"])
    seq, operand_dtype = inputs.shape[1], config.get("operand_dtype")
    place = jnp.broadcast_to(jnp.arange(seq), inputs.shape)
    l_main = cross_entropy(main, w_head, targets, place < seq - 1,
                           operand_dtype)
    l_mtp = cross_entropy(module, w_head, jnp.roll(targets, -1, axis=1),
                          place < seq - 2, operand_dtype)
    return l_main + mtp_weight * l_mtp, ((l_main, l_mtp), jnp.stack(chosen))
