"""Plain float32 reference of the block-diffusion training pass the repository
runs for SDAR-30B-A3B (`models.TransformerLM(layers=("blockdiff_attention",
"experts") * depth, block_diffusion=B)` and `models.masked_diffusion_loss`), in
`jax.numpy` with no kernel, no block skipping, no sorting, no grouped matmul,
no mixed precision and no framework.

A sequence `x` of L tokens and its noised copy `x~` (token i of block `b = i
// B` replaced by the mask token where `m_i = 1`) run as ONE sequence of 2 L
rows, `[x; x~]`: `h_0 = Embed([x; x~])`; every published layer is `h <- h +
Attn(RMSNorm(h))`, `h <- h + Experts(RMSNorm(h))` (pre-norm, no post-norm, no
biases); then RMSNorm and the untied head ON THE LAST L ROWS ONLY, and the
masked-diffusion bound `(1 / L) sum_i m_i / t_i * -log softmax(logits_i)[x_i]`
(`t_i` the masking probability of token i's block; the mean is over all L
data tokens).  On the normed input `u`:

`Attn`: `q_h = RMSNorm_e(u W_q)_h`, `k_g = RMSNorm_e(u W_k)_g` (ONE learned
    scale of the head's width for q, one for k), `v_g = (u W_v)_g`; rows i and
    L + i both turn at position i (adjacent pairs, base `rope_theta`, the whole
    head); query head h reads key/value head `h // (H / H_kv)`, WRITTEN AS AN
    INDEX (no repeat); softmax at `e^-1/2` over whole rows of keys under the
    EXPLICIT mask `seen` below; `W_o`.
`seen(r, c)`, rows r and c of the 2 L, `blk(p) = (p mod L) // B`:
    r clean (r < L), c clean:   blk(c) <= blk(r)   (its own block whole)
    r noised, c clean:          blk(c) <  blk(r)
    r noised, c noised:         blk(c) == blk(r)
    r clean, c noised:          never.
`Experts`: `p = softmax_float32(u W_r)` over all experts; the k largest are
    chosen; `g_e = p_e / sum_chosen p` (`norm_topk_prob`); `sum_chosen g_e
    W_down,e (silu(W_gate,e u) * W_up,e u)`, a loop over THIS SHARD's experts,
    each applied to every row and masked by its weight.

The shares (`expert_shard`, a sliced vocabulary) are what the system computes:
the experts the parameter tree holds, the router over all experts.  With the
whole layer's parameters it is the whole layer (tests/test_sdar.py adds the
shares up to it).

Departures from the published model, each of which the system shares, and the
readings the config does not settle (block length, noise schedule, q/k norm)
are in benchmark/configs/sdar30ba3b.json under `departures` and `assumed`.

It reads the system's own parameter tree (flax names).  The caller traces it
under `jax.default_matmul_precision("highest")`.  `jax.checkpoint` around a
layer, an expert, a head's block of query rows and a block of the head only
bounds what autodiff keeps (this runs beside 7.7 GB of training state on a
16 GB chip; one head's 8,192 x 8,192 scores are 268 MB); it changes no
arithmetic, nor does `lax.map` over the heads.  `operand_dtype` rounds every
matmul operand, and the q, k, v the attention reads, to a narrower type and
back: how the tolerances below were shown to refuse the next precision under
bfloat16 (float8_e4m3fn).  `drop` names one term left out — `"level_weight"`
(the `1 / t`), `"renormalize"` (the division by the chosen probabilities'
sum), `"noised_block"` (a noised query no longer sees its own block's noised
keys, itself excepted) — the wrong programs the rows below must refuse.

Tolerances (used by benchmark/builders/sdar_lm.py), each with its reason: the
system computes in bfloat16 with float32 accumulation, stores the logits in
bfloat16, and ROUTES on bfloat16 activations, 8 of 128 experts a row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.moe_lm import (_f32, _mm, experts_of_shard,
                                        rms_norm)

# Each limit from two readings on the chip at the published widths and the
# timed sizes (PERF.md section 6, PR 39): the largest the system gave over the
# seeds named there (3900000003, ...101 to ...107, and from the final tree
# ...301 to ...303, 2147483999, ...305 to ...307: fifteen), and what THIS reference
# gives against itself with every matmul operand, forward and backward, and the
# q, k, v the attention reads rounded to float8_e4m3fn — the nearest precision
# under the configuration's bfloat16 — or with one term dropped (seeds
# 3900000021, ...022), each of which has to be refused by one row at least.
#
# What is particular here: the loss weighs a masked token by 1 / t, t down to
# 1e-3, so a sequence's loss and gradient are a heavy-tailed weighted mean
# (one seed's loss reads 11.27 and its gradient norm 20.9 where the others
# read 10.0 to 10.7 and 7.9 to 9.2), and a few tokens' roundings do not
# average out; and the first layer's seeded softmax is sharp
# (`initialisation` in the configuration), which the routing row pays for.
#
# |loss_system - loss_reference| / loss_reference.  System 1.7e-5 to 2.9e-4:
# the accepted expert cells' 2.5e-4 is UNDER the largest reading, so three
# times that reading.  float8 6.0e-5 and 3.4e-4 and a missing renormalisation 1.3e-4 and
# 3.6e-4 are not told apart by the loss (the rows below tell them); a missing
# 1 / t reads 0.52.
LOSS_RTOL = 1e-3
# ||g_system - g_reference|| / ||g_reference|| over all parameters.  System
# 0.044 to 0.089 (Trinity's 0.014: the 1.6 % of pairs routed elsewhere each
# swap a whole expert term, and a heavy token among them weighs its weight);
# float8 0.68 and 0.71, no renormalisation 0.28 and 0.29, no 1 / t 0.86.  A
# noised query blind to its block's other noised keys reads 0.12 and 0.16,
# astride this limit: the kernels' own rows below are what refuses a wrong
# mask.
GRAD_RTOL = 1.5e-1
# | ||g_system|| / ||g_reference|| - 1 |: only the error's component along
# the gradient.  System 1.1e-4 to 7.8e-3 (the largest where one token's weight
# carries the norm); float8 0.17 and 0.24, no renormalisation 0.038 and 0.043,
# no 1 / t 0.71.
GRAD_NORM_RTOL = 2e-2
# Share of (row, choice) pairs, over the six expert layers, whose expert the
# float32 reference did not choose for that row: near-ties between the 8th and
# 9th of 128 probabilities that a bfloat16 rounding of the activations flips.
# System 0.0158 to 0.0167 (Trinity's 0.006: a mask row's router input is the
# sharpened first layer's output, whose bfloat16 probabilities are ~1 % off);
# float8 0.139 and 0.141, no renormalisation 0.060 and 0.063.
ROUTING_MISMATCH_MAX = 3.5e-2
# The block-diffusion kernels alone against `masked_attention` on the same
# rounded q, k, v at the cell's length and head width, two heads, forward and
# gradients (compare.kernel_against), with the softmax SHARP_SCALE times
# sharper than the model's: at the model's own scale a seeded row spreads its
# weight over ~2,000 keys and the B - 1 noised neighbours of a noised query
# move its output by less than bfloat16's rounding of the probabilities does;
# sharpened, a few keys carry a row.  Forward, the largest absolute error of
# an output element (values are N(0, 1)): the kernels 0.040 to 0.056 over
# sixteen seeds; a causal mask over the 2 L rows 4.43, a block of 8 for the stated 4
# 5.02, a block of 2 3.95 (seed 3900000021).  Gradients, the largest error
# over the reference gradient's largest element: the kernels at most 0.015
# (dq), 0.014 (dk), 0.008 (dv); the wrong masks 0.35 at the least; and the
# kernels against a reference whose noised queries are blind to their block's
# other noised keys 4.12 forward, 0.27 to 0.47 in the gradients.
SHARP_SCALE = 4.0
BLOCKDIFF_FWD_ATOL = 2.5e-1
BLOCKDIFF_GRAD_RTOL = 5e-2

QUERY_BLOCK = 512        # query rows of one head whose scores exist at once


def _round(a, operand_dtype):
    """What `_mm` does to an operand, for the q, k, v the attention reads."""
    return a if operand_dtype is None else _f32(a.astype(operand_dtype))


def rotary(x, positions, theta):
    """(..., rows, width): pairs (x[2i], x[2i+1]) of row r turn by
    `positions[r] * theta^(-i / (width / 2))`."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def seen(rows, cols, length: int, block: int, drop=None):
    """The mask of the module's docstring for rows `rows` (n,) against rows
    `cols` (m,) of the 2 `length`: (n, m) bool."""
    r, c = rows[:, None], cols[None, :]
    r_noised, c_noised = r >= length, c >= length
    r_block, c_block = (r % length) // block, (c % length) // block
    own = r_noised & c_noised & (c_block == r_block)
    if drop == "noised_block":
        own = r_noised & (c == r)
    return (~r_noised & ~c_noised & (c_block <= r_block)) \
        | (r_noised & ~c_noised & (c_block < r_block)) | own


def masked_attention(q, k, v, *, block_length, sm_scale=None, drop=None):
    """Softmax attention under `seen`, `q` (batch, heads, 2 L, e), `k` and `v`
    (batch, key/value heads, 2 L, e): query head h reads key/value head `h //
    (heads / key/value heads)`.  One head and QUERY_BLOCK query rows at a
    time, whole rows of keys."""
    batch, heads, rows, width = q.shape
    group = heads // k.shape[1]
    scale = width ** -0.5 if sm_scale is None else sm_scale
    step = QUERY_BLOCK if rows % QUERY_BLOCK == 0 else rows
    every = jnp.arange(rows)

    @jax.checkpoint
    def some_rows(q_rows, start, k_h, v_h):           # (batch, step, e)
        scores = jnp.einsum("bqe,bke->bqk", q_rows, k_h) * scale
        mask = seen(start + jnp.arange(step), every, rows // 2, block_length,
                    drop)
        return jnp.einsum("bqk,bke->bqe", jax.nn.softmax(
            jnp.where(mask, scores, -jnp.inf), axis=-1), v_h)

    def head(h):
        q_h, k_h, v_h = q[:, h], k[:, h // group], v[:, h // group]
        blocks = q_h.reshape(batch, rows // step, step, width).swapaxes(0, 1)
        out = jax.lax.map(
            lambda xs: some_rows(xs[0], xs[1], k_h, v_h),
            (blocks, jnp.arange(0, rows, step)))
        return out.swapaxes(0, 1).reshape(batch, rows, v.shape[-1])

    return jax.lax.map(head, jnp.arange(heads)).swapaxes(0, 1)


def attention_layer(u, p, *, block_length, rope_theta, norm_eps,
                    operand_dtype=None, drop=None):
    """Grouped-query attention with per-head q/k norms on the normed input
    `u` (batch, 2 L, hidden).  `p` holds `q_kernel` (hidden, heads, e),
    `kv_kernel` (hidden, 2, key/value heads, e), `q_head_norm_scale` and
    `k_head_norm_scale` (e,) and `o_kernel` (heads, e, hidden)."""
    batch, rows, hidden = u.shape
    w_q, w_kv, w_o = (_f32(p[n]) for n in ("q_kernel", "kv_kernel",
                                           "o_kernel"))
    width = w_q.shape[2]

    def per_head(w):
        return _mm(u, w.reshape(hidden, -1), operand_dtype).reshape(
            batch, rows, -1, width).transpose(0, 2, 1, 3)

    positions = jnp.arange(rows) % (rows // 2)
    q = rms_norm(per_head(w_q), _f32(p["q_head_norm_scale"]), norm_eps)
    k = rms_norm(per_head(w_kv[:, 0]), _f32(p["k_head_norm_scale"]), norm_eps)
    v = per_head(w_kv[:, 1])
    q, k = rotary(q, positions, rope_theta), rotary(k, positions, rope_theta)
    out = masked_attention(*(_round(t, operand_dtype) for t in (q, k, v)),
                           block_length=block_length, drop=drop)
    return _mm(out.transpose(0, 2, 1, 3).reshape(batch, rows, -1),
               w_o.reshape(-1, hidden), operand_dtype)


def router(u, w_router, *, experts_per_token, operand_dtype=None, drop=None):
    """(weights (rows, k), experts (rows, k)) of the softmax router: the k
    largest probabilities, divided by their sum."""
    probs = jax.nn.softmax(_mm(u, w_router, operand_dtype), axis=-1)
    chosen, experts = jax.lax.top_k(probs, experts_per_token)
    if drop != "renormalize":
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return chosen, experts


def layer(x, p_attention, p_experts, *, block_length, rope_theta, norm_eps,
          num_experts, experts_per_token, expert_shard, operand_dtype=None,
          drop=None):
    """(x out, chosen experts) of one published layer: two pattern entries of
    the system's tree, its attention and its experts."""
    u = rms_norm(x, _f32(p_attention["norm"]["scale"]), norm_eps)
    x = x + attention_layer(u, p_attention["mixer"],
                            block_length=block_length, rope_theta=rope_theta,
                            norm_eps=norm_eps, operand_dtype=operand_dtype,
                            drop=drop)
    u = rms_norm(x, _f32(p_experts["norm"]["scale"]), norm_eps).reshape(
        -1, x.shape[-1])
    m = p_experts["mixer"]
    weights, experts = router(u, _f32(m["router_kernel"]),
                              experts_per_token=experts_per_token,
                              operand_dtype=operand_dtype, drop=drop)
    first = expert_shard[0] * (num_experts // expert_shard[1])
    mixed = experts_of_shard(u, m, weights, experts, first, operand_dtype)
    return x + mixed.reshape(x.shape), experts


def forward(params, tokens, noised, **config):
    """(final hidden states of the noised half (batch, L, hidden), [chosen
    experts of each layer]); `config`: `layer`'s keywords."""
    x = _f32(params["embed"]["embedding"])[
        jnp.concatenate([tokens, noised], axis=1)]
    chosen, index = [], 0
    while f"layer_{2 * index}" in params:
        x, experts = jax.checkpoint(
            lambda x, p_a, p_e: layer(x, p_a, p_e, **config))(
                x, params[f"layer_{2 * index}"],
                params[f"layer_{2 * index + 1}"])
        chosen.append(experts)
        index += 1
    x = x[:, tokens.shape[1]:]
    return rms_norm(x, _f32(params["final_norm"]["scale"]),
                    config["norm_eps"]), chosen


def diffusion_loss(hidden, w_head, targets, masked, level, operand_dtype=None,
                   drop=None, block: int = 512):
    """`mean over sequences of (1 / L) sum_i m_i / t_i * -log softmax(head
    h_i)[x_i]`, a block of tokens at a time so that the float32 logits never
    exist all at once."""
    count = targets.size
    hidden = hidden.reshape(count, -1)
    weight = _f32(masked) if drop == "level_weight" \
        else _f32(masked) / _f32(level)

    @jax.checkpoint
    def block_sum(h, t, w):
        logp = jax.nn.log_softmax(_mm(h, w_head, operand_dtype), axis=-1)
        return -(w * jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]
                 ).sum()

    if count % block:
        block = count
    blocks = count // block
    total, _ = jax.lax.scan(
        lambda total, htw: (total + block_sum(*htw), None), jnp.float32(0.0),
        (hidden.reshape(blocks, block, -1), targets.reshape(blocks, block),
         weight.reshape(blocks, block)))
    return total / count


def loss_and_chosen(params, batch, **config):
    """(the masked-diffusion bound, the experts routed to: (layers, rows, k))
    from ONE forward pass; `batch` is (tokens, noised, masked, level), each
    (batch, L)."""
    tokens, noised, masked, level = batch
    hidden, chosen = forward(params, tokens, noised, **config)
    return diffusion_loss(hidden, _f32(params["lm_head_kernel"]), tokens,
                          masked, level, config.get("operand_dtype"),
                          config.get("drop")), jnp.stack(chosen)


def loss(params, batch, **config):
    return loss_and_chosen(params, batch, **config)[0]
