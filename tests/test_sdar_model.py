"""SDAR's pattern as a whole model (loss, every gradient and the experts'
choices against benchmark/reference/sdar_lm.py, the train step), the shares
that add up to the uncut layer, and the wrong programs the reference must
refuse: the second half of tests/test_sdar.py, whose sizes, helpers and
tolerances it reads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_lm as reference
from horovod_tpu.models import record_attention_blocks
from horovod_tpu.models.transformer import (LayerOptions, MixerLayer,
                                            SparseExperts)
from horovod_tpu.ops import flash_attention
from horovod_tpu.ops.attention import mask_blocks
from tests.test_hybrid import (close, relative_error, share_outputs,
                               sides_agree, spread,
                               trains_and_replicas_stay_equal, with_highest)
from tests.test_sdar import (BLOCK, DEPTH, EPS, EXPERTS, HEADS, HEAD_DIM,
                             HIDDEN, KV_HEADS, PER_TOKEN, SEQ, THETA, VOCAB,
                             case, lm, moe, noised_batch, probe_rows,
                             reference_config, reference_side, seed_zero,
                             seeded, system_loss, system_side)


# --- the model ---------------------------------------------------------------

@pytest.mark.parametrize("expert_shard", [(0, 1), (1, 4)])
@pytest.mark.parametrize("use_flash", [False, True])
def test_sdar_lm_loss_and_gradients_are_the_references(expert_shard,
                                                       use_flash):
    model = lm(expert_shard, use_flash)
    params, batch = seeded(model, seed=expert_shard[1])
    sides_agree(system_side(model, params, batch),
                reference_side(expert_shard)(params, batch))


def test_the_head_runs_on_the_noised_half():
    """Logits for L rows, and none of them moves with the clean copy's LAST
    block (no later block's noised query sees it), while the noised copy's
    rows do move their own block's."""
    model = lm()
    params, (tokens, noised, _, _) = seeded(model, seed=5)
    apply = jax.jit(lambda t, n: model.apply({"params": params}, t, noised=n))
    logits = apply(tokens, noised)
    assert logits.shape == (2, SEQ, VOCAB)
    other_tail = tokens.at[:, -BLOCK:].set((tokens[:, -BLOCK:] + 1) % VOCAB)
    close(apply(other_tail, noised), logits)
    other_head = tokens.at[:, :BLOCK].set((tokens[:, :BLOCK] + 1) % VOCAB)
    moved = jnp.abs(apply(other_head, noised) - logits).max(-1)
    assert float(moved[:, :BLOCK].max()) == 0.0        # its own block: unseen
    assert float(moved[:, BLOCK:].min()) > 0.0
    other_noise = noised.at[:, 0].set((noised[:, 0] + 1) % VOCAB)
    moved = jnp.abs(apply(tokens, other_noise) - logits).max(-1)
    assert float(moved[:, :BLOCK].min()) > 0.0
    assert float(moved[:, BLOCK:].max()) == 0.0


def test_block_diffusion_layers_count_their_tiles():
    model = lm(use_flash=True)
    params, batch = seeded(model, seed=4)
    wrote = jax.jit(lambda p, t, n: model.apply(
        {"params": p}, t, noised=n, mutable=["intermediates"])[1])(
            params, batch[0], batch[1])
    seen = record_attention_blocks(wrote["intermediates"])
    # 128 rows a copy are one 128-tile each: clean on clean, noised on clean,
    # noised on noised; a causal walk over the 256 rows visits as many.
    assert seen == {"blocks_visited": [3] * DEPTH,
                    "blocks_causal": [3] * DEPTH}
    assert mask_blocks(2 * SEQ, HEAD_DIM, block_diffusion=BLOCK) == (3, 3)


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern
    and the block-diffusion flash kernels (interpreted here) as in the
    benchmark.  The replicated weights stay equal and the loss of a repeated
    batch falls."""
    model = lm((0, 4), use_flash=True)
    trains_and_replicas_stay_equal(model, *seeded(model, seed=3),
                                   loss=system_loss)


# --- the shares add up to the uncut layer ------------------------------------

@pytest.mark.parametrize("n,experts", [(4, EXPERTS), (8, EXPERTS), (8, 128)])
def test_expert_shares_add_up_with_the_router_counted_once(n, experts):
    """The n shares' outputs sum to the uncut reference's layer: softmax over
    all experts and the renormalised weights on every share, each expert on
    one.  8 shares of 16 experts: the deployment's count."""
    whole = SparseExperts(moe(experts=experts), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(n), 2)
    u = jax.random.normal(keys[0], (2, SEQ, HIDDEN))
    params = jax.jit(whole.init)(keys[1], u)["params"]
    local = experts // n

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, **{name: params[name][held] for name in (
            "gate_kernel", "up_kernel", "down_kernel")})

    parts = share_outputs(
        n, lambda i: SparseExperts(moe((i, n), experts=experts), jnp.float32),
        share, params, u)
    flat = u.reshape(-1, HIDDEN)
    weights, chosen = with_highest(reference.router)(
        flat, params["router_kernel"], experts_per_token=PER_TOKEN)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
    want = with_highest(reference.experts_of_shard)(flat, params, weights,
                                                    chosen, 0)
    close(sum(parts), want.reshape(u.shape))


def test_the_layers_shares_add_up_with_attention_counted_once():
    """One published layer: every share computes the attention alike (counted
    once) and its own experts; attention's output plus the shares' expert
    outputs is the uncut reference's layer."""
    n = 4
    common = dict(n_heads=HEADS, dtype=jnp.float32, use_flash=False,
                  norm_eps=EPS)
    attention = MixerLayer("blockdiff_attention", LayerOptions(
        n_kv_heads=KV_HEADS, head_dim=HEAD_DIM, head_norm=True,
        block_diffusion=BLOCK, rope_theta=THETA, **common))

    def experts(shard):
        return MixerLayer("experts", LayerOptions(moe=moe(shard), **common))

    x, p_attention, _ = case(attention, seed=7)
    p_experts = spread(experts((0, 1)).init(jax.random.PRNGKey(8),
                                            x)["params"], 8)
    after = attention.apply({"params": p_attention}, x)
    local = EXPERTS // n
    total = after
    for i in range(n):
        held = slice(i * local, (i + 1) * local)
        mixer = dict(p_experts["mixer"], **{
            name: p_experts["mixer"][name][held]
            for name in ("gate_kernel", "up_kernel", "down_kernel")})
        total = total + experts((i, n)).apply(
            {"params": dict(p_experts, mixer=mixer)}, after) - after
    want = with_highest(reference.layer)(
        x, p_attention, p_experts, **reference_config())[0]
    close(total, want)


@pytest.mark.parametrize("n", [2, 8])
def test_vocabulary_slices_concatenate_to_the_uncut_head(n):
    """A sliced vocabulary is a smaller vocabulary: the i-th slice's model
    gives, for ids of the slice, the uncut model's logits of those columns."""
    model = lm()
    params, _ = seeded(model)
    rows = VOCAB // n
    whole = jax.jit(lambda p, t, m: model.apply({"params": p}, t, noised=m))
    small = lm(vocab=rows)
    sliced = jax.jit(lambda p, t, m: small.apply({"params": p}, t, noised=m))
    for i in range(n):
        tokens, noised, _, _ = noised_batch(jax.random.PRNGKey(9), vocab=rows)
        held = slice(i * rows, (i + 1) * rows)
        share = dict(params,
                     embed={"embedding": params["embed"]["embedding"][held]},
                     lm_head_kernel=params["lm_head_kernel"][:, held])
        close(sliced(share, tokens, noised),
              whole(params, tokens + i * rows, noised + i * rows)[..., held])


def test_the_kernels_pass_the_builders_own_rows():
    rows = probe_rows(lambda q, k, v, scale: flash_attention(
        q, k, v, block_diffusion=BLOCK, sm_scale=scale, block_q=128,
        block_k=128, interpret=True))
    assert len(rows) == 4 and all(row["value"] < 1e-4 * row["limit"]
                                  for row in rows), rows


@pytest.mark.parametrize("wrong", [dict(causal=True),
                                   dict(block_diffusion=2 * BLOCK),
                                   dict(block_diffusion=BLOCK // 2)], ids=str)
def test_a_wrong_mask_fails_the_builders_rows(wrong):
    """A causal mask over the 2 L rows, and a block twice or half as long,
    through the kernels themselves: each is over a limit of the cell's
    comparison, by a wide margin."""
    rows = probe_rows(lambda q, k, v, scale: flash_attention(
        q, k, v, sm_scale=scale, block_q=128, block_k=128, interpret=True,
        **wrong))
    over = [row for row in rows if row["value"] > 2 * row["limit"]]
    assert over, rows


@pytest.mark.parametrize("drop", ["level_weight", "renormalize",
                                  "noised_block"])
def test_a_dropped_term_is_another_program(drop):
    """The switches that leave a term out do change the loss and its
    gradients, by more than the cell's limits allow."""
    params, batch, right = seed_zero()
    wrong = reference_side(drop=drop)(params, batch)
    off = relative_error(wrong[1], right[1])
    assert abs(float(wrong[0][0] / right[0][0] - 1)) > reference.LOSS_RTOL \
        or float(off) > reference.GRAD_RTOL, (drop, off)
    assert float(off) > reference.GRAD_RTOL, (drop, off)


@pytest.mark.parametrize("dtype,least", [(jnp.float8_e4m3fn,
                                          reference.GRAD_RTOL),
                                         (jnp.bfloat16, 50 * 1e-4)],
                         ids=["float8_under_bfloat16",
                              "bfloat16_under_float32"])
def test_reference_refuses_the_next_precision_down(dtype, least):
    params, batch, (_, exact) = seed_zero()
    _, rounded = reference_side(operand_dtype=dtype)(params, batch)
    assert float(relative_error(rounded, exact)) > least
