"""Nemotron-3's pattern as a whole model (loss, every gradient and the experts'
choices against benchmark/reference/hybrid_lm.py, the train step) and the
tensor, expert and vocabulary shares that add up to the uncut layer: the
second half of tests/test_hybrid.py, whose sizes, helpers and tolerances it
reads.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import hybrid_lm as reference
from horovod_tpu.models import Mamba2Mixer, TransformerLM
from horovod_tpu.models.transformer import Attention, SparseExperts
from tests.test_hybrid import (EXPERTS, HEADS, HIDDEN, KV_HEADS, LATENT,
                               LAYERS, PER_TOKEN, SCALE, SEQ, SSM, VOCAB,
                               WIDTH, close, lm, mamba2_share, mixer_case, moe,
                               reference_side, relative_error, seeded,
                               share_outputs, sides_agree, system_side,
                               trains_and_replicas_stay_equal,
                               vocabulary_slices_concatenate, with_highest)


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("expert_shard,head_shard",
                         [((0, 1), (0, 1)), ((1, 4), (1, 2))])
def test_hybrid_lm_loss_and_gradients_are_the_references(expert_shard,
                                                         head_shard, chunk):
    model = lm(expert_shard, head_shard, chunk=chunk)
    params, batch = seeded(model, seed=chunk)
    sides_agree(system_side(model, params, batch),
                reference_side(expert_shard)(params, batch))


def test_reference_refuses_float8_operands():
    """The reference against itself with every matmul operand rounded to
    float8_e4m3fn: the error the benchmark's limits must refuse is far over
    what float32 reorderings give above."""
    model = lm()
    params, batch = seeded(model)
    (_, exact), (_, rounded) = (
        reference_side(operand_dtype=dtype)(params, batch)
        for dtype in (None, jnp.float8_e4m3fn))
    assert float(relative_error(rounded, exact)) > 0.05


def test_pattern_has_one_norm_and_one_mixer_a_layer():
    shapes = jax.eval_shape(lambda: lm((0, 4), (0, 2)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32))["params"])
    assert set(shapes) == {"embed", "final_norm", "lm_head_kernel"} | {
        f"layer_{i}" for i in range(len(LAYERS))}
    mixers = {"ssm": {"A_log", "D", "conv_bias", "conv_kernel", "dt_bias",
                      "in_proj_kernel", "norm_scale", "out_proj_kernel"},
              "attention": {"q_kernel", "kv_kernel", "o_kernel"},
              "experts": {"router_kernel", "up_kernel", "down_kernel",
                          "latent_down", "latent_up", "shared_up",
                          "shared_down"}}
    for i, kind in enumerate(LAYERS):
        assert set(shapes[f"layer_{i}"]) == {"norm", "mixer"}
        assert set(shapes[f"layer_{i}"]["mixer"]) == mixers[kind]
    # The share: 4 of 8 heads in 2 of 4 groups, 4 of 16 experts.
    inner, bc = 4 * SSM.head_dim, 2 * SSM.state
    assert shapes["layer_0"]["mixer"]["in_proj_kernel"].shape == (
        HIDDEN, 2 * inner + 2 * bc + 4)
    assert shapes["layer_1"]["mixer"]["up_kernel"].shape == (4, LATENT, WIDTH)
    assert shapes["layer_1"]["mixer"]["router_kernel"].shape == (HIDDEN,
                                                                 EXPERTS)


@pytest.mark.parametrize("how", ["decode_ctx", "seq_axis", "kind"])
def test_pattern_refuses_what_it_cannot_run(how):
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    if how == "kind":
        with pytest.raises(ValueError, match="layer kind"):
            TransformerLM(vocab_size=VOCAB, d_model=HIDDEN, n_heads=HEADS,
                          layers=("mlp",)).init(jax.random.PRNGKey(0), tokens)
        return
    model = TransformerLM(
        vocab_size=VOCAB, d_model=HIDDEN, n_heads=HEADS, layers=LAYERS,
        ssm=SSM, moe=moe(), seq_axis="sp" if how == "seq_axis" else None)
    with pytest.raises(ValueError, match="per-layer pattern"):
        model.init(jax.random.PRNGKey(0), tokens,
                   decode_ctx=object() if how == "decode_ctx" else None)


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern.
    The replicated weights stay equal and the loss of a repeated batch
    falls.  The flash kernel (interpreted here), as in the benchmark."""
    model = lm((0, 4), (0, 2), use_flash=True)
    trains_and_replicas_stay_equal(model, *seeded(model, seed=3))


@pytest.mark.parametrize("n,groups", [(2, 4), (4, 4), (8, 8)])
def test_mamba2_tensor_shares_add_up_to_the_uncut_layer(n, groups):
    ssm = SSM._replace(groups=groups)
    whole = Mamba2Mixer(*ssm, dtype=jnp.float32, norm_eps=1e-5)
    u, params, _ = mixer_case(whole, n)
    parts = share_outputs(
        n, lambda i: Mamba2Mixer(*ssm, head_shard=(i, n), dtype=jnp.float32,
                                 norm_eps=1e-5),
        lambda p, i: mamba2_share(p, i, n, ssm), params, u)
    close(sum(parts), with_highest(reference.mamba2)(
        u, params, head_dim=ssm.head_dim, state=ssm.state, norm_eps=1e-5),
        1e-4)


@pytest.mark.parametrize("n", [2, 8])
def test_attention_tensor_shares_add_up_to_the_uncut_layer(n):
    whole = Attention(HEADS, jnp.float32, use_flash=False,
                      n_kv_heads=KV_HEADS, rope=False)
    u, params, _ = mixer_case(whole, n)
    local, group = HEADS // n, HEADS // KV_HEADS

    def share(params, i):
        kv = slice(i * local // group, max(i * local // group + 1,
                                           (i + 1) * local // group))
        return {"q_kernel": params["q_kernel"][:, i * local:(i + 1) * local],
                "kv_kernel": params["kv_kernel"][:, :, kv],
                "o_kernel": params["o_kernel"][i * local:(i + 1) * local]}

    parts = share_outputs(
        n, lambda i: Attention(HEADS, jnp.float32, use_flash=False,
                               n_kv_heads=KV_HEADS, rope=False,
                               head_shard=(i, n)), share, params, u)
    close(sum(parts), with_highest(reference.grouped_query_attention)(
        u, params))


@pytest.mark.parametrize("n,experts", [(4, EXPERTS), (16, EXPERTS),
                                       (64, 128)])
def test_expert_shares_add_up_with_what_every_chip_computes_counted_once(
        n, experts):
    """The n shares' outputs each hold the shared expert, and (the projection
    up being linear) their sum holds it n times and the routed part once.
    64 shares of 2 experts: the deployment's count."""
    whole = SparseExperts(moe(experts=experts), jnp.float32)
    u, params, _ = mixer_case(whole, n)
    local = experts // n

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, up_kernel=params["up_kernel"][held],
                    down_kernel=params["down_kernel"][held])

    parts = share_outputs(
        n, lambda i: SparseExperts(moe((i, n), experts=experts), jnp.float32),
        share, params, u)
    flat = u.reshape(-1, HIDDEN)
    shared = reference.relu2(flat @ params["shared_up"]["kernel"]) \
        @ params["shared_down"]["kernel"]
    want = with_highest(reference.latent_experts)(
        flat, params, num_experts=experts, experts_per_token=PER_TOKEN,
        expert_shard=(0, 1), weight_scale=SCALE)[0]
    shared = shared.reshape(u.shape)
    # Each share less the shared expert, summed, and the shared expert once
    # (the same sum in the order that does not cancel n large terms).
    close(sum(part - shared for part in parts) + shared,
          want.reshape(u.shape))


@pytest.mark.parametrize("n", [2, 8])
def test_vocabulary_slices_concatenate_to_the_uncut_head(n):
    """A sliced vocabulary is a smaller vocabulary: the i-th slice's model —
    its rows of the embedding, its columns of the head — gives, for ids of
    the slice, the uncut model's logits of those columns."""
    vocabulary_slices_concatenate(lm, n)
