"""Ling-3.0-flash's pattern as a whole model (loss, every gradient and the
experts' choices against benchmark/reference/ling_lm.py, the train step) and
the tensor, expert and vocabulary shares that add up to the uncut layer: the
second half of tests/test_ling.py, whose sizes, helpers and tolerances it
reads.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import ling_lm as reference
from horovod_tpu.models import (DeltaMixer, LatentAttention, Mamba2Config,
                                TransformerLM)
from horovod_tpu.models.transformer import (LAYER_KINDS, LayerOptions,
                                            MixerLayer, SparseExperts)
from tests.test_hybrid import (close, mixer_case, relative_error, seeded,
                               share_outputs, sides_agree, system_side,
                               trains_and_replicas_stay_equal,
                               vocabulary_slices_concatenate, with_highest)
from tests.test_ling import (DELTA, D_FF, EXPERTS, GROUPS, HEADS, HIDDEN,
                             LATENT, LAYERS, SEQ, VOCAB, WIDTH, delta_share,
                             lm, moe, reference_side, routing)


# --- the whole model ----------------------------------------------------

@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("expert_shard,head_shard",
                         [((0, 1), (0, 1)), ((1, 4), (1, 2))])
def test_ling_lm_loss_and_gradients_are_the_references(expert_shard,
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


def test_pattern_has_one_norm_and_one_mixer_an_entry():
    shapes = jax.eval_shape(lambda: lm((0, 4), (0, 2)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32))["params"])
    assert set(shapes) == {"embed", "final_norm", "lm_head_kernel"} | {
        f"layer_{i}" for i in range(len(LAYERS))}
    mixers = {"delta": {"A_log", "conv_kernel", "dt_bias", "in_proj_kernel",
                        "norm_scale", "out_proj_kernel"},
              "latent_attention": {"q_kernel", "kv_a_kernel", "kv_norm_scale",
                                   "kv_b_kernel", "gate_kernel", "o_kernel"},
              "gated_mlp": {"gate", "up", "down"},
              "experts": {"router_kernel", "gate_kernel", "up_kernel",
                          "down_kernel", "shared_gate", "shared_up",
                          "shared_down"}}
    for i, kind in enumerate(LAYERS):
        assert set(shapes[f"layer_{i}"]) == {"norm", "mixer"}
        assert set(shapes[f"layer_{i}"]["mixer"]) == mixers[kind]
    # The share: 4 of 8 heads, 4 of 16 experts, the router over all 16.
    assert shapes["layer_0"]["mixer"]["A_log"].shape == (4,)
    assert shapes["layer_3"]["mixer"]["up_kernel"].shape == (4, HIDDEN, WIDTH)
    assert shapes["layer_3"]["mixer"]["router_kernel"].shape == (HIDDEN,
                                                                 EXPERTS)
    assert shapes["layer_1"]["mixer"]["up"]["kernel"].shape == (HIDDEN, D_FF)


def test_an_unknown_kind_is_refused_with_every_kind_named():
    with pytest.raises(ValueError) as refused:
        TransformerLM(vocab_size=VOCAB, d_model=HIDDEN, n_heads=HEADS,
                      layers=("window",)).init(
                          jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    for kind in LAYER_KINDS:
        assert kind in str(refused.value) and kind in MixerLayer.__doc__


def test_a_layers_options_are_the_models_fields_declared_once():
    """`LayerOptions` is `TransformerLM`'s defaulted fields by name, each with
    the model's default (a list declared twice that has drifted fails here);
    `MixerLayer` declares none of its own; what a model hands its layers is
    its own fields with `d_ff` resolved; and every kind's row names fields
    its mixer has."""
    model_fields = {field.name: field.default
                    for field in dataclasses.fields(TransformerLM)}
    assert set(LayerOptions._fields) < set(model_fields)
    assert LayerOptions._field_defaults == {
        name: model_fields[name] for name in LayerOptions._fields}
    assert [field.name for field in dataclasses.fields(MixerLayer)][:2] \
        == ["kind", "options"] and len(dataclasses.fields(MixerLayer)) == 4
    model = lm()
    options = model._layer_options()
    assert options == LayerOptions(**{
        name: getattr(model, name) for name in LayerOptions._fields})
    assert model.clone(d_ff=None)._layer_options() \
        == options._replace(d_ff=4 * HIDDEN)
    hash(options)
    for kind, row in LAYER_KINDS.items():
        arguments = row.arguments(options._replace(
            ssm=Mamba2Config(8, 8, 4, 16)))
        assert set(arguments) <= {
            field.name for field in dataclasses.fields(row.mixer)}, kind
        assert row.wants is None or row.wants in arguments


@pytest.mark.parametrize("mixer", [DeltaMixer(*DELTA, head_shard=(0, 3)),
                                   LatentAttention(HEADS, LATENT,
                                                   head_shard=(2, 2))])
def test_mixers_refuse_a_share_that_does_not_divide(mixer):
    with pytest.raises(ValueError, match="head_shard"):
        mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, SEQ, HIDDEN)))


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern.
    The replicated weights stay equal and the loss of a repeated batch
    falls.  The flash kernels (interpreted here), as in the benchmark; the
    delta rule's scan carries a state that varies over the mesh axis."""
    model = lm((0, 4), (0, 2), use_flash=True)
    trains_and_replicas_stay_equal(model, *seeded(model, seed=3))


@pytest.mark.parametrize("n", [2, 4, 8])
def test_delta_tensor_shares_add_up_to_the_uncut_layer(n):
    whole = DeltaMixer(*DELTA, dtype=jnp.float32)
    u, params, _ = mixer_case(whole, n)
    parts = share_outputs(
        n, lambda i: DeltaMixer(*DELTA, head_shard=(i, n), dtype=jnp.float32),
        lambda p, i: delta_share(p, i, n), params, u)
    close(sum(parts), with_highest(reference.kda)(
        u, params, head_dim=DELTA.head_dim, lower_bound=DELTA.lower_bound,
        norm_eps=1e-6), 1e-4)


@pytest.mark.parametrize("n", [2, 8])
def test_latent_attention_tensor_shares_add_up_with_the_latent_counted_once(
        n):
    """Every share holds the whole `W_kva` and the latent's norm (a chip of
    the mesh computes the latent alike); the heads' slices of the other four
    weights partition, and the n outputs sum to the uncut layer's."""
    whole = LatentAttention(HEADS, LATENT, jnp.float32, use_flash=False)
    u, params, _ = mixer_case(whole, n)
    local = HEADS // n

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, q_kernel=params["q_kernel"][:, held],
                    kv_b_kernel=params["kv_b_kernel"][:, held],
                    gate_kernel=params["gate_kernel"][:, held],
                    o_kernel=params["o_kernel"][held])

    parts = share_outputs(
        n, lambda i: LatentAttention(HEADS, LATENT, jnp.float32,
                                     use_flash=False, head_shard=(i, n)),
        share, params, u)
    close(sum(parts), with_highest(reference.latent_attention)(
        u, params, nope_dim=LATENT.nope_dim, rope_theta=LATENT.rope_theta,
        norm_eps=1e-6))


@pytest.mark.parametrize("n,experts,groups", [(4, EXPERTS, GROUPS),
                                              (16, EXPERTS, GROUPS),
                                              (64, 128, 8)])
def test_expert_shares_add_up_with_router_and_shared_expert_counted_once(
        n, experts, groups):
    """The n shares' outputs each hold the shared expert; their sum holds it
    n times and the routed part once.  64 shares of 2 experts in 8 groups,
    4 kept: the deployment's count."""
    kept = groups // 2
    whole = SparseExperts(moe(experts=experts, groups=groups, kept=kept),
                          jnp.float32)
    u, params, _ = mixer_case(whole, n)
    local = experts // n

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, **{name: params[name][held] for name in (
            "gate_kernel", "up_kernel", "down_kernel")})

    parts = share_outputs(
        n, lambda i: SparseExperts(
            moe((i, n), experts=experts, groups=groups, kept=kept),
            jnp.float32), share, params, u)
    flat = u.reshape(-1, HIDDEN)
    shared = reference.gated_mlp(flat, *(params[name]["kernel"] for name in (
        "shared_gate", "shared_up", "shared_down"))).reshape(u.shape)
    want = with_highest(reference.sparse_experts)(
        flat, params, num_experts=experts, expert_shard=(0, 1),
        **dict(routing(), n_group=groups, topk_group=kept))[0]
    close(sum(part - shared for part in parts) + shared,
          want.reshape(u.shape))


@pytest.mark.parametrize("n", [2, 8])
def test_vocabulary_slices_concatenate_to_the_uncut_head(n):
    """A sliced vocabulary is a smaller vocabulary: the i-th slice's model —
    its rows of the embedding, its columns of the head — gives, for ids of
    the slice, the uncut model's logits of those columns."""
    vocabulary_slices_concatenate(lm, n)
