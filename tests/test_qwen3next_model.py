"""Qwen3-Next's pattern as a whole model (loss, every gradient and the experts'
choices against benchmark/reference/qwen3next_lm.py, the train step) and the
tensor and expert shares that add up to the uncut layer: the second half of
tests/test_qwen3next.py, whose sizes, helpers and tolerances it reads.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import qwen3next_lm as reference
from horovod_tpu.models import DeltaMixer
from horovod_tpu.models.transformer import LAYER_KINDS, SparseExperts
from tests.test_hybrid import (close, mixer_case, relative_error, seeded,
                               share_outputs, sides_agree, system_side,
                               trains_and_replicas_stay_equal, with_highest)
from tests.test_qwen3next import (DELTA, EXPERTS, HEAD_DIM, HIDDEN, KV_HEADS,
                                  LAYERS, LINEAR_DIM, PER_TOKEN, SEQ,
                                  VALUE_HEADS, WIDTH, gated_delta_share, lm,
                                  moe, reference_side)


# --- the whole model --------------------------------------------------------

@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("expert_shard", [(0, 1), (1, 4)])
def test_qwen3next_lm_loss_and_gradients_are_the_references(expert_shard,
                                                            chunk):
    model = lm(expert_shard, chunk=chunk)
    params, batch = seeded(model, seed=chunk)
    sides_agree(system_side(model, params, batch),
                reference_side(expert_shard)(params, batch))


def test_reference_refuses_float8_operands():
    """The reference against itself with every matmul operand, and the q, k, v
    its recurrence and its attention read, rounded to float8_e4m3fn: the error
    the benchmark's limits must refuse is far over what float32 reorderings
    give above."""
    model = lm()
    params, batch = seeded(model)
    (_, exact), (_, rounded) = (
        reference_side(operand_dtype=dtype)(params, batch)
        for dtype in (None, jnp.float8_e4m3fn))
    assert float(relative_error(rounded, exact)) > 0.05


def test_pattern_has_one_norm_and_one_mixer_an_entry():
    shapes = jax.eval_shape(lambda: lm((0, 4)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32))["params"])
    assert set(shapes) == {"embed", "final_norm", "lm_head_kernel"} | {
        f"layer_{i}" for i in range(len(LAYERS))}
    mixers = {"gated_delta": {"A_log", "conv_kernel", "dt_bias",
                              "in_proj_kernel", "norm_scale",
                              "out_proj_kernel"},
              "attention": {"q_kernel", "kv_kernel", "q_head_norm_scale",
                            "k_head_norm_scale", "gate_kernel", "o_kernel"},
              "experts": {"router_kernel", "gate_kernel", "up_kernel",
                          "down_kernel", "shared_gate", "shared_up",
                          "shared_down", "shared_output_gate_kernel"}}
    for i, kind in enumerate(LAYERS):
        assert set(shapes[f"layer_{i}"]) == {"norm", "mixer"}
        assert set(shapes[f"layer_{i}"]["mixer"]) == mixers[kind]
    assert LAYER_KINDS["gated_delta"].mixer is LAYER_KINDS["delta"].mixer \
        is DeltaMixer
    # The share: 4 of 16 experts, the router over all 16, the mixers whole.
    assert shapes["layer_0"]["mixer"]["A_log"].shape == (VALUE_HEADS,)
    assert shapes["layer_1"]["mixer"]["up_kernel"].shape == (4, HIDDEN, WIDTH)
    assert shapes["layer_1"]["mixer"]["router_kernel"].shape == (HIDDEN,
                                                                 EXPERTS)
    assert shapes["layer_6"]["mixer"]["kv_kernel"].shape == (
        HIDDEN, 2, KV_HEADS, HEAD_DIM)


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern.
    The replicated weights stay equal and the loss of a repeated batch falls.
    The flash kernels (interpreted here), as in the benchmark; the delta
    rule's scan carries a state that varies over the mesh axis."""
    model = lm((0, 4), use_flash=True)
    trains_and_replicas_stay_equal(model, *seeded(model, seed=3))


def test_gated_delta_tensor_shares_add_up_to_the_uncut_layer():
    whole = DeltaMixer(*DELTA, gate="head", dtype=jnp.float32)
    u, params, _ = mixer_case(whole, 2)
    parts = share_outputs(
        2, lambda i: DeltaMixer(*DELTA, gate="head", head_shard=(i, 2),
                                dtype=jnp.float32),
        lambda p, i: gated_delta_share(p, i, 2), params, u)
    close(sum(parts), with_highest(reference.gated_delta)(
        u, params, head_dim=LINEAR_DIM, norm_eps=1e-6), 1e-4)


@pytest.mark.parametrize("n,experts", [(4, EXPERTS), (16, EXPERTS),
                                       (16, 512)])
def test_expert_shares_add_up_with_the_gated_shared_expert_counted_once(
        n, experts):
    """The n shares' outputs each hold the gated shared expert; their sum
    holds it n times and the routed part once.  16 shares of 32 experts: the
    deployment's count."""
    whole = SparseExperts(moe(experts=experts), jnp.float32)
    u, params, _ = mixer_case(whole, n)
    local = experts // n

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, **{name: params[name][held] for name in (
            "gate_kernel", "up_kernel", "down_kernel")})

    parts = share_outputs(
        n, lambda i: SparseExperts(moe((i, n), experts=experts), jnp.float32),
        share, params, u)
    flat = u.reshape(-1, HIDDEN)
    shared = (jax.nn.sigmoid(flat @ params["shared_output_gate_kernel"])
              * reference.gated_mlp(flat, *(params[name]["kernel"] for name in (
                  "shared_gate", "shared_up", "shared_down")))).reshape(
                      u.shape)
    want = with_highest(reference.sparse_experts)(
        flat, params, num_experts=experts, expert_shard=(0, 1),
        experts_per_token=PER_TOKEN)[0]
    close(sum(part - shared for part in parts) + shared,
          want.reshape(u.shape))
