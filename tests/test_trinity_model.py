"""Trinity-Mini's pattern as a whole model (loss, every gradient and the
experts' choices against benchmark/reference/trinity_lm.py, the train step),
the shares that add up to the uncut layer, and the wrong programs the
reference must refuse: the second half of tests/test_trinity.py, whose sizes,
helpers and tolerances it reads.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import trinity_lm as reference
from horovod_tpu.common import metrics
from horovod_tpu.models import record_attention_blocks
from horovod_tpu.models.transformer import SparseExperts
from horovod_tpu.ops import flash_attention
from tests.test_hybrid import (close, mixer_case, relative_error, seeded,
                               share_outputs, sides_agree, sown, system_side,
                               trains_and_replicas_stay_equal,
                               vocabulary_slices_concatenate, with_highest)
from tests.test_trinity import (EXPERTS, HIDDEN, PER_TOKEN, SCALE, WINDOW, lm,
                                moe, probe_rows, reference_config,
                                reference_side, seed_zero)


# --- the model ---------------------------------------------------------------

@pytest.mark.parametrize("expert_shard", [(0, 1), (1, 4)])
def test_trinity_lm_loss_and_gradients_are_the_references(expert_shard):
    model = lm(expert_shard)
    params, batch = seeded(model, seed=expert_shard[1])
    sides_agree(system_side(model, params, batch),
                reference_side(expert_shard)(params, batch))


def test_the_embedding_multiplier_is_the_references():
    model = lm()
    params, batch = seeded(model, seed=2)
    scaled = jax.jit(model.apply)({"params": params}, batch[0])
    as_one = jax.jit(lm().clone(embed_scale=None).apply)({"params": dict(
        params, embed={"embedding": params["embed"]["embedding"]
                       * HIDDEN ** 0.5})}, batch[0])
    close(scaled, as_one)


def test_windowed_layers_count_their_blocks(monkeypatch):
    model = lm(use_flash=True)
    params, batch = seeded(model, seed=4)
    wrote = sown(model, {"params": params}, batch[0])
    monkeypatch.setattr(metrics.registry, "enabled", True)
    seen = record_attention_blocks(wrote)
    # 128 tokens are one 128-block: the three windowed layers visit it.
    assert seen == {"blocks_visited": [1, 1, 1], "blocks_causal": [1, 1, 1]}
    snapshot = metrics.registry.snapshot()
    assert snapshot["attention"] == seen
    text = metrics.prometheus_text(snapshot)
    assert 'hvd_tpu_attention_blocks{layer="2",kind="visited"} 1' in text
    assert "grid_" not in text
    # the full layer: no windowed layer's counters, and none of its own
    assert "layer_6" not in wrote


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern,
    the banded and the causal flash kernels (interpreted here) as in the
    benchmark.  The replicated weights stay equal and the loss of a repeated
    batch falls."""
    model = lm((0, 4), use_flash=True)
    trains_and_replicas_stay_equal(model, *seeded(model, seed=3))


# --- the shares add up to the uncut layer ------------------------------------

@pytest.mark.parametrize("n,experts", [(4, EXPERTS), (8, EXPERTS), (8, 128)])
def test_expert_shares_add_up_with_router_and_shared_expert_counted_once(
        n, experts):
    """The n shares' outputs each hold the shared expert; their sum holds it
    n times and the routed part once.  8 shares of 16 experts: the
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
    shared = reference.gated_mlp(flat, *(params[name]["kernel"] for name in (
        "shared_gate", "shared_up", "shared_down"))).reshape(u.shape)
    want = with_highest(reference.sparse_experts)(
        flat, params, num_experts=experts, expert_shard=(0, 1),
        experts_per_token=PER_TOKEN, weight_scale=SCALE)[0]
    close(sum(part - shared for part in parts) + shared,
          want.reshape(u.shape))


@pytest.mark.parametrize("n", [2, 8])
def test_vocabulary_slices_concatenate_to_the_uncut_head(n):
    """A sliced vocabulary is a smaller vocabulary: the i-th slice's model —
    its rows of the embedding, its columns of the head — gives, for ids of
    the slice, the uncut model's logits of those columns."""
    vocabulary_slices_concatenate(lm, n)


def test_the_banded_kernels_pass_the_builders_own_rows():
    rows = probe_rows(lambda q, k, v, scale: flash_attention(
        q, k, v, causal=True, window=WINDOW, sm_scale=scale, block_q=128,
        block_k=128, interpret=True))
    assert len(rows) == 4 and all(row["value"] < 1e-4 * row["limit"]
                                  for row in rows), rows


@pytest.mark.parametrize("wrong", [None, WINDOW + 1, WINDOW - 1],
                         ids=["causal_for_the_window", "one_key_too_wide",
                              "one_key_too_narrow"])
def test_a_wrong_window_fails_the_builders_rows(wrong):
    """A causal mask where the window is stated, and a window one key off
    either way, through the kernels themselves: each is over a limit of the
    cell's comparison, by a wide margin."""
    rows = probe_rows(lambda q, k, v, scale: flash_attention(
        q, k, v, causal=True, window=wrong, sm_scale=scale, block_q=128,
        block_k=128, interpret=True))
    over = [row for row in rows if row["value"] > 2 * row["limit"]]
    assert over, rows


@pytest.mark.parametrize("wrong", [
    dict(window_error=1), dict(window_error=-1), dict(causal_for_window=True)],
    ids=str)
def test_the_references_wrong_windows_are_other_programs(wrong):
    """The switches that make the wrong programs do change the loss."""
    params, batch, ((right, _), _) = seed_zero()
    other = with_highest(reference.loss)(params, batch,
                                         **reference_config(**wrong))
    assert abs(float(other - right)) > 1e-6


@pytest.mark.parametrize("dtype,least", [(jnp.float8_e4m3fn,
                                          reference.GRAD_RTOL),
                                         (jnp.bfloat16, 50 * 1e-4)],
                         ids=["float8_under_bfloat16",
                              "bfloat16_under_float32"])
def test_reference_refuses_the_next_precision_down(dtype, least):
    """The reference against itself with every matmul operand, and the q, k,
    v the attention reads, rounded a precision down: float8 where the
    configuration states bfloat16 is over the cell's gradient limit; bfloat16
    where float32 is stated (these tests, the rehearsal) is fifty times over
    what the float32 system is held to above."""
    params, batch, (_, exact) = seed_zero()
    _, rounded = reference_side(operand_dtype=dtype)(params, batch)
    assert float(relative_error(rounded, exact)) > least
