"""What Mellum2 adds — a rotation that takes a table of scaled frequencies
(YaRN) with a factor on cosine and sine, a rotation a layer kind in one model
(the full layers' and the windowed layers'), and pattern entries that compute
their forward pass again in the backward pass — against the plain float32
reference the benchmark keeps (benchmark/reference/mellum_lm.py): a dense
softmax under an explicit mask, a head at a time, the key/value head by index,
a loop over the shard's experts, the frequency table a pair at a time in
Python floats.  CPU, float32, seeded weights, small sizes; the kernels
interpreted.

Tolerances: both sides are float32 and differ in the order of their sums
(online softmax over blocks against whole rows, grouped rows against masked
whole batches, a rotation by a product with a signed permutation against one
by slices), so they agree to float32 rounding accumulated over a few layers:
2e-5 of the largest value, 1e-4 for the whole model's gradients.  bfloat16
anywhere (a bfloat16 softmax among them) reads 1e-3 to 1e-2, and a full layer
without its attention factor 1e-2 and more: both fail every case, and the last
tests of this file say by how much.  With recomputation on, loss, gradients
and counters are held to the model's without it EXACTLY: the same operations
in the same order.
"""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark import ops_count_mellum, ops_count_trinity
from benchmark.reference import compare, mellum_lm as reference
from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models import (MoEConfig, RopeScaling, TransformerLM,
                                next_token_loss, record_attention_blocks,
                                record_expert_rows)
from horovod_tpu.models.transformer import (MixerLayer, SparseExperts, rope,
                                            yarn_frequencies)
from horovod_tpu.ops import flash_attention
from horovod_tpu.ops.attention import _bwd_plan, flash_grid_steps, mask_blocks
from tests.test_hybrid import (both_ways, close, mixer_case, seeded,
                               system_loss, trees_close, with_highest)
from tests.test_ops import _pallas_call_names

RTOL = 2e-5
VOCAB, HIDDEN, SEQ, HEADS, KV_HEADS, HEAD_DIM = 256, 64, 128, 8, 2, 16
WINDOW, THETA, EPS = 32, 500000.0, 1e-6
EXPERTS, PER_TOKEN, WIDTH = 16, 4, 48
# YaRN at the tests' head width: 8 pairs, the original positions 64 of the
# 128 the tests run, so that the ramp is 0 at pair 0, a half at pair 1 and 1
# from pair 2 on (low 0, high 2): neither all 0 nor all 1.
YARN = RopeScaling(16.0, 64, 32.0, 1.0, 1.2772588722239782)
YARN_NUMBERS = dict(zip(("factor", "original_positions", "beta_fast",
                         "beta_slow", "attention_factor"), YARN))
# Three published layers, both kinds: windowed, windowed, full, each attention
# and then the experts.
KINDS = ("window_attention", "window_attention", "attention")
LAYERS = tuple(entry for kind in KINDS for entry in (kind, "experts"))


def moe(shard=(0, 1), row_bound=None, experts=EXPERTS):
    return MoEConfig(experts, PER_TOKEN, WIDTH, shard, row_bound,
                     renormalize=True)


def lm(expert_shard=(0, 1), use_flash=False, vocab=VOCAB, recompute=False,
       dtype=jnp.float32, **more):
    return TransformerLM(
        vocab_size=vocab, d_model=HIDDEN, n_heads=HEADS, dtype=dtype,
        logits_dtype=dtype, use_flash=use_flash, norm_eps=EPS,
        moe=moe(expert_shard), layers=LAYERS, n_kv_heads=KV_HEADS,
        head_dim=HEAD_DIM, window=WINDOW, head_norm=True, rope_theta=THETA,
        rope_scaling=YARN, window_rope=(THETA, None), recompute=recompute,
        **more)


def reference_config(expert_shard=(0, 1), **more):
    return dict(layers=KINDS, window=WINDOW, rope_theta=THETA,
                yarn=YARN_NUMBERS, norm_eps=EPS, num_experts=EXPERTS,
                experts_per_token=PER_TOKEN, expert_shard=expert_shard,
                **more)


# --- the rotation ------------------------------------------------------------

PUBLISHED = RopeScaling(16, 8192, 32, 1, 1.2772588722239782)


def test_the_yarn_table_at_the_published_numbers():
    """theta 500,000, head 128, 8,192 original positions, factor 16, beta 32
    and 1: c reads 18.08 and 34.98, so low 18 and high 35; pair 17 keeps its
    plain frequency, pair 35 has it divided by 16, the pairs between lie
    strictly between; the table has nothing to do with a sequence length."""
    table, low, high = yarn_frequencies(500000.0, 64, PUBLISHED)
    plain = 500000.0 ** (-np.arange(64) / 64)
    assert (low, high) == (18, 35)
    assert table[17] == plain[17] and table[18] == plain[18]
    np.testing.assert_allclose(table[35], plain[35] / 16, rtol=1e-15)
    np.testing.assert_allclose(table[36:], plain[36:] / 16, rtol=1e-15)
    np.testing.assert_array_equal(table[:18], plain[:18])
    between = table[19:35]
    assert ((between < plain[19:35]) & (between > plain[19:35] / 16)).all()
    np.testing.assert_allclose(table[26], plain[26] * (9 / 17 + 8 / 17 / 16),
                               rtol=1e-12)
    # The reference's table, a pair at a time in Python floats, is the same.
    want, want_low, want_high = reference.yarn_table(
        500000.0, 64, factor=16, original_positions=8192, beta_fast=32,
        beta_slow=1)
    assert (want_low, want_high) == (18, 35)
    np.testing.assert_allclose(table, want, rtol=1e-14)


def test_the_attention_factor_is_the_sources_and_the_default():
    assert PUBLISHED.magnitude == 1.2772588722239782
    assert RopeScaling(16, 8192).magnitude \
        == pytest.approx(0.1 * math.log(16) + 1, rel=1e-15)
    assert RopeScaling(16, 8192).magnitude \
        == pytest.approx(1.2772588722239782, rel=1e-15)


def test_the_tests_own_ramp_is_neither_all_0_nor_all_1():
    table, low, high = yarn_frequencies(THETA, HEAD_DIM // 2, YARN)
    plain = THETA ** (-np.arange(8) / 8)
    assert (low, high) == (0, 2)
    np.testing.assert_allclose(table / plain,
                               [1, (1 + 1 / 16) / 2] + [1 / 16] * 6)


@pytest.mark.parametrize("rotary_dim", [None, 8])
def test_a_scaled_rotation_is_the_references(rotary_dim):
    """Forward and backward, the factor on cosine and sine: a row's norm is
    the factor times what it was, and the cotangent turns back by the same
    table with the same factor."""
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(keys[0], (2, 4, SEQ, HEAD_DIM))
    mix = jax.random.normal(keys[1], x.shape)
    positions = jnp.arange(SEQ)
    turning = rotary_dim or HEAD_DIM

    def system(x):
        return rope(x, positions, THETA, -2, rotary_dim, YARN)

    def plain(x):
        turned = reference.rotary(x[..., :turning], THETA, YARN_NUMBERS)
        return jnp.concatenate([turned, x[..., turning:]], axis=-1)

    close(system(x), plain(x))
    close(jax.grad(lambda x: (system(x) * mix).sum())(x),
          jax.grad(lambda x: (plain(x) * mix).sum())(x))
    np.testing.assert_allclose(
        jnp.linalg.norm(system(x)[..., :turning], axis=-1),
        YARN.magnitude * jnp.linalg.norm(x[..., :turning], axis=-1),
        rtol=1e-5)


# `rope` with a base alone must lower to what it did before it took a table:
# every rotated cell of the benchmark runs it.  The digests are of
# `jax.jit(value_and_grad).lower(...).as_text()` at the parent commit of PR 49
# (d415dca, jax 0.9.0).
BASE_ONLY_DIGESTS = {
    None: "4c78c2898a2c30106796de1479a8845ea3db0f99f31a26952d4443365167221b",
    8: "375b8b2f2fc6d7915d8e2cb395d1c47014022a638aa5c0eaf5fd2715655a1fbb"}


def rope_text(rotary_dim, *scaling):
    x = jax.ShapeDtypeStruct((2, 4, 128, 16), jnp.bfloat16)
    positions = jax.ShapeDtypeStruct((128,), jnp.int32)

    def total(x, positions):
        return rope(x, positions, 500000.0, -2, rotary_dim,
                    *scaling).astype(jnp.float32).sum()

    return jax.jit(jax.value_and_grad(total)).lower(x, positions).as_text()


@pytest.mark.parametrize("rotary_dim", list(BASE_ONLY_DIGESTS))
def test_base_only_rope_lowers_to_the_parents_text(rotary_dim):
    text = rope_text(rotary_dim)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == BASE_ONLY_DIGESTS[rotary_dim]
    assert rope_text(rotary_dim, None) == text
    assert rope_text(rotary_dim, YARN) != text


# --- the layers --------------------------------------------------------------

@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("kind", ["window_attention", "attention"])
def test_an_attention_layer_of_either_kind_is_the_reference(kind, use_flash):
    """The windowed layer at the plain frequencies, the full one at YaRN's
    with its factor, one `MixerLayer` configuration for both."""
    layer = MixerLayer(kind, HEADS, jnp.float32, use_flash, norm_eps=EPS,
                       n_kv_heads=KV_HEADS, head_dim=HEAD_DIM, window=WINDOW,
                       head_norm=True, rope_theta=THETA, rope_scaling=YARN,
                       window_rope=(THETA, None))
    x, params, mix = mixer_case(layer, seed=3)
    windowed = kind == "window_attention"

    def plain(p, x):
        u = reference.rms_norm(x, p["norm"]["scale"], EPS)
        return x + reference.attention_layer(
            u, p["mixer"], window=WINDOW if windowed else None,
            rope_theta=THETA, yarn=None if windowed else YARN_NUMBERS,
            norm_eps=EPS)

    both_ways(lambda p, x: layer.apply({"params": p}, x), plain, x, params,
              mix)


def test_unset_the_windowed_layers_turn_as_the_full_ones():
    """One `rope_theta` for both kinds is the case where the two rotations
    agree: `window_rope` unset is `window_rope` given the same numbers, and
    the model's program before there was a second rotation."""
    def text(**rotation):
        model = lm().clone(rope_scaling=None, **rotation)
        tokens = jnp.zeros((1, SEQ), jnp.int32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])
        return jax.jit(jax.grad(lambda p: system_loss(
            model, p, (tokens, tokens)))).lower(params).as_text()

    assert text(window_rope=None) == text(window_rope=(THETA, None))
    assert text(window_rope=None) != text(window_rope=(10000.0, None))


# --- the model ---------------------------------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["kept", "recomputed"])
@pytest.mark.parametrize("expert_shard", [(0, 1), (1, 4)])
def test_mellum_lm_loss_and_gradients_are_the_references(expert_shard,
                                                         recompute):
    model = lm(expert_shard, recompute=recompute)
    params, batch = seeded(model, seed=expert_shard[1])
    config = reference_config(expert_shard)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p: system_loss(model, p, batch)))(params)
    want, want_grads = with_highest(jax.value_and_grad(
        lambda p: reference.loss(p, batch, **config)))(params)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    trees_close(got_grads, want_grads, 1e-4)
    _, wrote = model.apply({"params": params}, batch[0],
                           mutable=["intermediates"])
    chose = jnp.stack([wrote["intermediates"][f"layer_{i}"]["mixer"][
        "chosen_experts"][0] for i, kind in enumerate(LAYERS)
        if kind == "experts"])
    want = with_highest(reference.loss_and_chosen)(params, batch, **config)[1]
    np.testing.assert_array_equal(jnp.sort(chose, -1), jnp.sort(want, -1))


# --- recomputation -----------------------------------------------------------

# The pattern without recomputation and with one rotation must lower to what
# it did: the digest is of `jax.jit(grad).lower(...).as_text()` of this model
# at the parent commit of PR 49 (d415dca, jax 0.9.0).
PATTERN_DIGEST = (
    "8246fe8a4a63e5392e270049e7db160022ecff927512f0320775dbb05678602e")


def test_unset_the_pattern_lowers_to_the_parents_program():
    model = TransformerLM(
        vocab_size=256, d_model=64, n_heads=8, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=False, norm_eps=1e-6,
        moe=MoEConfig(16, 4, 48, (0, 4), 1.5, renormalize=True),
        layers=("window_attention", "experts", "attention", "experts"),
        n_kv_heads=2, head_dim=16, window=32, head_norm=True,
        rope_theta=500000.0)
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])

    def loss(params, tokens):
        logits, _ = model.apply({"params": params}, tokens,
                                mutable=["intermediates"])
        return next_token_loss(logits, tokens)

    text = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PATTERN_DIGEST
    model = model.clone(recompute=True)
    again = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    assert again != text and "optimization_barrier" in again


def loss_and_wrote(model, params, batch):
    logits, wrote = model.apply({"params": params}, batch[0],
                                mutable=["intermediates", "router"])
    return next_token_loss(logits, batch[1]), wrote


@pytest.mark.parametrize("use_flash", [False, True])
def test_recomputed_layers_give_the_same_loss_gradients_and_counters(
        use_flash):
    """Bit for bit: the same operations in the same order inside a layer.
    What the layers sow — the router's statistics, the experts' rows, the
    attention's tiles — reads the same, once each and not twice."""
    kept, again = (lm((0, 4), use_flash, recompute=flag)
                   for flag in (False, True))
    params, batch = seeded(kept, seed=11)
    shapes = jax.eval_shape(lambda: again.init(
        jax.random.PRNGKey(0), batch[0])["params"])
    assert jax.tree.map(jnp.shape, params) \
        == jax.tree.map(lambda s: s.shape, shapes)
    (loss, wrote), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_and_wrote(kept, p, batch), has_aux=True))(params)
    (loss_2, wrote_2), grads_2 = jax.jit(jax.value_and_grad(
        lambda p: loss_and_wrote(again, p, batch), has_aux=True))(params)
    assert float(loss) == float(loss_2)
    assert jax.tree.structure(wrote) == jax.tree.structure(wrote_2)
    for one, two in zip(jax.tree.leaves((grads, wrote)),
                        jax.tree.leaves((grads_2, wrote_2))):
        np.testing.assert_array_equal(one, two)
    every = jax.tree.leaves(wrote_2, is_leaf=lambda v: isinstance(v, tuple))
    assert every and all(len(sown) == 1 for sown in every)
    assert record_expert_rows(wrote["intermediates"]) \
        == record_expert_rows(wrote_2["intermediates"])
    assert record_attention_blocks(wrote["intermediates"]) \
        == record_attention_blocks(wrote_2["intermediates"])


def test_a_recomputed_layer_keeps_its_kernels_outputs_and_its_routing():
    """A recomputing layer keeps its input, its flash forward kernel's
    outputs, its grouped products' and its router's decision
    (`_kept_by_a_recomputing_layer`): the gradient's program holds every
    kernel, every grouped product (9 an expert layer) and every `top_k` as
    often as the unrecomputed model's — rows kept in one pass's order are
    never read in another's — while the projections, the rotations and the
    rows' movement are in it once more, under JAX's own marker inside the
    backward phase."""
    def program(recompute):
        model = lm((0, 4), True, recompute=recompute, dtype=jnp.bfloat16)
        tokens = jnp.zeros((1, SEQ), jnp.int32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])
        grad = jax.make_jaxpr(jax.grad(
            lambda p: system_loss(model, p, (tokens, tokens))))(params)
        names = _pallas_call_names(grad.jaxpr)
        return {name: names.count(name) for name in set(names)}, str(grad)

    kept, kept_text = program(False)
    again, again_text = program(True)
    assert kept == again == {
        "hvd_flash_fwd_window": 2, "hvd_flash_bwd_window": 2,
        "hvd_flash_fwd": 1, "hvd_flash_bwd": 1}
    assert kept_text.count(" ragged_dot_general[") == 27
    assert again_text.count(" ragged_dot_general[") == 27
    assert kept_text.count(" top_k[") == again_text.count(" top_k[") == 3
    assert again_text.count(" dot_general[") > kept_text.count(" dot_general[")
    model = lm((0, 4), recompute=True)
    params, batch = seeded(model)
    text = jax.jit(jax.grad(lambda p: system_loss(model, p, batch))).lower(
        params).compile().as_text()
    marked = [line for line in text.splitlines()
              if "rematted_computation" in line]
    assert marked and all("transpose(" in line for line in marked)
    assert any("hvd_attn_rotate" in line for line in marked)
    assert any("hvd_moe_experts" in line for line in marked)
    assert not any("hvd_lm_head" in line for line in marked)


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern,
    recomputed, the banded and the causal flash kernels (interpreted here) as
    in the benchmark.  The replicated weights stay equal and the loss of a
    repeated batch falls, to what the unrecomputed model's falls to."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))
    tx = optax.adamw(1e-2)
    ends = []
    for recompute in (True, False):
        model = lm((0, 4), use_flash=True, recompute=recompute)
        params, batch = seeded(model, seed=3)
        step = build_train_step(
            lambda p, b: system_loss(model, p, b), tx, mesh, axis_name="hvd",
            batch_spec=(P("hvd"), P("hvd")))
        state = (params, tx.init(params))
        losses = []
        for _ in range(4):
            *state, loss = step(*state, batch)
            losses.append(float(loss))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        ends.append(losses)
        for leaf in jax.tree.leaves(state[0]):
            first, second = (np.asarray(s.data)
                             for s in leaf.addressable_shards)
            np.testing.assert_array_equal(first, second)
    assert ends[0] == ends[1]


# --- the shares add up to the uncut layer ------------------------------------

@pytest.mark.parametrize("n,experts", [(4, EXPERTS), (4, 64), (8, 64)])
def test_expert_shares_add_up_to_the_uncut_layer(n, experts):
    """The n shares' outputs — nothing is computed on every chip alike here:
    no shared expert — sum to the uncut reference layer.  4 shares of 16 of
    64 experts: the deployment's count."""
    whole = SparseExperts(moe(experts=experts), jnp.float32)
    u, params, _ = mixer_case(whole, n)
    local = experts // n
    parts = []
    for i in range(n):
        held = slice(i * local, (i + 1) * local)
        share = dict(params, **{name: params[name][held] for name in (
            "gate_kernel", "up_kernel", "down_kernel")})
        parts.append(jax.jit(SparseExperts(
            moe((i, n), experts=experts), jnp.float32).apply)(
                {"params": share}, u))
    want = with_highest(reference.sparse_experts)(
        u.reshape(-1, HIDDEN), params, num_experts=experts,
        expert_shard=(0, 1), experts_per_token=PER_TOKEN)[0]
    close(sum(parts), want.reshape(u.shape))


def test_vocabulary_slices_concatenate_to_the_uncut_head():
    """A sliced vocabulary is a smaller vocabulary: the i-th quarter's model
    gives, for ids of the slice, the uncut model's logits of its columns."""
    model = lm()
    params, _ = seeded(model)
    n, rows = 4, VOCAB // 4
    whole, sliced = jax.jit(model.apply), jax.jit(lm(vocab=rows).apply)
    for i in range(n):
        ids = jax.random.randint(jax.random.PRNGKey(9), (1, SEQ), 0, rows)
        held = slice(i * rows, (i + 1) * rows)
        share = dict(params,
                     embed={"embedding": params["embed"]["embedding"][held]},
                     lm_head_kernel=params["lm_head_kernel"][:, held])
        close(sliced({"params": share}, ids),
              whole({"params": params}, ids + i * rows)[..., held])


# --- the cell's shapes, off the kernels' own tables --------------------------

def test_the_cells_plan_and_counts():
    """16,384 rows of head 128 leave the combined backward for the split pair
    in 1,024-blocks; the 1,024-key band is two tiles wide: 31 of the causal
    mask's 136 tile pairs, for 1,024 x 1,025 / 2 + 15,360 x 1,024 of its
    16,384 x 16,385 / 2 exact pairs (an eighth)."""
    assert _bwd_plan(16384, 128, 1024, 1024, 32) == ("split", 1024, 1024)
    assert _bwd_plan(8192, 128, 1024, 1024, 32)[0] == "combined"
    assert mask_blocks(16384, 128, causal=True, window=1024) == (31, 136)
    grids = flash_grid_steps(16384, 128, 32, causal=True, window=1024)
    assert grids == {name: (31, 31, 256) for name in (
        "hvd_flash_fwd_window", "hvd_flash_bwd_dkdv_window",
        "hvd_flash_bwd_dq_window")}
    assert set(flash_grid_steps(16384, 128, 32, causal=True)) == {
        "hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"}
    band = ops_count_trinity.band_pairs(16384, 1024)
    assert band == 1024 * 1025 // 2 + 15360 * 1024
    assert 0.12 < band / ops_count_trinity.band_pairs(16384) < 0.13


def test_the_counts_know_of_recomputation_where_they_should():
    """The model's work (`total`, what `mfu_pct` reads) does not; what the
    compiler is compared with runs the projections and the router a fourth
    time, the grouped products (kept) and the head three."""
    shape = {"hidden": 2304, "vocab": 24576, "window_layers": 3,
             "full_layers": 1,
             "attention": {"heads": 32, "kv_heads": 4, "head_dim": 128,
                           "window": 1024},
             "experts": {"num_experts": 64, "expert_width": 896}}
    kept = ops_count_mellum.mellum_lm_train_ops_per_token(
        shape, 16384, 2.0, 3.0)
    again = ops_count_mellum.mellum_lm_train_ops_per_token(
        shape, 16384, 2.0, 3.0, recompute=True)
    assert again["total"] == kept["total"]
    head = 6 * 2304 * 24576
    assert again["head"] == kept["head"] == head
    grouped = 4 * 6 * 3 * 2304 * 896 * 3.0          # every buffer row, dense
    np.testing.assert_allclose(
        (again["visible_to_compiler"] - head - grouped) * 3,
        (kept["visible_to_compiler"] - head - grouped) * 4)
    # 192 M multiply-adds a token in the products, as the issue counted.
    products = (kept["total"] - kept["attention"]) / 6
    assert 190e6 < products < 194e6
    assert ops_count_mellum.flash_kernel(16384, 32, 128, 3, 1024) \
        == ops_count_trinity.flash_kernel(16384, 32, 128, 3, 1024)


# --- the reference refuses the wrong programs --------------------------------

def probe_rows(kernel, window, seq=256):
    """The builder's kernel comparison at a small size: `kernel` against the
    reference's masked softmax under the sharpened scale."""
    sharp = reference.SHARP_SCALE * HEAD_DIM ** -0.5
    return compare.kernel_against(
        lambda q, k, v: kernel(q, k, v, sharp),
        lambda q, k, v: reference.band_attention(q, k, v, window=window,
                                                 sm_scale=sharp),
        (1, 4, seq, HEAD_DIM), jnp.float32, 7, reference.FLASH_FWD_ATOL,
        reference.FLASH_GRAD_RTOL, "flash_")


@pytest.mark.parametrize("window", [WINDOW, None], ids=["band", "causal"])
def test_the_kernels_pass_the_builders_own_rows(window):
    rows = probe_rows(lambda q, k, v, scale: flash_attention(
        q, k, v, causal=True, window=window, sm_scale=scale, block_q=128,
        block_k=128, interpret=True), window)
    assert len(rows) == 4 and all(row["value"] < 1e-4 * row["limit"]
                                  for row in rows), rows


@pytest.mark.parametrize("wrong", [None, WINDOW + 1, WINDOW - 1],
                         ids=["causal_for_the_window", "one_key_too_wide",
                              "one_key_too_narrow"])
def test_a_wrong_window_fails_the_builders_rows(wrong):
    rows = probe_rows(lambda q, k, v, scale: flash_attention(
        q, k, v, causal=True, window=wrong, sm_scale=scale, block_q=128,
        block_k=128, interpret=True), WINDOW)
    over = [row for row in rows if row["value"] > 2 * row["limit"]]
    assert over, rows


def gradient_error(params, batch, **wrong):
    """||g_wrong - g|| / ||g|| of the reference against itself."""
    right, other = (with_highest(jax.grad(lambda p: reference.loss(
        p, batch, **reference_config(**config))))(params)
        for config in ({}, wrong))
    norm = optax.global_norm
    return float(norm(jax.tree.map(jnp.subtract, other, right))
                 / norm(right))


@pytest.mark.parametrize("wrong", [
    dict(drop="attention_factor"), dict(drop="yarn"), dict(drop="window"),
    dict(drop="renormalize"), dict(window_error=1), dict(window_error=-1)],
    ids=lambda wrong: "_".join(map(str, wrong.values())))
def test_the_references_wrong_programs_are_other_programs(wrong):
    """A full layer without its attention factor, or at the plain
    frequencies; a windowed layer that sees every key, or one key more or
    fewer; weights that are not renormalised: each is a hundred times and
    more over what these tests hold the system's gradients to (1e-4)."""
    params, batch = seeded(lm())
    assert gradient_error(params, batch, **wrong) > 1e-2


@pytest.mark.parametrize("dtype,least", [(jnp.float8_e4m3fn,
                                          reference.GRAD_RTOL),
                                         (jnp.bfloat16, 50 * 1e-4)],
                         ids=["float8_under_bfloat16",
                              "bfloat16_under_float32"])
def test_reference_refuses_the_next_precision_down(dtype, least):
    """The reference against itself with every matmul operand, and the q, k,
    v the attention reads, rounded a precision down: float8 where the
    configuration states bfloat16 is over the cell's gradient limit; bfloat16
    where float32 is stated (these tests, the rehearsal) — a bfloat16 softmax
    is the least of it — is fifty times over what the float32 system is held
    to above."""
    params, batch = seeded(lm())
    assert gradient_error(params, batch, operand_dtype=dtype) > least
