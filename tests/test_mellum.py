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

import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import compare, mellum_lm as reference
from horovod_tpu.models import (MoEConfig, RopeScaling, TransformerLM,
                                next_token_loss)
from horovod_tpu.models.transformer import (LayerOptions, MixerLayer, rope,
                                            yarn_frequencies)
from tests.test_hybrid import (both_ways, close, mixer_case, reference_sides,
                               relative_error, seeded, sides_agree,
                               system_loss, system_side)

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


reference_side = reference_sides(reference_config, reference.loss_and_chosen)


@functools.cache
def seed_zero():
    """`seeded(lm())` and the reference's ((loss, chosen), gradients) there:
    what every wrong program is measured against."""
    params, batch = seeded(lm())
    return params, batch, reference_side()(params, batch)


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
    layer = MixerLayer(kind, LayerOptions(
        n_heads=HEADS, dtype=jnp.float32, use_flash=use_flash, norm_eps=EPS,
        n_kv_heads=KV_HEADS, head_dim=HEAD_DIM, window=WINDOW, head_norm=True,
        rope_theta=THETA, rope_scaling=YARN, window_rope=(THETA, None)))
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
    sides_agree(system_side(model, params, batch),
                reference_side(expert_shard)(params, batch))


# --- recomputation -----------------------------------------------------------

# The pattern without recomputation and with one rotation must lower to what
# it did: the digest is of `jax.jit(grad).lower(...).as_text()` of this model
# at the parent commit of PR 49 (d415dca, jax 0.9.0).
PATTERN_DIGEST = (
    "8246fe8a4a63e5392e270049e7db160022ecff927512f0320775dbb05678602e")


def loss_and_wrote(model, params, batch):
    logits, wrote = model.apply({"params": params}, batch[0],
                                mutable=["intermediates", "router"])
    return next_token_loss(logits, batch[1]), wrote


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


def gradient_error(**wrong):
    """||g_wrong - g|| / ||g|| of the reference against itself."""
    params, batch, (_, right) = seed_zero()
    _, other = reference_side(**wrong)(params, batch)
    return float(relative_error(other, right))
