"""What Trinity-Mini adds — a sliding window in the flash kernels that skips
the blocks outside its band, `Attention` with a head width of its own, a
per-head q/k norm, an output gate and a window, a windowed layer kind beside
the full one, a post-norm in `MixerLayer`, an embedding multiplier — against
the plain float32 reference the benchmark keeps
(benchmark/reference/trinity_lm.py): a dense softmax under an explicit band
mask, a head at a time, the key/value head by index, a loop over the shard's
experts.  CPU, float32, seeded weights, small sizes; the kernels interpreted.

Tolerances: both sides are float32 and differ in the order of their sums
(online softmax over blocks against whole rows, grouped rows against masked
whole batches), so they agree to float32 rounding accumulated over a few
layers: 2e-5 of the largest value, 1e-4 for the whole model's gradients.
bfloat16 anywhere would read 1e-3 to 1e-2 and fail every case.
"""

import functools
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu.ops.attention as attn
from benchmark.reference import compare, trinity_lm as reference
from horovod_tpu.models import MoEConfig, TransformerLM
from horovod_tpu.models.transformer import (LAYER_KINDS, Attention,
                                            LayerOptions, MixerLayer)
from horovod_tpu.ops import (blockwise_attention, flash_attention,
                             mha_reference)
from horovod_tpu.ops.attention import mask_blocks
from tests.test_hybrid import (both_ways, close, mixer_case, reference_sides,
                               seeded)
from tests.test_flash_table import check_tables, pallas_calls
from tests.test_ops import _pallas_call_names

RTOL = 2e-5
VOCAB, HIDDEN, SEQ, HEADS, KV_HEADS, HEAD_DIM, D_FF = 256, 64, 128, 8, 2, 16, 96
WINDOW, THETA, EPS = 32, 10000.0, 1e-5
EXPERTS, PER_TOKEN, WIDTH, SCALE = 16, 4, 48, 2.826
# A published layer is attention and then an MLP or the experts: the leading
# dense layer, two windowed layers and a full one.
LAYERS = ("window_attention", "gated_mlp", "window_attention", "experts",
          "window_attention", "experts", "attention", "experts")


def moe(shard=(0, 1), row_bound=None, experts=EXPERTS):
    return MoEConfig(experts, PER_TOKEN, WIDTH, shard, row_bound, "sigmoid",
                     True, SCALE, shared_width=WIDTH)


def lm(expert_shard=(0, 1), use_flash=False, vocab=VOCAB):
    return TransformerLM(
        vocab_size=vocab, d_model=HIDDEN, n_heads=HEADS, d_ff=D_FF,
        dtype=jnp.float32, use_flash=use_flash, norm_eps=EPS,
        moe=moe(expert_shard), layers=LAYERS, n_kv_heads=KV_HEADS, rope=False,
        head_dim=HEAD_DIM, window=WINDOW, head_norm=True, attn_gate=True,
        post_norm=True, embed_scale=HIDDEN ** 0.5)


def reference_config(expert_shard=(0, 1), **more):
    return dict(layers=LAYERS, embed_scale=HIDDEN ** 0.5, window=WINDOW,
                rope_theta=THETA, norm_eps=EPS, num_experts=EXPERTS,
                experts_per_token=PER_TOKEN, expert_shard=expert_shard,
                weight_scale=SCALE, **more)


reference_side = reference_sides(reference_config, reference.loss_and_chosen)


@functools.cache
def seed_zero():
    """`seeded(lm())` and the reference's ((loss, chosen), gradients) there:
    what every wrong program is measured against."""
    params, batch = seeded(lm())
    return params, batch, reference_side()(params, batch)


# --- the banded kernels ------------------------------------------------------

def masked_softmax(q, k, v, window):
    """Written out here, a third time: query t sees keys s, 0 <= t - s <
    window."""
    seq = q.shape[2]
    t, s = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = (s <= t) & (t - s < window)
    scores = jnp.einsum("bhqe,bhke->bhqk", q, k,
                        precision="highest") * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bhke->bhqe", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v, precision="highest")


def qkv(seq, d, seed=0, heads=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(key, (1, heads, seq, d)) for key in keys]


def plan_of(monkeypatch, mode, block):
    monkeypatch.setattr(attn, "_bwd_plan", lambda q_len, d, bq, bk, bh=1:
                        (mode, min(bq, block), min(bk, block)))


# seq, window, block_q, block_k, head width: windows that divide the block,
# that do not, of one key, of every key, past the sequence; two head widths;
# blocks of two sizes.
BANDS = [(512, 128, 128, 128, 64), (512, 256, 128, 128, 128),
         (512, 200, 128, 128, 64), (512, 77, 256, 128, 128),
         (512, 300, 128, 256, 64), (384, 1, 128, 128, 64),
         (384, 384, 128, 128, 64), (384, 1000, 128, 128, 128)]


@pytest.mark.parametrize("path", ["combined", "split", "blockwise"])
@pytest.mark.parametrize("seq,window,block_q,block_k,d", BANDS, ids=str)
def test_banded_attention_is_the_masked_softmax(monkeypatch, seq, window,
                                                block_q, block_k, d, path):
    q, k, v, mix = qkv(seq, d, seed=window)
    if path == "blockwise":
        def banded(q, k, v):
            return blockwise_attention(q, k, v, causal=True, window=window,
                                       block_size=block_k)
    else:
        plan_of(monkeypatch, path, 128)

        def banded(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=True)

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    def plain(q, k, v):
        return masked_softmax(q, k, v, window)

    close(banded(q, k, v), plain(q, k, v))
    close(mha_reference(q, k, v, causal=True, window=window), plain(q, k, v))
    got = jax.grad(total(banded), (0, 1, 2))(q, k, v)
    want = jax.grad(total(plain), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        # A window of one key leaves q and k no gradient at all: rounding
        # against the values' size (1), not against zero.
        np.testing.assert_allclose(
            g, w, atol=RTOL * max(1.0, float(jnp.abs(w).max())), rtol=0)


def test_a_ragged_length_takes_the_blockwise_path():
    q, k, v, mix = qkv(200, 64, seed=3)

    def banded(q, k, v):
        return flash_attention(q, k, v, causal=True, window=50,
                               interpret=True)

    program = jax.make_jaxpr(jax.grad(lambda *a: (banded(*a) * mix).sum(),
                                      (0, 1, 2)))(q, k, v)
    assert _pallas_call_names(program.jaxpr) == []
    close(banded(q, k, v), masked_softmax(q, k, v, 50))
    assert mask_blocks(200, 64, causal=True, window=50) is None


@pytest.mark.parametrize("window,causal", [(0, True), (-3, True), (64, False)])
def test_a_window_wants_causal_and_a_key(window, causal):
    q, k, v, _ = qkv(128, 64)
    for fn in (flash_attention, blockwise_attention):
        with pytest.raises(ValueError, match="window"):
            fn(q, k, v, causal=causal, window=window)


def touching(seq, window, block_q, block_k):
    """By brute force over positions: the (query block, key block) pairs that
    hold at least one (t, s) with 0 <= t - s < window."""
    t, s = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (s <= t) & (t - s < window)
    return {(i, j) for i in range(seq // block_q)
            for j in range(seq // block_k)
            if seen[i * block_q:(i + 1) * block_q,
                    j * block_k:(j + 1) * block_k].any()}


# The cell's shape in both block sizes (70 of 136 and 21 of 36), a window that
# cuts blocks, unequal blocks both ways, a window of one key.
WALKS = [(8192, 2048, 512, 512), (8192, 2048, 1024, 1024),
         (2048, 700, 256, 512), (2048, 700, 512, 256), (1024, 1, 128, 128),
         (1024, 129, 128, 128)]


@pytest.mark.parametrize("seq,window,block_q,block_k", WALKS, ids=str)
def test_the_kernels_visit_exactly_the_bands_blocks(seq, window, block_q,
                                                    block_k):
    """Every kernel's table, walked as the grid walks it, queries outer
    (forward, dq) and keys outer (dk/dv, the combined backward): the steps
    are the block pairs that touch the band, once each and no other, so a key
    (or query) block is never fetched for a pair outside the band; a row's
    steps are contiguous with `first` and `last` set once; and the count is
    what `mask_blocks` and the layer's counter report."""
    want = touching(seq, window, block_q, block_k)
    assert check_tables(attn.Causal(window), seq, block_q, block_k) == want
    causal = len(touching(seq, seq, block_q, block_k))
    assert mask_blocks(seq, 128, causal=True, window=window, block_q=block_q,
                       block_k=block_k) == (len(want), causal)
    # The grids are the bands', not the sequence's.
    shape = jax.ShapeDtypeStruct((1, 2, seq, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=True).astype(jnp.float32).sum()

    grids = pallas_calls(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        shape, shape, shape).jaxpr)
    assert grids["hvd_flash_fwd_window"] == (2, len(want))
    # The backward re-plans its blocks for the shape (`_bwd_plan`).
    _, plan_q, plan_k = attn._bwd_plan(seq, 128, block_q, block_k, 2)
    plan_steps = len(touching(seq, window, plan_q, plan_k))
    backward = {name: grid for name, grid in grids.items() if "bwd" in name}
    assert backward and all(name.endswith("_window") for name in backward)
    for name, grid in backward.items():
        assert grid == (2, plan_steps), (name, grid)


def test_the_cells_counts():
    assert mask_blocks(8192, 128, causal=True, window=2048) == (21, 36)
    assert mask_blocks(8192, 128, causal=True, window=2048, block_q=512,
                       block_k=512) == (70, 136)
    assert mask_blocks(8192, 128, causal=True, window=8192) == (36, 36)


@pytest.mark.parametrize("plan", ["combined", "split"])
def test_no_window_and_a_window_past_the_sequence_are_the_causal_program(
        monkeypatch, plan):
    plan_of(monkeypatch, plan, 128)
    shape = jax.ShapeDtypeStruct((1, 2, 512, 64), jnp.bfloat16)

    def program(**window):
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=True,
                                   **window).astype(jnp.float32).sum()
        return jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(shape, shape, shape)

    causal = program()
    assert str(program(window=512)) == str(causal) \
        == str(program(window=None))
    names = set(_pallas_call_names(causal.jaxpr))
    assert names and not any(name.endswith("_window") for name in names)
    assert set(_pallas_call_names(program(window=511).jaxpr)) \
        == {name + "_window" for name in names}


# --- the layers --------------------------------------------------------------

def attention(window, rope, use_flash):
    return Attention(HEADS, jnp.float32, use_flash=use_flash, norm_eps=EPS,
                     n_kv_heads=KV_HEADS, rope=rope, head_dim=HEAD_DIM,
                     window=window, head_norm=True, gate=True)


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("window,rope", [(WINDOW, True), (None, False)],
                         ids=["windowed_rotated", "full_unrotated"])
def test_attention_is_the_reference(window, rope, use_flash):
    """A head width (16) that is not hidden / heads (8), a norm a head with one
    scale for q and one for k, the gate, 8 query heads on 2 key/value heads."""
    layer = attention(window, rope, use_flash)
    u, params, mix = mixer_case(layer, seed=3)
    assert params["q_kernel"].shape == (HIDDEN, HEADS, HEAD_DIM)
    assert params["gate_kernel"].shape == (HIDDEN, HEADS, HEAD_DIM)
    assert params["kv_kernel"].shape == (HIDDEN, 2, KV_HEADS, HEAD_DIM)
    assert params["q_head_norm_scale"].shape == (HEAD_DIM,)
    both_ways(lambda p, u: layer.apply({"params": p}, u),
              lambda p, u: reference.attention_layer(
                  u, p, window=window, rope_theta=THETA, norm_eps=EPS),
              u, params, mix)


@pytest.mark.parametrize("kind", ["gated_mlp", "window_attention",
                                  "attention"])
def test_a_post_norm_layer_is_the_reference(kind):
    layer = MixerLayer(kind, LayerOptions(
        n_heads=HEADS, dtype=jnp.float32, use_flash=False, norm_eps=EPS,
        n_kv_heads=KV_HEADS, rope=False, d_ff=D_FF, head_dim=HEAD_DIM,
        window=WINDOW, head_norm=True, attn_gate=True, post_norm=True))
    x, params, mix = mixer_case(layer, seed=5)
    assert set(params) == {"norm", "mixer", "post_norm"}
    config = reference_config()
    config = {name: config[name] for name in config
              if name not in ("layers", "embed_scale")}
    both_ways(lambda p, x: layer.apply({"params": p}, x),
              lambda p, x: reference.layer(x, p, kind, **config)[0],
              x, params, mix)


def test_the_kinds_and_the_defaults():
    assert LAYER_KINDS["window_attention"].mixer \
        is LAYER_KINDS["attention"].mixer is Attention
    with pytest.raises(ValueError, match="window="):
        MixerLayer("window_attention", LayerOptions(n_heads=HEADS)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, SEQ, HIDDEN)))
    plain = MixerLayer("attention", LayerOptions(
        n_heads=HEADS, dtype=jnp.float32, use_flash=False))
    shapes = jax.eval_shape(lambda: plain.init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ, HIDDEN)))["params"])
    assert set(shapes) == {"norm", "mixer"}
    assert set(shapes["mixer"]) == {"qkv_kernel", "o_kernel"}
    assert shapes["mixer"]["o_kernel"].shape == (HEADS, HIDDEN // HEADS,
                                                 HIDDEN)


# --- the reference refuses the wrong programs --------------------------------

def probe_rows(kernel, window=WINDOW, seq=256):
    """The builder's kernel comparison at a small size: `kernel` against the
    reference's masked softmax under the sharpened scale."""
    sharp = reference.SHARP_SCALE * HEAD_DIM ** -0.5
    return compare.kernel_against(
        lambda q, k, v: kernel(q, k, v, sharp),
        lambda q, k, v: reference.band_attention(q, k, v, window=window,
                                                 sm_scale=sharp),
        (1, 4, seq, HEAD_DIM), jnp.float32, 7, reference.WINDOW_FWD_ATOL,
        reference.WINDOW_GRAD_RTOL, "window_flash_")
