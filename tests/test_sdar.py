"""What SDAR's block-diffusion training adds — a block mask over `[clean;
noised]` rows in the flash kernels, which walk only the tiles it touches (two
runs of key tiles a query tile, not one band), `Attention` and `TransformerLM`
with `block_diffusion=`, a head over the noised half alone and
`masked_diffusion_loss` — against the plain float32 reference the benchmark
keeps (benchmark/reference/sdar_lm.py): a dense softmax under an explicit
mask, a head at a time, the key/value head by index, a loop over the shard's
experts.  CPU, float32, seeded weights, small sizes; the kernels interpreted.

Tolerances: as tests/test_trinity.py's, and for its reasons.
"""

import functools
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu.ops.attention as attn
from benchmark import ops_count_sdar
from benchmark.reference import compare, sdar_lm as reference
from horovod_tpu.models import (MoEConfig, TransformerLM,
                                masked_diffusion_loss, next_token_loss)
from horovod_tpu.models.transformer import (LAYER_KINDS, Attention,
                                            LayerOptions, MixerLayer)
from horovod_tpu.ops import (blockwise_attention, flash_attention,
                             mha_reference)
from horovod_tpu.ops.attention import mask_blocks
from tests.test_hybrid import (both_ways, close, reference_sides, spread,
                               with_highest)
from tests.test_flash_table import check_tables
from tests.test_ops import _pallas_call_names
from tests.test_trinity import pallas_calls, plan_of, qkv

RTOL = 2e-5
VOCAB, HIDDEN, SEQ, HEADS, KV_HEADS, HEAD_DIM = 256, 64, 128, 8, 2, 16
BLOCK, THETA, EPS = 4, 1e6, 1e-6
EXPERTS, PER_TOKEN, WIDTH, DEPTH = 16, 4, 48, 2
LAYERS = ("blockdiff_attention", "experts") * DEPTH


def moe(shard=(0, 1), row_bound=None, experts=EXPERTS):
    return MoEConfig(experts, PER_TOKEN, WIDTH, shard, row_bound,
                     renormalize=True)


def lm(expert_shard=(0, 1), use_flash=False, vocab=VOCAB):
    return TransformerLM(
        vocab_size=vocab, d_model=HIDDEN, n_heads=HEADS, dtype=jnp.float32,
        use_flash=use_flash, norm_eps=EPS, moe=moe(expert_shard),
        layers=LAYERS, n_kv_heads=KV_HEADS, head_dim=HEAD_DIM, head_norm=True,
        block_diffusion=BLOCK, rope_theta=THETA)


def reference_config(expert_shard=(0, 1), **more):
    return dict(block_length=BLOCK, rope_theta=THETA, norm_eps=EPS,
                num_experts=EXPERTS, experts_per_token=PER_TOKEN,
                expert_shard=expert_shard, **more)


def noised_batch(key, batch=2, seq=SEQ, block=BLOCK, vocab=VOCAB):
    """(tokens, noised, masked, level) as benchmark/builders/sdar_lm.py makes
    them: one level a block, one draw a token."""
    keys = jax.random.split(key, 3)
    tokens = jax.random.randint(keys[0], (batch, seq), 0, vocab - 1)
    level = 1e-3 + (1 - 1e-3) * jnp.repeat(
        jax.random.uniform(keys[1], (batch, seq // block)), block, axis=1)
    masked = jax.random.uniform(keys[2], (batch, seq)) < level
    return tokens, jnp.where(masked, vocab - 1, tokens), masked, level


def seeded(model, seed=0):
    """(`model`'s seeded parameters spread, a noised batch), made in one
    program."""
    def make(key):
        keys = jax.random.split(key, 2)
        batch = noised_batch(keys[0])
        params = spread(model.init(keys[1], batch[0],
                                   noised=batch[1])["params"], seed)
        return params, batch

    return jax.jit(make)(jax.random.PRNGKey(seed))


def system_loss(model, params, batch):
    tokens, noised, masked, level = batch
    return masked_diffusion_loss(
        model.apply({"params": params}, tokens, noised=noised), tokens,
        masked, level)


def system_side(model, params, batch):
    """((the loss, the experts every expert layer chose), every gradient) of
    `model` from one program."""
    tokens, noised, masked, level = batch

    def loss_and_chosen(params):
        logits, wrote = model.apply({"params": params}, tokens, noised=noised,
                                    mutable=["intermediates"])
        chose = jnp.stack([wrote["intermediates"][f"layer_{i}"]["mixer"][
            "chosen_experts"][0] for i in range(1, 2 * DEPTH, 2)])
        return masked_diffusion_loss(logits, tokens, masked, level), chose

    return jax.jit(jax.value_and_grad(loss_and_chosen, has_aux=True))(params)


reference_side = reference_sides(reference_config, reference.loss_and_chosen)


@functools.cache
def seed_zero():
    """`seeded(lm())` and the reference's ((loss, chosen), gradients) there:
    what every wrong program is measured against."""
    params, batch = seeded(lm())
    return params, batch, reference_side()(params, batch)


# --- the mask and the kernels ------------------------------------------------

def seen_by_hand(length, block):
    """Written out here, a third time, a pair at a time."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for r in range(2 * length):
        for c in range(2 * length):
            r_block, c_block = (r % length) // block, (c % length) // block
            if r < length:
                seen[r, c] = c < length and c_block <= r_block
            elif c < length:
                seen[r, c] = c_block < r_block
            else:
                seen[r, c] = c_block == r_block
    return seen


def masked_softmax(q, k, v, block):
    seen = jnp.asarray(seen_by_hand(q.shape[2] // 2, block))
    scores = jnp.einsum("bhqe,bhke->bhqk", q, k,
                        precision="highest") * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bhke->bhqe", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v, precision="highest")


@pytest.mark.parametrize("length,block", [(16, 4), (24, 8), (20, 6), (8, 8)])
def test_the_three_masks_are_one(length, block):
    rows = np.arange(2 * length)
    want = seen_by_hand(length, block)
    np.testing.assert_array_equal(attn.block_diffusion_mask(
        jnp.asarray(rows)[:, None], jnp.asarray(rows)[None, :], block,
        length), want)
    np.testing.assert_array_equal(reference.seen(
        jnp.asarray(rows), jnp.asarray(rows), length, block), want)
    # A row sees itself, a noised row its whole block, and the exact count.
    assert want.diagonal().all()
    if length % block == 0:
        assert want.sum() == ops_count_sdar.blockdiff_pairs(length, block)


# copy length, block length, block_q, block_k, head width: block lengths of 4
# and 32, tiles that hold whole blocks and tiles that cut them (96 and 192 in
# 128-tiles, 256 in two), unequal tiles both ways, two head widths.
MASKS = [(256, 4, 128, 128, 64), (256, 32, 128, 128, 128),
         (512, 4, 256, 128, 64), (512, 32, 128, 256, 128),
         (384, 96, 128, 128, 64), (384, 192, 128, 128, 64),
         (256, 256, 128, 128, 64), (512, 4, 512, 512, 64)]


@pytest.mark.parametrize("path", ["combined", "split", "blockwise"])
@pytest.mark.parametrize("length,block,block_q,block_k,d", MASKS, ids=str)
def test_block_diffusion_attention_is_the_masked_softmax(
        monkeypatch, length, block, block_q, block_k, d, path):
    q, k, v, mix = qkv(2 * length, d, seed=block)
    if path == "blockwise":
        def masked(q, k, v):
            return blockwise_attention(q, k, v, block_diffusion=block,
                                       block_size=block_k)
    else:
        plan_of(monkeypatch, path, 256)

        def masked(q, k, v):
            return flash_attention(q, k, v, block_diffusion=block,
                                   block_q=block_q, block_k=block_k,
                                   interpret=True)

        program = jax.make_jaxpr(jax.grad(
            lambda *a: (masked(*a) * mix).sum(), (0, 1, 2)))(q, k, v)
        names = set(_pallas_call_names(program.jaxpr))
        assert names and all(n.endswith("_blockdiff") for n in names), names

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    def plain(q, k, v):
        return masked_softmax(q, k, v, block)

    close(masked(q, k, v), plain(q, k, v))
    close(mha_reference(q, k, v, block_diffusion=block), plain(q, k, v))
    got = jax.grad(total(masked), (0, 1, 2))(q, k, v)
    want = jax.grad(total(plain), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        close(g, w)


@pytest.mark.parametrize("length,block", [(200, 4), (192, 32)], ids=str)
def test_a_copy_no_tile_divides_takes_the_scan(length, block):
    """200 rows a copy are no whole 128-tiles; 192 are one and a half: a tile
    would lie across the two copies."""
    q, k, v, mix = qkv(2 * length, 64, seed=3)

    def masked(q, k, v):
        return flash_attention(q, k, v, block_diffusion=block, interpret=True)

    program = jax.make_jaxpr(jax.grad(lambda *a: (masked(*a) * mix).sum(),
                                      (0, 1, 2)))(q, k, v)
    assert _pallas_call_names(program.jaxpr) == []
    close(masked(q, k, v), masked_softmax(q, k, v, block))
    assert mask_blocks(2 * length, 64, block_diffusion=block) is None


@pytest.mark.parametrize("wrong", [dict(causal=True), dict(window=8),
                                   dict(block_diffusion=0)], ids=str)
def test_block_diffusion_is_a_mask_of_its_own(wrong):
    q, k, v, _ = qkv(128, 64)
    for fn in (flash_attention, blockwise_attention, mha_reference):
        with pytest.raises(ValueError, match="block_diffusion|window"):
            fn(q, k, v, **{"block_diffusion": 4, **wrong})
    with pytest.raises(ValueError, match="block_diffusion"):
        flash_attention(q[:, :, :127], k[:, :, :127], v[:, :, :127],
                        block_diffusion=4)


def touching(length, block, block_q, block_k):
    """By brute force over positions: the (query tile, key tile) pairs that
    hold at least one seen (row, column)."""
    seen = seen_by_hand(length, block)
    return {(i, j) for i in range(2 * length // block_q)
            for j in range(2 * length // block_k)
            if seen[i * block_q:(i + 1) * block_q,
                    j * block_k:(j + 1) * block_k].any()}


# The cell's shape cut to an eighth in both tile sizes (the same 24 of 36 and
# 80 of 136 tiles), blocks that tiles cut, unequal tiles both ways, a block as
# long as the copy.
WALKS = [(512, 4, 64, 64), (512, 4, 128, 128), (512, 32, 128, 128),
         (512, 96, 128, 128), (512, 4, 128, 256), (512, 32, 256, 128),
         (256, 256, 128, 128), (512, 200, 128, 128)]


@pytest.mark.parametrize("length,block,block_q,block_k", WALKS, ids=str)
def test_the_kernels_visit_exactly_the_masks_tiles(length, block, block_q,
                                                   block_k):
    """Every kernel's table, walked as the grid walks it, queries outer
    (forward, dq) and keys outer (dk/dv, the combined backward): the steps
    are the tile pairs the mask touches, once each and no other, so a tile is
    fetched only for a pair the mask touches; a row's steps are contiguous
    with `first` and `last` set once; a tile the table calls whole holds no
    hidden pair; and the count is what `mask_blocks` reports."""
    want = touching(length, block, block_q, block_k)
    assert check_tables(attn.BlockDiffusion(block, length), 2 * length,
                        block_q, block_k) == want
    num_q = 2 * length // block_q
    if block_q % 128 == 0 and block_k % 128 == 0:
        rows = 2 * length
        causal = sum((i * block_q + block_q - 1) // block_k + 1
                     for i in range(num_q))
        assert mask_blocks(rows, 128, block_diffusion=block, block_q=block_q,
                           block_k=block_k) == (len(want), causal)
        # The grids are the tables', not the rows'.
        shape = jax.ShapeDtypeStruct((1, 2, rows, 128), jnp.bfloat16)

        def loss(q, k, v):
            return flash_attention(
                q, k, v, block_diffusion=block, block_q=block_q,
                block_k=block_k, interpret=True).astype(jnp.float32).sum()

        grids = pallas_calls(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
            shape, shape, shape).jaxpr)
        assert grids["hvd_flash_fwd_blockdiff"] == (2, len(want))
        _, plan_q, plan_k = attn._bwd_plan(rows, 128, block_q, block_k, 2)
        plan_steps = len(touching(length, block, plan_q, plan_k))
        backward = {n: g for n, g in grids.items() if "bwd" in n}
        assert backward and all(n.endswith("_blockdiff") for n in backward)
        for name, grid in backward.items():
            assert grid == (2, plan_steps), (name, grid)


def test_the_cells_counts():
    assert mask_blocks(8192, 128, block_diffusion=4) == (24, 36)
    assert mask_blocks(8192, 128, block_diffusion=4, block_q=512,
                       block_k=512) == (80, 136)
    assert mask_blocks(8192, 128, block_diffusion=32) == (24, 36)
    assert ops_count_sdar.blockdiff_pairs(4096, 4) == 4096 ** 2 + 4096 * 4


@pytest.mark.parametrize("plan", ["combined", "split"])
def test_causal_and_windowed_calls_keep_their_kernels(monkeypatch, plan):
    """The calls the other cells make name the kernels they named, each on
    the grid of its own mask's live tiles."""
    plan_of(monkeypatch, plan, 128)
    shape = jax.ShapeDtypeStruct((1, 2, 512, 64), jnp.bfloat16)

    def calls(**mask):
        def loss(q, k, v):
            return flash_attention(q, k, v, interpret=True, block_q=128,
                                   block_k=128,
                                   **mask).astype(jnp.float32).sum()
        return pallas_calls(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
            shape, shape, shape).jaxpr)

    backward = {"combined": ["hvd_flash_bwd"],
                "split": ["hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"]}[plan]
    # 4 x 4 tiles of 128: 10 on and under the diagonal; the diagonal and the
    # one under it; 256 rows a copy: a clean query tile's clean key tiles to
    # its own (1 + 2), a noised one's earlier-or-cut clean ones and its own
    # noised (2 + 3).
    for mask, suffix, steps in (
            (dict(causal=True), "", 10), (dict(), "", 16),
            (dict(causal=True, window=128), "_window", 7),
            (dict(block_diffusion=4), "_blockdiff", 8)):
        grids = calls(**mask)
        assert set(grids) == {n + suffix
                              for n in ["hvd_flash_fwd"] + backward}
        assert set(grids.values()) == {(2, steps)}, (mask, grids)


# --- the layers --------------------------------------------------------------

def case(module, seed, rows=2 * SEQ):
    def make(key):
        keys = jax.random.split(key, 3)
        u = jax.random.normal(keys[0], (2, rows, HIDDEN))
        params = spread(module.init(keys[1], u)["params"], seed)
        return u, params, jax.random.normal(keys[2], u.shape)

    return jax.jit(make)(jax.random.PRNGKey(seed))


@pytest.mark.parametrize("use_flash", [False, True])
def test_attention_is_the_reference(use_flash):
    """Rows i and L + i at position i, rotary base 1e6, the block mask, a norm
    a head, 8 query heads on 2 key/value heads, no gate."""
    layer = Attention(HEADS, jnp.float32, use_flash=use_flash, norm_eps=EPS,
                      n_kv_heads=KV_HEADS, head_dim=HEAD_DIM, head_norm=True,
                      block_diffusion=BLOCK, rope_theta=THETA)
    u, params, mix = case(layer, seed=3)
    assert set(params) == {"q_kernel", "kv_kernel", "q_head_norm_scale",
                           "k_head_norm_scale", "o_kernel"}
    both_ways(lambda p, u: layer.apply({"params": p}, u),
              lambda p, u: reference.attention_layer(
                  u, p, block_length=BLOCK, rope_theta=THETA, norm_eps=EPS),
              u, params, mix)


def test_the_rotary_base_is_the_models():
    """Base 1e6 and base 1e4 differ, and the default is the old 1e4."""
    def layer(**theta):
        return Attention(HEADS, jnp.float32, use_flash=False,
                         block_diffusion=BLOCK, **theta)
    u, params, _ = case(layer(), seed=1)
    old = layer().apply({"params": params}, u)
    close(layer(rope_theta=10000.0).apply({"params": params}, u), old)
    new = layer(rope_theta=THETA).apply({"params": params}, u)
    assert float(jnp.abs(new - old).max()) > 1e-3


def test_the_kinds_and_what_they_want():
    assert LAYER_KINDS["blockdiff_attention"].mixer is Attention
    x = jnp.zeros((1, 2 * SEQ, HIDDEN))
    with pytest.raises(ValueError, match="block_diffusion="):
        MixerLayer("blockdiff_attention", LayerOptions(n_heads=HEADS)).init(
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="block_diffusion="):
        Attention(HEADS, block_diffusion=4, window=8).init(
            jax.random.PRNGKey(0), x)
    model, tokens = lm(), jnp.zeros((1, SEQ), jnp.int32)
    with pytest.raises(ValueError, match="noised="):
        model.init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="noised="):
        lm().clone(block_diffusion=None).init(jax.random.PRNGKey(0), tokens,
                                              noised=tokens)


# --- the loss ----------------------------------------------------------------

def test_every_token_masked_at_level_one_is_the_mean_cross_entropy():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, VOCAB))
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, VOCAB)
    ones = jnp.ones((2, SEQ))
    np.testing.assert_allclose(
        masked_diffusion_loss(logits, targets, ones.astype(bool), ones),
        next_token_loss(logits, targets), rtol=1e-6)


def test_the_loss_weighs_masked_tokens_by_their_level():
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    logits = jax.random.normal(keys[0], (2, SEQ, VOCAB))
    tokens, _, masked, level = noised_batch(keys[1])
    xent = -jnp.take_along_axis(jax.nn.log_softmax(logits), tokens[..., None],
                                -1)[..., 0]
    want = (xent * masked / level).sum(-1).mean() / SEQ
    np.testing.assert_allclose(
        masked_diffusion_loss(logits, tokens, masked, level), want, rtol=1e-5)
    hidden = jax.random.normal(keys[0], (2, SEQ, HIDDEN))
    head = jax.random.normal(keys[1], (HIDDEN, VOCAB)) * HIDDEN ** -0.5
    np.testing.assert_allclose(
        masked_diffusion_loss(hidden @ head, tokens, masked, level),
        with_highest(reference.diffusion_loss)(hidden, head, tokens, masked,
                                               level), rtol=1e-5)


# --- the reference refuses the wrong programs --------------------------------

def probe_rows(kernel, length=256):
    """The builder's kernel comparison at a small size: `kernel` against the
    reference's masked softmax under the sharpened scale."""
    sharp = reference.SHARP_SCALE * HEAD_DIM ** -0.5
    return compare.kernel_against(
        lambda q, k, v: kernel(q, k, v, sharp),
        lambda q, k, v: reference.masked_attention(
            q, k, v, block_length=BLOCK, sm_scale=sharp),
        (1, 4, 2 * length, HEAD_DIM), jnp.float32, 7,
        reference.BLOCKDIFF_FWD_ATOL, reference.BLOCKDIFF_GRAD_RTOL,
        "blockdiff_flash_")
