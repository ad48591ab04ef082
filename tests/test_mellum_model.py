"""Mellum-2's pattern recomputed, through the train step, in shares that add up
to the uncut layer, at the cell's shapes, and the wrong programs the reference
must refuse: the second half of tests/test_mellum.py, whose sizes, helpers and
tolerances it reads.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_count_mellum, ops_count_trinity
from benchmark.reference import mellum_lm as reference
from horovod_tpu.models import (DeltaConfig, IndexerConfig, LatentConfig,
                                Mamba2Config, MoEConfig, TransformerLM,
                                indexer_loss, looped_exit_loss,
                                next_token_loss, record_attention_blocks,
                                record_expert_rows)
from horovod_tpu.models.transformer import SparseExperts, _sown
from horovod_tpu.ops import flash_attention
from horovod_tpu.ops.moe import GROUPED_KERNELS
from horovod_tpu.ops.attention import _bwd_plan, flash_grid_steps, mask_blocks
from tests.test_hybrid import (close, mixer_case, seeded, share_outputs,
                               system_loss, trains_and_replicas_stay_equal,
                               vocabulary_slices_concatenate, with_highest)
from tests.test_ops import _pallas_call_names
from tests.test_mellum import (EPS, EXPERTS, HEADS, HEAD_DIM, HIDDEN, KINDS,
                               KV_HEADS, LAYERS, PATTERN_DIGEST, PER_TOKEN,
                               SEQ, THETA, VOCAB, WINDOW, YARN, gradient_error,
                               lm, loss_and_wrote, moe, probe_rows)


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


# One small model a family of pattern whose program no digest above holds —
# every layer kind and `post_norm`, `attn_gate`, `embed_scale`, `rotary_dim`,
# `noised=` and `loops` between them — as `TransformerLM`'s keywords, and two
# digests recorded at the parent commit of PR 58 (dbb284f,
# jax 0.9.0, before `MixerLayer` took one options value; the lowered texts of
# `nemotron` and `qwen3next` again at PR 59, whose Mamba-2 and head-gated
# delta mixers open with `models.ssm.mixer_opening`: their parameters'
# digests held, and `ling`'s channel gate kept both of its own; `qwen3next`'s
# text once more at PR 61, whose head form solves in two kernels; all seven
# held at PR 63, whose pass of q through `ops.attn_prep` engages at heads of
# 128 turned whole and at none of these small widths): of
# `jax.jit(grad).lower(...).as_text()` and of the parameters seeded from
# `PRNGKey(0)` (paths, shapes, types, bytes).  Bfloat16, as the cells run.
FAMILY_SIZES = dict(vocab_size=256, d_model=64, n_heads=8,
                    dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16,
                    use_flash=False)
FAMILIES = {
    "nemotron": dict(
        layers=("ssm", "experts", "attention"), norm_eps=1e-5, rope=False,
        n_kv_heads=2, head_shard=(0, 2),
        ssm=Mamba2Config(heads=8, head_dim=8, groups=4, state=16, chunk=32),
        moe=MoEConfig(16, 4, 48, (0, 4), 1.5, "sigmoid", True, 2.5, "relu2",
                      32, 96)),
    "ling": dict(
        layers=("delta", "latent_attention", "gated_mlp", "experts"), d_ff=96,
        delta=DeltaConfig(heads=8, head_dim=8, chunk=32),
        latent=LatentConfig(16, 8, 4, 8, rope_theta=6e6),
        moe=MoEConfig(16, 4, 48, (0, 4), 1.5, "sigmoid", True, 2.5,
                      shared_width=40, n_group=4, topk_group=2)),
    "trinity": dict(
        layers=("window_attention", "experts", "attention", "experts"),
        norm_eps=1e-5, rope=False, n_kv_heads=2, head_dim=16, window=32,
        head_norm=True, attn_gate=True, post_norm=True, embed_scale=8.0,
        moe=MoEConfig(16, 4, 48, (0, 4), 1.5, "sigmoid", True, 2.826,
                      shared_width=48)),
    "sdar": dict(
        layers=("blockdiff_attention", "experts"), n_kv_heads=2, head_dim=16,
        head_norm=True, block_diffusion=4, rope_theta=1e6,
        moe=MoEConfig(16, 4, 48, (0, 4), 1.5, renormalize=True)),
    "qwen3next": dict(
        layers=("gated_delta", "attention"), n_heads=4, n_kv_heads=2,
        head_dim=32, head_norm=True, attn_gate=True, rope_theta=1e7,
        rotary_dim=8,
        delta=DeltaConfig(heads=2, head_dim=16, chunk=32, value_heads=4)),
    "ouro": dict(
        layers=("attention", "gated_mlp") * 2, n_heads=4, d_ff=96,
        post_norm=True, rope_theta=1e6, loops=3, exit_gate=True),
    # The selection is the flash kernels' operand: their interpreted calls,
    # over four times `topk` positions.
    "keye": dict(
        layers=("selected_attention", "experts"), n_heads=4, n_kv_heads=2,
        head_dim=16, head_norm=True, rope_theta=1e7, use_flash=True,
        indexer=IndexerConfig(4, 16, 64),
        moe=MoEConfig(16, 4, 48, (0, 4), 1.5, renormalize=True)),
}
FAMILY_DIGESTS = {
    "nemotron": (
        "e06543fe20e5cca0d88d98d2b103b1a446ff2d8055d34d6e2e70229a9168cf5a",
        "38e380a4e237e77d4a0e1740c889e39ade0b306b062f36fc6ebc00488bbdd7c2"),
    "ling": (
        "aedbbf55266b42d1365e7d8e0ffb658ac6cc15035adda318c6cf1857d108af88",
        "306dbe1b09f4ec00ac24fe548fbbd391a89ecdbb4643a0eff6a7f3dd0d28fb45"),
    "trinity": (
        "65364aaa31e81742bc58204f99e3204d1958690aee626e8cf5fecc62c7cc7d2a",
        "ac903589fe2ff7ef8bb18ba6b4c2ac4caac38cc680077370174e827b54f64eb3"),
    "sdar": (
        "226f2e273d1a677f34065ee963fa237a993d755162817fc118ef4181fcc460e8",
        "16061fccdea8661316ab18d63dd5c720e9b87007b5839ecdf7857b2385a0ac1b"),
    "qwen3next": (
        "c33520116f794d2e68eaec40ddebad26501c0e7b2ec6e4caed321f370cdd77a2",
        "4139ed284cc607e2ae47c85fb96e215363f754eaf02788b1b805db03fa567bd2"),
    "ouro": (
        "506a976b3cd6c36f66d5acba623d61373f7e54481e37c79a7da852483ea18678",
        "94437dba6b73d113cc9a89971c61b721329bdff0308dc32e6dbeb0e641d99dd9"),
    "keye": (
        "674fd4db469babe218c6b90bad614724c60abfa18a65b0bf4adeec4c86a0bc39",
        "b93e452b2273c2ca85dfd31aa4e892bb00786b2df3aca9d5175651481a76da17"),
}


def noised_too(model, tokens):
    """A block-diffusion model is called with the noised copy."""
    return {} if model.block_diffusion is None else {"noised": tokens}


def family_loss(model, params, tokens):
    if model.loops is not None:
        return looped_exit_loss(*model.apply({"params": params}, tokens,
                                             targets=tokens))
    logits, wrote = model.apply(
        {"params": params}, tokens, mutable=["intermediates", "router"],
        **noised_too(model, tokens))
    if model.indexer is not None:
        return next_token_loss(logits, tokens) \
            + indexer_loss(wrote["intermediates"])
    return next_token_loss(logits, tokens)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_familys_pattern_lowers_to_the_parents_program(family):
    model = TransformerLM(**{**FAMILY_SIZES, **FAMILIES[family]})
    seq = 128 if model.indexer is None else 4 * model.indexer.topk
    tokens = jnp.zeros((2, seq), jnp.int32)
    seeded = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), tokens,
        **noised_too(model, tokens))["params"])()
    text = jax.jit(jax.grad(
        lambda p, t: family_loss(model, p, t))).lower(
            jax.eval_shape(lambda: seeded), tokens).as_text()
    tree = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(seeded):
        tree.update(f"{jax.tree_util.keystr(path)} {leaf.shape} "
                    f"{leaf.dtype} ".encode() + np.asarray(leaf).tobytes())
    assert "hvd_attn_prep" not in text
    assert (hashlib.sha256(text.encode()).hexdigest(), tree.hexdigest()) \
        == FAMILY_DIGESTS[family]


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
    assert record_expert_rows(wrote_2["intermediates"])["experts_kernel"] \
        == ["ragged_dot"] * len(KINDS)
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


def test_a_recomputed_layer_keeps_the_tiled_kernels_outputs(monkeypatch):
    """The same where the grouped products take the tiled kernels (a TPU
    backend, said here; widths of 128 and 384 and 512 rows a group): a
    recomputing layer's gradient program holds `hvd_grouped_fwd` three times
    a layer, as the unrecomputed model's — its outputs are kept by name, so
    no product is made again inside the backward's recomputation — and each
    backward form as often; `experts_kernel` says which kernel ran."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    tokens = jnp.zeros((1, 4 * SEQ), jnp.int32)

    def tiled_lm(recompute):
        return TransformerLM(
            vocab_size=VOCAB, d_model=128, n_heads=HEADS, dtype=jnp.bfloat16,
            logits_dtype=jnp.bfloat16, use_flash=True, norm_eps=EPS,
            moe=MoEConfig(8, 4, 384, (0, 2), None, renormalize=True),
            layers=LAYERS, n_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
            window=WINDOW, head_norm=True, rope_theta=THETA,
            rope_scaling=YARN, window_rope=(THETA, None),
            recompute=recompute)

    def shapes(model):
        return jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])

    def program(recompute):
        model = tiled_lm(recompute)
        grad = jax.make_jaxpr(jax.grad(
            lambda p: system_loss(model, p, (tokens, tokens))))(shapes(model))
        names = _pallas_call_names(grad.jaxpr)
        return {name: names.count(name) for name in set(names)
                if "grouped" in name}, str(grad)

    kernels = []        # a static shape's: what a trace sows, a run would
    jax.eval_shape(lambda p: kernels.extend(_sown(tiled_lm(False).apply(
        {"params": p}, tokens, mutable=["intermediates"])[1],
        "experts_kernel")), shapes(tiled_lm(False)))
    (kept, kept_text), (again, again_text) = program(False), program(True)
    layers = len(KINDS)
    assert kept == again == {"hvd_grouped_fwd": 3 * layers,
                             "hvd_grouped_drows": 3 * layers,
                             "hvd_grouped_dweights": 3 * layers}
    assert " ragged_dot_general[" not in kept_text + again_text
    assert kernels == [GROUPED_KERNELS.index("tiled")] * layers
    assert again_text.count(" dot_general[") > kept_text.count(" dot_general[")


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern,
    recomputed, the banded and the causal flash kernels (interpreted here) as
    in the benchmark.  The replicated weights stay equal and the loss of a
    repeated batch falls, to what the unrecomputed model's falls to."""
    ends = []
    for recompute in (True, False):
        model = lm((0, 4), use_flash=True, recompute=recompute)
        ends.append(trains_and_replicas_stay_equal(
            model, *seeded(model, seed=3)))
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

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, **{name: params[name][held] for name in (
            "gate_kernel", "up_kernel", "down_kernel")})

    parts = share_outputs(
        n, lambda i: SparseExperts(moe((i, n), experts=experts), jnp.float32),
        share, params, u)
    want = with_highest(reference.sparse_experts)(
        u.reshape(-1, HIDDEN), params, num_experts=experts,
        expert_shard=(0, 1), experts_per_token=PER_TOKEN)[0]
    close(sum(parts), want.reshape(u.shape))


def test_vocabulary_slices_concatenate_to_the_uncut_head():
    """A sliced vocabulary is a smaller vocabulary: the i-th quarter's model
    gives, for ids of the slice, the uncut model's logits of its columns."""
    vocabulary_slices_concatenate(lm, 4)


# --- the cell's shapes, off the kernels' own tables --------------------------

def test_the_cells_plan_and_counts():
    """16,384 rows of head 128 run the combined backward in (512, 512) blocks,
    which asks Mosaic for the scoped VMEM its plan computes (past 16,384 rows
    the split pair); the forward stays in 1,024-blocks.  The 1,024-key band is
    two tiles of 1,024 wide, 31 of the causal mask's 136 tile pairs, and three
    of 512, 93 of 528, for 1,024 x 1,025 / 2 + 15,360 x 1,024 of its
    16,384 x 16,385 / 2 exact pairs (an eighth)."""
    assert _bwd_plan(16384, 128, 1024, 1024, 32) == ("combined", 512, 512)
    assert _bwd_plan(32768, 128, 1024, 1024, 32) == ("split", 1024, 1024)
    assert _bwd_plan(8192, 128, 1024, 1024, 32)[0] == "combined"
    assert mask_blocks(16384, 128, causal=True, window=1024) == (31, 136)
    grids = flash_grid_steps(16384, 128, 32, causal=True, window=1024)
    assert grids == {"hvd_flash_fwd_window": (31, 31, 256),
                     "hvd_flash_bwd_window": (93, 93, 1024)}
    assert flash_grid_steps(16384, 128, 32, causal=True) == {
        "hvd_flash_fwd": (136, 136, 256), "hvd_flash_bwd": (528, 528, 1024)}
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


@pytest.mark.parametrize("wrong", [
    dict(drop="attention_factor"), dict(drop="yarn"), dict(drop="window"),
    dict(drop="renormalize"), dict(window_error=1), dict(window_error=-1)],
    ids=lambda wrong: "_".join(map(str, wrong.values())))
def test_the_references_wrong_programs_are_other_programs(wrong):
    """A full layer without its attention factor, or at the plain
    frequencies; a windowed layer that sees every key, or one key more or
    fewer; weights that are not renormalised: each is a hundred times and
    more over what these tests hold the system's gradients to (1e-4)."""
    assert gradient_error(**wrong) > 1e-2


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
    assert gradient_error(operand_dtype=dtype) > least
