"""What Keye-VL-2.0's language model adds — a learned indexer that scores every
earlier key, a selection of each query's `topk` best that the flash kernels
take as their mask's operand, and the indexer's own loss with the two
stop-gradients that keep it apart from the next-token loss — against the plain
float32 reference the benchmark keeps (benchmark/reference/keye_lm.py): dense
scores, a sort, a masked softmax, autodiff.  CPU, float32, seeded weights,
small sizes; the kernels through Pallas's interpreter.  A family's cases read
one jitted evaluation (`functools.cache`), as tests/test_hybrid.py's do.

Tolerances: as tests/test_trinity.py's, and for its reasons.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_count_keye
from benchmark.builders import keye_lm as builder
from benchmark.reference import keye_lm as reference
from horovod_tpu.common import metrics
from horovod_tpu.models import (IndexerConfig, MoEConfig, TransformerLM,
                                indexer_loss, next_token_loss,
                                record_attention_selection)
from horovod_tpu.models.transformer import (LAYER_KINDS, LayerOptions,
                                            MixerLayer)
from horovod_tpu.ops import dsa
from horovod_tpu.ops.attention import Selected, masked_flash_attention
from tests.test_hybrid import (close, relative_error, spread,
                               trains_and_replicas_stay_equal, trees_close,
                               with_highest)

VOCAB, HIDDEN, SEQ, HEADS, KV_HEADS, HEAD_DIM = 256, 64, 256, 4, 2, 16
INDEX_HEADS, INDEX_DIM, TOPK, THETA, EPS = 4, 16, 64, 1e7, 1e-6
EXPERTS, PER_TOKEN, WIDTH, DEPTH = 16, 4, 48, 2
INDEXER = IndexerConfig(INDEX_HEADS, INDEX_DIM, TOPK)


def moe(shard=(0, 1)):
    return MoEConfig(EXPERTS, PER_TOKEN, WIDTH, shard, None, renormalize=True)


def lm(expert_shard=(0, 1), topk=TOPK, kind="selected_attention"):
    return TransformerLM(
        vocab_size=VOCAB, d_model=HIDDEN, n_heads=HEADS, dtype=jnp.float32,
        use_flash=True, norm_eps=EPS, moe=moe(expert_shard),
        layers=(kind, "experts") * DEPTH, n_kv_heads=KV_HEADS,
        head_dim=HEAD_DIM, head_norm=True, rope_theta=THETA,
        indexer=INDEXER._replace(topk=topk)
        if kind == "selected_attention" else None)


def reference_config(expert_shard=(0, 1), **more):
    return dict(topk=TOPK, rope_theta=THETA, norm_eps=EPS,
                num_experts=EXPERTS, experts_per_token=PER_TOKEN,
                expert_shard=expert_shard, **more)


@functools.cache
def seeded(expert_shard=(0, 1), seed=0, batch=1):
    """(`lm(expert_shard)`'s seeded parameters spread, (inputs, targets)),
    made in one program."""
    model = lm(expert_shard)

    def make(key):
        keys = jax.random.split(key, 2)
        tokens = jax.random.randint(keys[0], (batch, SEQ + 1), 0, VOCAB)
        params = spread(model.init(keys[1], tokens[:, :128])["params"], seed)
        return params, (tokens[:, :-1], tokens[:, 1:])

    return jax.jit(make)(jax.random.PRNGKey(seed))


def system_loss(model, params, batch):
    logits, wrote = model.apply({"params": params}, batch[0],
                                mutable=["intermediates"])
    return next_token_loss(logits, batch[1]) \
        + indexer_loss(wrote["intermediates"])


@functools.cache
def system_side(expert_shard=(0, 1), topk=TOPK):
    """((next-token loss, indexers' loss), the gradient of each alone, what
    the layers chose) of `lm(expert_shard, topk)` on `seeded(expert_shard)`,
    from one program."""
    model = lm(expert_shard, topk)
    params, batch = seeded(expert_shard)

    def both(params):
        def term(i):
            return lambda p: builder.loss_terms_and_rows(model, p, batch)[0][i]

        terms, seen = builder.loss_terms_and_rows(model, params, batch)
        return terms, (jax.grad(term(0))(params), jax.grad(term(1))(params)), \
            seen

    return jax.jit(both)(params)


@functools.cache
def reference_side(expert_shard=(0, 1), **more):
    """(the reference's two terms, the share of selections that differ from
    the system's, its chosen experts; the gradient of each term alone)."""
    params, batch = seeded(expert_shard)
    theirs = system_side(expert_shard)[2]["selections"]
    config = reference_config(expert_shard, **more)

    def both(params):
        def term(i):
            return lambda p: reference.loss_terms(p, batch, theirs,
                                                  **config)[i]

        return reference.loss_terms(params, batch, theirs, **config), (
            jax.grad(term(0))(params), jax.grad(term(1))(params))

    return with_highest(both)(params)


def is_indexers(path):
    return bool(builder._group(path))


# --- the model against the reference -----------------------------------------

SIDES = [(0, 1), (1, 4)]


@pytest.mark.parametrize("expert_shard", SIDES, ids=str)
def test_both_losses_are_the_references(expert_shard):
    (loss, kl), _, _ = system_side(expert_shard)
    (want_loss, want_kl, *_), _ = reference_side(expert_shard)
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    np.testing.assert_allclose(kl, want_kl, rtol=2e-5)
    assert float(kl) > 0.01


@pytest.mark.parametrize("term", [0, 1], ids=["next_token", "indexer_kl"])
@pytest.mark.parametrize("expert_shard", SIDES, ids=str)
def test_each_losss_gradients_are_the_references(expert_shard, term):
    got = system_side(expert_shard)[1][term]
    want = reference_side(expert_shard)[1][term]
    trees_close(got, want, rtol=1e-4)


@pytest.mark.parametrize("expert_shard", SIDES, ids=str)
def test_selections_and_experts_are_the_references(expert_shard):
    seen = system_side(expert_shard)[2]
    (_, _, differ, want, selected), _ = reference_side(expert_shard)
    assert float(differ) == 0.0
    assert (np.asarray(selected) == np.asarray(seen["selections"])).all()
    assert (np.sort(seen["chosen_experts"], -1) == np.sort(want, -1)).all()
    assert seen["selections"].shape == (DEPTH, 1, SEQ, SEQ)


@pytest.mark.parametrize("term", [0, 1], ids=["next_token", "indexer_kl"])
def test_the_two_stop_gradients_keep_the_losses_apart(term):
    """The indexers' parameters take no gradient from the next-token loss,
    every other parameter none from the indexers' loss, and each does take
    one from its own."""
    grads = system_side()[1][term]
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        reached = float(jnp.abs(leaf).max()) > 0.0
        assert reached == (is_indexers(path) == bool(term)), (path, term)


def test_the_builders_rows_pass_and_group_the_indexer():
    """benchmark/builders/keye_lm.py's own comparison at this size: every row
    far inside its limit, the indexers' gradient a group of its own."""
    params, batch = seeded()
    terms, grads, seen = builder.system_terms(lm(), jax.devices())(params,
                                                                   batch)
    against = with_highest(lambda *a: builder.against_reference(
        reference_config(), *a))(params, batch, grads,
                                 seen["chosen_experts"], seen["selections"])
    rows = builder.compare_rows(terms, against, seen, 0)
    assert [row["name"] for row in rows] == [
        "loss_rel_error", "indexer_kl_rel_error", "grad_norm_rel_error",
        "body_grad_rel_l2_error", "indexer_grad_rel_l2_error",
        "rows_over_bound", "routing_mismatch_share",
        "selection_mismatch_share"]
    assert all(row["value"] <= 1e-2 * row["limit"] for row in rows), rows
    norms = {row["name"]: row["reference"] for row in rows[3:5]}
    assert min(norms.values()) > 0.0


# --- what is wrong must read wrong -------------------------------------------

def test_topk_at_the_sequence_length_is_another_program():
    """Every earlier key kept — a parameter of the model, no switch: the
    selection-off control.  Its loss and gradients leave the reference's by
    more than the cell's limits, and no layer selects."""
    (loss, kl), grads, seen = system_side(topk=SEQ)
    (want_loss, *_), want = reference_side()
    assert seen["selections"].shape[0] == 0 and float(kl) == 0.0
    off = float(relative_error(
        jax.tree.map(jnp.add, *grads), jax.tree.map(jnp.add, *want)))
    assert off > reference.GRAD_RTOL, off
    assert abs(float(loss / want_loss) - 1) > 1e-4


def test_reference_refuses_float8_operands():
    _, exact = reference_side()
    _, rounded = reference_side(operand_dtype=jnp.float8_e4m3fn)
    for term in (0, 1):
        assert float(relative_error(rounded[term], exact[term])) \
            > reference.GRAD_RTOL


# --- the layer ----------------------------------------------------------------

def test_at_most_topk_positions_is_the_causal_layer_to_the_last_bit():
    """`topk >= seq`: the layer computes no score, runs the causal kernels
    and sows a KL of zero; its output is the layer without an indexer's, bit
    for bit."""
    params, (inputs, _) = seeded()
    plain = {name: dict(layer, mixer={
        k: v for k, v in layer["mixer"].items()
        if not k.startswith("index_")})
        if name.startswith("layer_") and "index_q_kernel" in layer["mixer"]
        else layer for name, layer in params.items()}
    got, wrote = jax.jit(lambda p: lm(topk=SEQ).apply(
        {"params": p}, inputs, mutable=["intermediates"]))(params)
    want = jax.jit(lambda p: lm(kind="attention").apply(
        {"params": p}, inputs))(plain)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert float(indexer_loss(wrote["intermediates"])) == 0.0
    assert record_attention_selection(wrote["intermediates"])[
        "keys_selected"] == []


def test_the_layers_shares_add_up_with_attention_counted_once():
    """One published layer: every share computes attention and indexer alike
    (counted once) and its own experts; attention's output plus the eight
    shares' expert outputs is the uncut reference's layer."""
    n = 8
    common = dict(n_heads=HEADS, dtype=jnp.float32, norm_eps=EPS)
    attention = MixerLayer("selected_attention", LayerOptions(
        use_flash=True, n_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
        head_norm=True, rope_theta=THETA, indexer=INDEXER, **common))

    def experts(shard):
        return MixerLayer("experts", LayerOptions(
            use_flash=False, moe=moe(shard), **common))

    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (1, SEQ, HIDDEN))
    local = EXPERTS // n

    def total(x):
        p_attention = spread(attention.init(keys[1], x[:, :128])["params"], 7)
        p_experts = spread(experts((0, 1)).init(keys[2], x)["params"], 8)
        after = attention.apply({"params": p_attention}, x)
        out = after
        for i in range(n):
            held = slice(i * local, (i + 1) * local)
            mixer = dict(p_experts["mixer"], **{
                name: p_experts["mixer"][name][held]
                for name in ("gate_kernel", "up_kernel", "down_kernel")})
            out = out + experts((i, n)).apply(
                {"params": dict(p_experts, mixer=mixer)}, after) - after
        return out, p_attention, p_experts

    got, p_attention, p_experts = jax.jit(total)(x)
    want = with_highest(lambda *a: reference.layer(
        *a, None, **reference_config())[0])(x, p_attention, p_experts)
    close(got, want)


def test_the_kinds_and_what_they_want():
    assert LAYER_KINDS["selected_attention"].mixer \
        is LAYER_KINDS["attention"].mixer
    x = jnp.zeros((1, 128, HIDDEN))
    with pytest.raises(ValueError, match="indexer="):
        MixerLayer("selected_attention", LayerOptions(n_heads=HEADS)).init(
            jax.random.PRNGKey(0), x)
    for wrong in (dict(window=8), dict(block_diffusion=4),
                  dict(use_flash=False), dict(seq_axis="sp")):
        with pytest.raises(ValueError, match="indexer= selects"):
            from horovod_tpu.models.transformer import Attention
            Attention(HEADS, indexer=INDEXER, **wrong).init(
                jax.random.PRNGKey(0), x)


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern,
    the selected flash kernels and the selection's own (interpreted here) as
    in the benchmark.  The replicated weights stay equal and the loss of a
    repeated batch falls."""
    model = lm((0, 4))
    params, batch = seeded((0, 4), seed=3, batch=2)
    trains_and_replicas_stay_equal(model, params, batch, loss=system_loss)


# --- the names a trace is read by ---------------------------------------------

@functools.cache
def lowered_step():
    params, batch = seeded()
    return jax.jit(jax.grad(lambda p: system_loss(lm(), p, batch))).lower(
        params).as_text(debug_info=True)


@pytest.mark.parametrize("name", [
    "hvd_dsa_index", "hvd_dsa_index_bwd_dq", "hvd_dsa_index_bwd_dk",
    "hvd_dsa_probs", "hvd_flash_fwd_selected", "hvd_flash_bwd_selected"])
def test_a_kernel_is_in_the_lowered_step_by_its_name(name):
    text = lowered_step()
    assert f"{name}" in text
    # the causal kernels are not: every attention call here selects
    assert "hvd_flash_fwd\"" not in text


@pytest.mark.parametrize("scope", ["hvd_dsa_index", "hvd_dsa_select",
                                   "hvd_dsa_kl", "hvd_attn_attend"])
def test_a_scope_is_in_the_lowered_step(scope):
    assert f"/{scope}" in lowered_step() or f"{scope}/" in lowered_step()


def test_the_layers_count_their_selection(monkeypatch):
    _, _, seen = system_side()
    counts = np.asarray(seen["selection_counts"])
    want = ops_count_keye.selected_pairs(SEQ, TOPK)
    causal = ops_count_keye.causal_pairs(SEQ)
    assert (counts[:, 1] == causal).all() and (counts[:, 4] == 1).all()
    assert (counts[:, 0] == want + counts[:, 2]).all()
    assert (counts[:, 2] >= 0).all() and (counts[:, 2] < 0.01 * want).all()
    for layer, chosen in zip(counts, seen["selections"]):
        assert int(chosen.sum()) == layer[0]
    params, (inputs, _) = seeded()
    wrote = jax.jit(lambda p: lm().apply(
        {"params": p}, inputs, mutable=["intermediates"])[1])(params)
    monkeypatch.setattr(metrics.registry, "enabled", True)
    recorded = record_attention_selection(wrote["intermediates"])
    assert recorded["keys_selected"] == counts[:, 0].tolist()
    assert recorded["tiles_causal"] == [1, 1]
    snapshot = metrics.registry.snapshot()["attention"]
    assert {name: snapshot[name] for name in recorded} == recorded
    metrics.registry.set_attention_blocks([], [])    # as it was found


# --- the selection's own pieces (ops/dsa.py) ----------------------------------

@functools.cache
def pieces(seed=0, heads=INDEX_HEADS):
    """Seeded indexer operands of two sequences and everything `ops/dsa.py`
    makes of them at `TOPK`, beside the dense forms, from one program."""
    def make(key):
        keys = jax.random.split(key, 6)
        q_i = jax.random.normal(keys[0], (2, heads, SEQ, INDEX_DIM))
        k_i = jax.random.normal(keys[1], (2, SEQ, INDEX_DIM))
        w = 0.3 * jax.random.normal(keys[2], (2, SEQ, heads))
        q, k, v = (jax.random.normal(key, (2, HEADS, SEQ, HEAD_DIM))
                   for key in keys[3:])
        scores = dsa.index_scores(q_i, k_i, w, True)
        chosen = dsa.select(scores, TOPK)
        out, lse = masked_flash_attention(q, k, v, Selected(TOPK),
                                          chosen.chosen, interpret=True)
        probs = dsa.head_probs(q, k, lse, chosen.chosen, interpret=True)

        def kl(q_i, k_i, w):
            return dsa.indexer_kl(q_i, k_i, w, scores, chosen.chosen, probs,
                                  True)

        return dict(q_i=q_i, k_i=k_i, w=w, q=q, k=k, v=v, scores=scores,
                    chosen=chosen, out=out, probs=probs,
                    kl=jax.value_and_grad(kl, (0, 1, 2))(q_i, k_i, w))

    return jax.jit(make)(jax.random.PRNGKey(seed))


def dense_scores(q_i, k_i, w):
    x = jnp.einsum("bhte,bse->bhts", q_i, k_i, precision="highest")
    return jnp.einsum("bth,bhts->bts", w, jax.nn.relu(x),
                      precision="highest")


CAUSAL = np.tril(np.ones((SEQ, SEQ), bool))


def test_the_score_product_is_the_einsums():
    made = pieces()
    want = dense_scores(made["q_i"], made["k_i"], made["w"])
    close(jnp.where(CAUSAL, made["scores"], 0.0), jnp.where(CAUSAL, want, 0.0))


@pytest.mark.parametrize("topk", [1, 7, TOPK, SEQ - 1])
@pytest.mark.parametrize("ties", [False, True])
def test_the_threshold_is_the_sorts(topk, ties):
    """`select` against the reference's sort, on scores with many exact ties
    (rounded to a few values) and without: the same keys, every key of a tie
    at the threshold kept and counted."""
    scores = pieces()["scores"]
    if ties:
        scores = jnp.round(scores * 2.0) / 2.0
    got = jax.jit(dsa.select, static_argnums=1)(scores, topk)
    want = jax.jit(jax.vmap(lambda s: reference.selection(
        s, jnp.arange(SEQ), topk)))(scores)
    assert ((np.asarray(got.chosen) != 0) == np.asarray(want)).all()
    assert int(got.keys_selected) == int(want.sum())
    assert int(got.threshold_ties) == int(want.sum()) \
        - 2 * ops_count_keye.selected_pairs(SEQ, topk)
    # (untied scores tie too, at zero: four heads' ReLUs all shut)
    assert int(got.threshold_ties) >= 0 and (int(got.threshold_ties) > 0 or not ties
                                   or topk == SEQ - 1)
    assert int(got.keys_causal) == 2 * ops_count_keye.causal_pairs(SEQ)


def test_the_target_pass_is_the_heads_mean_probability():
    made = pieces()
    kept = np.asarray(made["chosen"].chosen) != 0
    logits = jnp.einsum("bhqd,bhkd->bhqk", made["q"], made["k"],
                        precision="highest") * HEAD_DIM ** -0.5
    want = jax.nn.softmax(jnp.where(kept[:, None], logits, -jnp.inf),
                          -1).mean(1)
    close(jnp.where(CAUSAL, made["probs"], 0.0), want, 1e-4)
    np.testing.assert_allclose(want.sum(-1), 1.0, rtol=1e-5)


def test_the_indexers_loss_and_its_closed_form_gradient():
    made = pieces()
    kept = jnp.asarray(made["chosen"].chosen) != 0
    p = jnp.where(kept, made["probs"], 0.0)

    def dense(q_i, k_i, w):
        log_q = jax.nn.log_softmax(jnp.where(
            kept, dense_scores(q_i, k_i, w), -jnp.inf), -1)
        return jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                     - jnp.where(kept, log_q, 0.0)),
                         0.0).sum() / (2 * SEQ)

    want, want_grads = with_highest(jax.value_and_grad(dense, (0, 1, 2)))(
        made["q_i"], made["k_i"], made["w"])
    got, got_grads = made["kl"]
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for g, w in zip(got_grads, want_grads):
        close(g, w, 1e-3)


def test_rows_off_the_grid_are_refused():
    with pytest.raises(ValueError, match="grid"):
        dsa.index_scores(jnp.zeros((1, 2, 200, 8)), jnp.zeros((1, 200, 8)),
                         jnp.zeros((1, 200, 2)), True)
    with pytest.raises(ValueError, match="selects nothing"):
        dsa.select(jnp.zeros((1, 128, 128)), 128)


# --- the counts the benchmark prices the step by -------------------------------

def test_the_cells_counts():
    """43.75 % of the causal pairs at the cell's shape; attention counted
    over the selected pairs, the indexer and the kernels' own rooflines over
    the causal ones."""
    selected = ops_count_keye.selected_pairs(8192, 2048)
    causal = ops_count_keye.causal_pairs(8192)
    assert selected == sum(min(t + 1, 2048) for t in range(8192))
    assert abs(100.0 * selected / causal - 43.75) < 0.1
    assert ops_count_keye.selected_pairs(1024, 2048) \
        == ops_count_keye.causal_pairs(1024)
    shape = {"hidden": 2048, "vocab": 18992, "layers": 6,
             "attention": {"heads": 32, "kv_heads": 4, "head_dim": 128},
             "indexer": {"heads": 16, "head_dim": 64, "topk": 2048},
             "experts": {"num_experts": 128, "expert_width": 768}}
    ops = ops_count_keye.keye_lm_train_ops_per_token(shape, 8192, 1.0, 1.5)
    assert ops["attention"] == 3 * 6 * 4 * 128 * 32 * selected / 8192
    parts = ("attention", "indexer", "experts", "attention_projections",
             "router", "head")
    assert abs(sum(ops[p] for p in parts) / ops["total"] - 1) < 1e-12
    kernel = ops_count_keye.flash_kernel(8192, 32, 128, 6)
    assert kernel["fwd"]["ops"] == 6 * 4 * 128 * 32 * causal / 8192
    assert kernel["bwd"]["ops"] == 2.5 * kernel["fwd"]["ops"]
    assert kernel["fwd"]["ops"] > ops["attention"] / 3


# --- the chip's compiler, without the chip -------------------------------------

@pytest.mark.parametrize("kernel", ["index", "index_backward", "probs",
                                    "flash_selected"])
def test_the_cells_kernels_compile_for_the_chip(v5e, kernel):
    """Each kernel of the cell at the cell's shape — 8,192 rows, 32 heads of
    128, an indexer of 16 heads of 64, `topk` 2,048 — through libtpu for a
    described v5e: scoped VMEM (the combined backward holds an int8 tile
    more than the causal one) and tiling, which the interpreter cannot
    refuse."""
    from jax.sharding import SingleDeviceSharding

    seq, heads, d, index_heads, e, topk = 8192, 32, 128, 16, 64, 2048

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))

    q_i = shaped((1, index_heads, seq, e), jnp.bfloat16)
    k_i = shaped((1, seq, e), jnp.bfloat16)
    w = shaped((1, seq, index_heads), jnp.float32)
    q = shaped((1, heads, seq, d), jnp.bfloat16)
    square = shaped((1, seq, seq), jnp.float32)
    chosen = shaped((1, seq, seq), jnp.int8)
    lse = shaped((1, heads, seq), jnp.float32)

    def loss(q, k, v, chosen):
        return masked_flash_attention(
            q, k, v, Selected(topk), chosen,
            interpret=False)[0].astype(jnp.float32).sum()

    fn, args, calls = {
        "index": (lambda *a: dsa._index_scores(*a, False), (q_i, k_i, w), 1),
        "index_backward": (lambda *a: dsa._index_backward(*a, False),
                           (q_i, k_i, w, square), 2),
        "probs": (lambda *a: dsa._head_probs(*a, d ** -0.5, False),
                  (q, q, lse, chosen), 1),
        "flash_selected": (jax.grad(loss, (0, 1, 2)), (q, q, q, chosen), 2),
    }[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('"tpu_custom_call"') == calls
