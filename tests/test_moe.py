"""The sparse-expert layer of models.TransformerLM (OLMoE's block) against the
plain float32 reference the benchmark keeps (benchmark/reference/moe_lm.py):
a loop over the shard's experts, each applied to every token — no sorting, no
grouped matmul.  CPU, float32, seeded weights, small sizes.

Tolerances: both sides are float32 and differ in the order of their sums
(grouped rows against masked whole batches, scatter-add against a loop), so
they agree to a few units of float32 rounding accumulated over two layers:
2e-5 relative.  bfloat16 anywhere (2^-8 a rounding) would read 1e-3 to 1e-2
and fail every case.
"""

import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import moe_lm as reference
from horovod_tpu.common import metrics
from horovod_tpu.models import (MoEConfig, TransformerLM,
                                moe_next_token_loss, next_token_loss,
                                record_expert_rows, router_losses)
from horovod_tpu.models.transformer import SparseExperts
from horovod_tpu.ops import moe as moe_ops
from horovod_tpu.ops.moe import (HELD_PAIRS_PER_ROW, ROW_SLAB_WIDTH,
                                 ROW_WALK_PAIRS_PER_ROW, WHOLE_ROW_WIDTH,
                                 buffer_rows_to_tokens, dispatch_rows,
                                 pass_back, token_rows_to_buffer, top_choices,
                                 walks_rows, way_back)

RTOL = 2e-5
VOCAB, HIDDEN, HEADS, LAYERS, SEQ = 256, 64, 2, 2, 128
EXPERTS, PER_TOKEN, WIDTH = 8, 2, 32
SHARDS = [(0, 1), (0, 4), (3, 4)]


def lm(shard=(0, 1), use_flash=False, moe=None, hidden=HIDDEN):
    return TransformerLM(
        vocab_size=VOCAB, d_model=hidden, n_layers=LAYERS, n_heads=HEADS,
        dtype=jnp.float32, use_flash=use_flash, qk_norm=True, norm_eps=1e-5,
        moe=moe or MoEConfig(EXPERTS, PER_TOKEN, WIDTH, shard))


def reference_config(shard):
    return dict(num_experts=EXPERTS, experts_per_token=PER_TOKEN,
                expert_shard=shard, norm_eps=1e-5)


def seeded(model, seed=0, batch=1):
    def make(key):
        keys = jax.random.split(key, 3)
        tokens = jax.random.randint(keys[0], (batch, SEQ + 1), 0, VOCAB)
        params = model.init(keys[1], tokens[:, :-1])["params"]
        # Scales away from one, so that a norm left out or misplaced shows.
        params = jax.tree.map(
            lambda a: a * (1 + 0.1 * jax.random.normal(keys[2], a.shape)),
            params)
        return params, (tokens[:, :-1], tokens[:, 1:])

    return jax.jit(make)(jax.random.PRNGKey(seed))


def system_terms(model, params, batch):
    logits, state = model.apply({"params": params}, batch[0],
                                mutable=["router"])
    return (next_token_loss(logits, batch[1]),) \
        + router_losses(state["router"])


def system_loss(model, params, batch):
    logits, state = model.apply({"params": params}, batch[0],
                                mutable=["router"])
    return moe_next_token_loss(logits, batch[1], state["router"])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def both_sides(model, params, batch, **config):
    """((loss, (the three terms, what the layers sowed)), every gradient) of
    the model and ((loss, the three terms), every gradient) of the reference
    under `config`: one program a side, where op by op every primitive of
    two layers compiles alone."""
    def system(params):
        logits, state = model.apply({"params": params}, batch[0],
                                    mutable=["router", "intermediates"])
        terms = (next_token_loss(logits, batch[1]),) \
            + router_losses(state["router"])
        return moe_next_token_loss(logits, batch[1], state["router"]), (
            terms, state["intermediates"])

    def plain(params):
        return reference.loss(params, batch, **config), \
            reference.loss_terms(params, batch, **config)

    return tuple(jax.jit(jax.value_and_grad(side, has_aux=True))(params)
                 for side in (system, plain))


@functools.cache
def sides_of(shard, seed):
    model = lm(shard)
    return both_sides(model, *seeded(model, seed), **reference_config(shard))


def assert_terms_match(got, want):
    for g, w in zip(got, want):
        assert abs(float(g) - float(w)) <= RTOL * abs(float(w))


def assert_leaves_match(got, want):
    """Every gradient leaf, named: the paths of `want`."""
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(want)[0]]
    for path, g, w in zip(paths, jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all(), path
        assert rel(g, w) <= 10 * RTOL, (path, rel(g, w))
    return paths


@pytest.mark.parametrize("term", [0, 1, 2],
                         ids=["cross_entropy", "load_balance", "router_z"])
@pytest.mark.parametrize("shard", SHARDS, ids=str)
def test_loss_terms_match_reference(shard, term):
    ((_, (got, _)), _), ((_, want), _) = sides_of(shard, 0)
    assert_terms_match(got[term:term + 1], want[term:term + 1])


@pytest.mark.parametrize("shard", SHARDS, ids=str)
def test_every_gradient_leaf_matches_reference(shard):
    (_, got), (_, want) = sides_of(shard, 1)
    assert len(assert_leaves_match(got, want)) == 3 + LAYERS * 10


# Rows wide enough that the row walk takes them a slab of columns at a time
# (`ops.moe.way_back`): two whole slabs and a narrower last one.
WIDE = WHOLE_ROW_WIDTH + ROW_SLAB_WIDTH // 4


@pytest.mark.parametrize("hidden,form", [(HIDDEN, "rows"),
                                         (WIDE, "row_slabs")])
def test_a_shard_that_walks_its_buffer_matches_reference(hidden, form):
    """A sixteenth of 32 experts, 4 a token, 1,024 tokens for a buffer of 512
    rows: the way back to the tokens is the scatter-add of the buffer's rows
    (`ops.moe.walks_rows`), whole or in column slabs where the model is
    wide, and the three loss terms and every gradient leaf are the
    reference's, which knows no buffer."""
    model = lm(moe=MoEConfig(32, 4, WIDTH, (0, 16), 1.0), hidden=hidden)
    ((_, (terms, sown)), got), ((_, want_terms), want) = both_sides(
        model, *seeded(model, seed=12, batch=8), num_experts=32,
        experts_per_token=4, expert_shard=(0, 16), norm_eps=1e-5)
    seen = record_expert_rows(sown)
    assert seen["rows_walked"] == [512] * LAYERS
    assert seen["way_back"] == [form] * LAYERS
    assert seen["rows_over_bound"] == 0
    assert_terms_match(terms, want_terms)
    assert_leaves_match(got, want)


def test_loss_is_the_three_terms_with_olmoe_coefficients():
    model = lm()
    params, batch = seeded(model, seed=2)
    xent, balance, z = jax.jit(
        lambda p: system_terms(model, p, batch))(params)
    total = jax.jit(lambda p: system_loss(model, p, batch))(params)
    assert float(total) == pytest.approx(
        float(xent + 0.01 * balance + 0.001 * z), rel=1e-6)
    assert 0.9 < float(balance) < 1.5   # 1 for a uniform router


def layer_and_input(shard, seed=3, row_bound=None, tokens=SEQ):
    layer = SparseExperts(MoEConfig(EXPERTS, PER_TOKEN, WIDTH, shard,
                                    row_bound), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(keys[0], (1, tokens, HIDDEN))
    return layer, layer.init(keys[1], x)["params"], x


def test_shards_sum_to_the_whole_layer():
    whole, params, x = layer_and_input((0, 1))
    want = whole.apply({"params": params}, x)
    local = EXPERTS // 4
    total = 0.0
    for i in range(4):
        part = dict(params, **{
            name: params[name][i * local:(i + 1) * local]
            for name in ("gate_kernel", "up_kernel", "down_kernel")})
        total = total + SparseExperts(
            MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (i, 4)), jnp.float32).apply(
                {"params": part}, x)
    assert rel(total, want) <= RTOL


def test_one_expert_takes_every_row_and_one_takes_none():
    """No drop and no NaN at the extremes of routing: expert 0 is in every
    token's choice, expert 1 in none."""
    layer, params, x = layer_and_input((0, 1), seed=4)
    x = jnp.abs(x)                       # all positive: a column of ones
    kernel = params["router_kernel"]     # then fixes an expert's rank
    params = dict(params, router_kernel=kernel.at[:, 0].set(1.0)
                  .at[:, 1].set(-1.0))

    def run(params, x):
        out, state = layer.apply({"params": params}, x,
                                 mutable=["intermediates"])
        return out, state["intermediates"]

    out, seen = run(params, x)
    rows = np.asarray(seen["rows_per_local_expert"][0])
    assert rows[0] == SEQ and rows[1] == 0 and rows.sum() == SEQ * PER_TOKEN
    assert int(seen["rows_over_bound"][0]) == 0
    flat = x.reshape(-1, HIDDEN)
    _, weights, experts = reference.router(flat, params["router_kernel"],
                                           PER_TOKEN)
    want = reference.experts_of_shard(flat, params, weights, experts, 0)
    assert rel(out.reshape(-1, HIDDEN), want) <= RTOL
    grads = jax.grad(lambda p: run(p, x)[0].sum())(params)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["gate_kernel"][1]).max()) == 0.0
    assert float(jnp.abs(grads["gate_kernel"][0]).max()) > 0.0


@pytest.mark.parametrize("row_bound,held", [(None, 1024),
                                            (1.0, 512), (4.0, 1024)])
def test_rows_over_a_bound_are_counted(row_bound, held):
    """Tokens * k = 1,024 pairs, 4 shards: a bound of 1.0 is 256 rows rounded
    up to 512, and every row the router sends this shard past what the buffer
    holds is in the counter; with no bound the buffer holds every pair."""
    tokens = 512
    layer, params, x = layer_and_input((0, 4), seed=5, row_bound=row_bound,
                                       tokens=tokens)
    x = jnp.abs(x)
    kernel = params["router_kernel"]
    params = dict(params, router_kernel=kernel.at[:, 0].set(1.0)
                  .at[:, 1].set(0.9))      # both local experts, every token
    _, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    seen = state["intermediates"]
    routed = int(np.asarray(seen["rows_per_local_expert"][0]).sum())
    assert routed == tokens * PER_TOKEN
    assert int(seen["rows_over_bound"][0]) == max(0, routed - held)


# A shard with a sixteenth of 32 experts, 4 a token, its buffer bounded at the
# balanced share: 8 x 128 tokens are 4,096 pairs for 512 rows, 8 pairs a row —
# the side of `ops.moe.walks_rows` on which the way back walks the buffer.
FEW_EXPERTS = MoEConfig(32, 4, WIDTH, (0, 16), 1.0)


@pytest.mark.parametrize("moe,batch_size,walked,hidden,form", [
    (MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (0, 4)), 1, SEQ * PER_TOKEN,
     HIDDEN, "pairs"),
    (FEW_EXPERTS, 8, 512, HIDDEN, "rows"),
    (FEW_EXPERTS, 8, 512, WIDE, "row_slabs")],
    ids=["(0, 4)", "(0, 16)", "(0, 16)-wide"])
def test_counters_reach_the_caller_and_the_registry(moe, batch_size, walked,
                                                    hidden, form):
    """Rows per local expert, rows over the bound, the rows a pass back to
    the tokens walks (every pair's for a quarter of the experts, the
    buffer's for a sixteenth) and that pass's form (the buffer's rows whole,
    or in column slabs where the model is wide), per layer, from the
    `intermediates` collection; mirrored into hvd.metrics when it is on."""
    model = lm(moe=moe, hidden=hidden)
    params, batch = seeded(model, seed=8, batch=batch_size)
    _, state = model.apply({"params": params}, batch[0],
                           mutable=["intermediates"])
    was_on = metrics.registry.enabled
    metrics.registry.enable()
    try:
        seen = record_expert_rows(state["intermediates"])
        mirrored = metrics.registry.snapshot()["moe"]
    finally:
        if not was_on:
            metrics.registry.disable()
    rows = np.asarray(seen["rows_per_local_expert"])
    assert rows.shape == (LAYERS, 2) and seen["rows_over_bound"] == 0
    chosen = np.asarray(state["intermediates"]["layer_1"]["moe"]
                        ["chosen_experts"][0])
    assert rows[1].tolist() == [(chosen == e).sum() for e in range(2)]
    assert mirrored["rows_per_local_expert"] == rows.tolist()
    assert seen["rows_walked"] == [walked] * LAYERS == mirrored["rows_walked"]
    assert seen["way_back"] == [form] * LAYERS == mirrored["way_back"]
    text = metrics.prometheus_text(
        {**metrics.registry.snapshot(), "moe": mirrored})
    assert "hvd_tpu_moe_expert_rows{layer=\"1\",expert=\"0\"} " \
        f"{rows[1][0]}" in text
    assert f"hvd_tpu_moe_rows_walked{{layer=\"1\"}} {walked}" in text
    assert f"hvd_tpu_moe_way_back{{layer=\"1\",form=\"{form}\"}} 1" in text


def test_dispatch_sorts_local_experts_first():
    experts = jnp.array([5, 2, 7, 3, 2, 0, 3, 6], jnp.int32)
    sent = dispatch_rows(experts, 2, 2, 8)
    assert sent.group_sizes.tolist() == [2, 2]
    assert experts[sent.pair][:4].tolist() == [2, 2, 3, 3]
    assert sent.pair[:4].tolist() == [1, 4, 3, 6]       # stable
    assert int(sent.rows_over_bound) == 0
    cut = dispatch_rows(experts, 2, 2, 3)
    assert cut.group_sizes.tolist() == [2, 1]
    assert cut.rows_per_expert.tolist() == [2, 2]
    assert int(cut.rows_over_bound) == 1


# The two row movers against the scatter-add formulation they replaced,
# written out here as the layer had it (PR 26): the forward gather's autodiff
# backward scatter-adds in the rows' dtype, the combine scatter-adds float32
# products.  float32: the two differ in the order of a token's k terms,
# 2e-6.  bfloat16: the combine's products and sums are float32 on both sides
# and differ as in float32; the dispatch's backward sum is float32 rounded
# once where the scatter-add rounds after every row, so the limit there is
# bfloat16's rounding over k terms (2^-8 each) — and the gather form must be
# the CLOSER of the two to a float32 sum.
MOVER_TOKENS, MOVER_WIDTH = 96, 16
BOUNDS = {"every_pair": None, "cut": 40}
# Beside the layouts above, a shard with a sixteenth of 32 experts, 4 a token:
# 384 pairs for a buffer of 40 rows (it holds the ~24 routed here) or of 16 (it
# cuts some off), 9.6 and 24 pairs a row — the side of `walks_rows` on which
# the way back to the tokens walks the buffer's rows by a scatter-add.
FEW = (32, 4)
# The same shard with rows of WIDE elements, which the row walk adds a slab of
# columns at a time (`ops.moe.way_back`: "row_slabs"), a layout's fifth and
# sixth entry: tokens with no row here and with one ("routed"); every held row
# one token's, a row at each local expert ("one_token"); no token choosing an
# expert of this shard, so no held row at all ("none_held").
FEW_WIDE = [FEW + ((0, 16), 40, WIDE, "routed"),
            FEW + ((5, 16), 40, WIDE, "routed"),
            FEW + ((0, 16), 16, WIDE, "routed"),
            FEW + ((0, 16), 40, WIDE, "one_token"),
            FEW + ((0, 16), 40, WIDE, "none_held")]
LAYOUTS = [(EXPERTS, PER_TOKEN, shard, bound) for shard in SHARDS
           for bound in BOUNDS.values()] \
    + [FEW + ((0, 16), 40), FEW + ((5, 16), 40), FEW + ((0, 16), 16)] \
    + FEW_WIDE


def layout_id(layout):
    experts, k, shard, bound, *wide = layout
    return "-".join([f"{experts}x{k}", str(shard),
                     str(bound or "every_pair")]
                    + [str(w) for w in wide]).replace(" ", "")


def routed(layout, dtype, seed=9):
    """Seeded routing and rows: (sent, flat, out, weight, d_rows, d_mixed),
    the last two the cotangents of the buffer's rows and of the tokens'."""
    experts, k, (i, n), bound, *wide = layout
    width, routing = wide or (MOVER_WIDTH, "routed")
    local = experts // n
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    probs = jax.nn.softmax(jax.random.normal(keys[0],
                                             (MOVER_TOKENS, experts)))
    if routing != "routed":
        here = slice(i * local, (i + 1) * local)
        probs = probs.at[:, here].set(0.0)
        if routing == "one_token":
            probs = probs.at[5, here].set(1.0)
    weight, expert = jax.lax.top_k(probs, k)
    rows = MOVER_TOKENS * k if bound is None else bound
    sent = dispatch_rows(expert, i * local, local, rows)
    assert walks_rows(sent) == (experts == FEW[0])
    # Static shapes alone; in this process (no TPU) `held_pairs` is `pairs`.
    assert way_back(sent, width) == (
        ("row_slabs" if wide else "rows") if experts == FEW[0]
        else "held_pairs" if MOVER_TOKENS * k >= HELD_PAIRS_PER_ROW * rows
        else "pairs")
    assert pass_back(jax.ShapeDtypeStruct((rows, width), dtype), sent) \
        == way_back(sent, width).replace("held_pairs", "pairs")
    if wide:
        held = np.asarray(sent.token_of_row)[:int(sent.group_sizes.sum())]
        rows_of_token = np.bincount(held, minlength=MOVER_TOKENS)
        assert sorted(set(rows_of_token.tolist()) - {local}) == {
            "routed": [0, 1], "one_token": [0], "none_held": [0]}[routing]
        assert (rows_of_token[5] == local) == (routing == "one_token")
    flat = jax.random.normal(keys[1], (MOVER_TOKENS, width), dtype)
    # As grouped_matmul leaves it and its backward hands it down: rows past
    # the last group are zero.
    inside = (jnp.arange(rows) < sent.group_sizes.sum())[:, None]
    out = jnp.where(inside, jax.random.normal(
        keys[2], (rows, width), dtype), 0)
    d_rows = jnp.where(inside, jax.random.normal(
        keys[3], (rows, width), dtype), 0)
    d_mixed = jax.random.normal(keys[4], (MOVER_TOKENS, width), dtype)
    return sent, flat, out, weight, d_rows, d_mixed


def scatter_add_dispatch(flat, sent):
    return flat[sent.pair // sent.position.shape[-1]]


def scatter_add_combine(out, weight, sent):
    weighted = out.astype(jnp.float32) \
        * weight.reshape(-1)[sent.pair][:, None]
    return jnp.zeros((MOVER_TOKENS, out.shape[1]), jnp.float32).at[
        sent.pair // sent.position.shape[-1]].add(weighted).astype(out.dtype)


def close(got, want, dtype, what):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    limit = 2e-6 if dtype == jnp.float32 else 2.0 ** -6
    assert np.abs(got - want).max() <= limit * max(1.0, np.abs(want).max()), \
        (what, np.abs(got - want).max())


DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["float32", "bfloat16"])
EVERY_LAYOUT = pytest.mark.parametrize("layout", LAYOUTS, ids=layout_id)


@DTYPES
@EVERY_LAYOUT
def test_token_rows_to_buffer_matches_the_scatter_add_form(layout, dtype):
    sent, flat, _, _, d_rows, _ = routed(layout, dtype)
    _, _, (_, n_shards), bound = layout[:4]
    if bound is not None and (n_shards == 1 or bound == 16):
        assert int(sent.rows_over_bound) > 0
    got, back = jax.vjp(lambda f: token_rows_to_buffer(f, sent), flat)
    want, back_scatter = jax.vjp(lambda f: scatter_add_dispatch(f, sent),
                                 flat)
    np.testing.assert_array_equal(got, want)
    (d_flat,), (d_scatter,) = back(d_rows), back_scatter(d_rows)
    assert d_flat.dtype == flat.dtype
    close(d_flat, d_scatter, dtype, "d_flat")
    exact = jax.vjp(lambda f: scatter_add_dispatch(f, sent),
                    flat.astype(jnp.float32))[1](
                        d_rows.astype(jnp.float32))[0]
    if sent.valid.any():
        assert rel(d_flat, exact) <= rel(d_scatter, exact) + 1e-7
    else:
        assert not d_flat.any() and not exact.any()


@DTYPES
@EVERY_LAYOUT
def test_buffer_rows_to_tokens_matches_the_scatter_add_form(layout, dtype):
    sent, _, out, weight, _, d_mixed = routed(layout, dtype)
    got, back = jax.vjp(lambda o, w: buffer_rows_to_tokens(o, w, sent),
                        out, weight)
    want, back_scatter = jax.vjp(
        lambda o, w: scatter_add_combine(o, w, sent), out, weight)
    assert got.dtype == out.dtype
    close(got, want, dtype, "mixed")
    (d_out, d_weight), (d_out_s, d_weight_s) = back(d_mixed), \
        back_scatter(d_mixed)
    assert d_out.dtype == out.dtype and d_weight.dtype == weight.dtype
    close(d_out, d_out_s, dtype, "d_out")
    close(d_weight, d_weight_s, jnp.float32, "d_weight")
    # Pairs with no row in the buffer: exactly zero, not nearly.
    assert float(jnp.abs(jnp.where(sent.valid, 0.0, d_weight)).max()) == 0.0
    assert (float(jnp.abs(jnp.where(sent.valid, d_weight, 0.0)).max()) > 0.0) \
        == bool(sent.valid.any())


@DTYPES
@pytest.mark.parametrize("layout", [
    (EXPERTS, PER_TOKEN, (0, 4), None), (EXPERTS, PER_TOKEN, (3, 4), None),
    FEW + ((0, 16), 40), FEW + ((5, 16), 40), FEW_WIDE[0], FEW_WIDE[3],
    FEW_WIDE[4]], ids=layout_id)
def test_rows_past_the_held_ones_take_no_part(layout, dtype):
    """Whatever the buffer holds at and past `group_sizes.sum()` — rows of
    experts held elsewhere, rows the kernel never wrote — reaches no token
    and no weight, on either side of `walks_rows`: NaN there changes not a
    bit of any result."""
    sent, flat, out, weight, d_rows, d_mixed = routed(layout, dtype)
    past = (jnp.arange(out.shape[0]) >= sent.group_sizes.sum())[:, None]
    assert int(past.sum()) > 0

    def results(out, d_rows):
        mixed, back = jax.vjp(lambda o, w: buffer_rows_to_tokens(o, w, sent),
                              out, weight)
        d_flat, = jax.vjp(lambda f: token_rows_to_buffer(f, sent),
                          flat)[1](d_rows)
        return (mixed, d_flat) + back(d_mixed)

    clean = results(out, d_rows)
    dirty = results(jnp.where(past, jnp.nan, out),
                    jnp.where(past, jnp.nan, d_rows))
    for a, b in zip(clean, dirty):
        assert np.isfinite(np.asarray(b, np.float32)).all()
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("pairs,width,form,rule", [
    (ROW_WALK_PAIRS_PER_ROW * 16, 4, "rows", "rows"),
    (ROW_WALK_PAIRS_PER_ROW * 16 - 1, 4, "pairs", "held_pairs"),
    (ROW_WALK_PAIRS_PER_ROW * 16, WHOLE_ROW_WIDTH, "rows", "rows"),
    (ROW_WALK_PAIRS_PER_ROW * 16, WHOLE_ROW_WIDTH + 1, "row_slabs",
     "row_slabs"),
    (ROW_WALK_PAIRS_PER_ROW * 16 - 1, WHOLE_ROW_WIDTH + 1, "pairs",
     "held_pairs"),
    (HELD_PAIRS_PER_ROW * 16, 4, "pairs", "held_pairs"),
    (HELD_PAIRS_PER_ROW * 16 - 1, 4, "pairs", "pairs")],
    ids=["at", "one_under", "widest_whole", "one_wider", "one_under_wide",
         "at_the_kernels", "one_under_the_kernels"])
def test_the_buffers_rows_are_walked_from_the_constant_on(pairs, width, form,
                                                          rule):
    """`walks_rows` and `way_back` read static shapes alone: pairs >= C *
    bound, then the row's width against `WHOLE_ROW_WIDTH` — one scatter-add
    of whole rows up to it, one a slab of `ROW_SLAB_WIDTH` columns past
    it; under C, from `HELD_PAIRS_PER_ROW` pairs a row on, the kernel's form,
    which a process off the TPU runs as the gather (`pass_back`)."""
    expert = jnp.arange(pairs, dtype=jnp.int32)[:, None] % EXPERTS
    sent = dispatch_rows(expert, 0, 1, 16)
    walks = form != "pairs"
    rows = jnp.ones((16, width), jnp.bfloat16)
    assert walks_rows(sent) is walks and way_back(sent, width) == rule
    assert pass_back(rows, sent) == form
    assert sent.rows_walked(form) == (16 if walks else pairs)
    text = jax.jit(lambda r: buffer_rows_to_tokens(
        r, jnp.ones((pairs, 1)), sent)).lower(rows).as_text()
    assert text.count('"stablehlo.scatter"') == {
        "pairs": 0, "rows": 1, "row_slabs": -(-width // ROW_SLAB_WIDTH)}[form]


@EVERY_LAYOUT
def test_position_is_the_inverse_of_the_sorted_order(layout):
    sent = routed(layout, jnp.float32)[0]
    k = layout[1]
    pairs = MOVER_TOKENS * k
    position = np.asarray(sent.position)
    assert position.shape == (MOVER_TOKENS, k)
    assert sorted(position.reshape(-1).tolist()) == list(range(pairs))
    valid = np.asarray(sent.valid).reshape(-1)
    held = int(sent.group_sizes.sum())
    assert valid.sum() == held == min(int(sent.rows_per_expert.sum()),
                                      len(sent.pair))
    flat_position = position.reshape(-1)
    assert (flat_position[valid] < held).all()
    assert (np.asarray(sent.pair)[flat_position[valid]]
            == np.arange(pairs)[valid]).all()
    # Inside the buffer the inverse holds for pairs of other shards too.
    inside = flat_position < len(sent.pair)
    assert (np.asarray(sent.pair)[flat_position[inside]]
            == np.arange(pairs)[inside]).all()
    assert (np.asarray(sent.token_of_row)
            == np.asarray(sent.pair) // k).all()


def test_top_choices_backward_is_top_k_s():
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(10),
                                             (MOVER_TOKENS, EXPERTS)))
    mix = jax.random.normal(jax.random.PRNGKey(11),
                            (MOVER_TOKENS, PER_TOKEN))
    got = jax.grad(lambda p: (top_choices(p, PER_TOKEN)[0] * mix).sum())(
        probs)
    want = jax.grad(lambda p: (jax.lax.top_k(p, PER_TOKEN)[0] * mix).sum())(
        probs)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(top_choices(probs, PER_TOKEN)[1],
                                  jax.lax.top_k(probs, PER_TOKEN)[1])


@pytest.mark.parametrize("row_bound", [None, 1.0], ids=["every_pair", "1.0"])
@pytest.mark.parametrize("shard", SHARDS, ids=str)
def test_layer_and_its_gradient_lower_to_no_scatter(shard, row_bound):
    """Rows move by gathers in both directions: neither the layer's program
    nor its gradient's holds a scatter, for the whole layer or a shard, with
    a bound or without."""
    layer, params, x = layer_and_input(shard, row_bound=row_bound,
                                       tokens=512)
    text = jax.jit(jax.grad(
        lambda p, x: layer.apply({"params": p}, x).sum(),
        (0, 1))).lower(params, x).as_text()
    assert "scatter" not in text and "while" not in text
    assert text.count("gather") >= 4


def lowered_layer_gradient(moe, tokens, hidden, dtype):
    """The text the gradient of one sparse-expert layer lowers to, by its
    weights and its input; shapes only."""
    layer = SparseExperts(moe, dtype)
    x = jax.ShapeDtypeStruct((1, tokens, hidden), dtype)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, dtype))["params"])
    return jax.jit(jax.grad(
        lambda p, x: jnp.square(layer.apply({"params": p}, x).astype(
            jnp.float32)).sum(), (0, 1))).lower(params, x).as_text()


@pytest.mark.parametrize("moe,hidden,scatters", [
    (MoEConfig(32, 4, WIDTH, (0, 4), 1.0), HIDDEN, 0),
    (MoEConfig(32, 4, WIDTH, (3, 16), 1.0), HIDDEN, 3),
    (MoEConfig(32, 4, WIDTH, (0, 16), None), HIDDEN, 0),
    (MoEConfig(32, 4, WIDTH, (3, 16), 1.0), 2560, 11),
    (MoEConfig(32, 4, WIDTH, (0, 4), 1.0), 2560, 0)],
    ids=["quarter", "sixteenth", "sixteenth_unbounded", "sixteenth_wide",
         "quarter_wide"])
def test_the_way_back_lowers_to_the_form_its_shapes_choose(moe, hidden,
                                                           scatters):
    """2,048 tokens, 8,192 pairs.  A quarter of the experts (2,048 rows, 4
    pairs a row) and a sixteenth with a buffer for every pair: gathers alone,
    as above, whatever the width.  A sixteenth bounded at its share (512
    rows, 16 pairs a row): the combine's forward and the dispatch's backward
    are scatter-adds of the buffer's rows, the weights' cotangent one scatter
    of their scalars, and no k-wide gather is left.  At Ling's 2,560 a row
    each of the two is five scatter-adds of 512 columns and none of whole
    rows: nothing as wide as the model is scattered into."""
    text = lowered_layer_gradient(moe, 2048, hidden, jnp.float32)
    assert "while" not in text
    assert text.count('"stablehlo.scatter"') == scatters
    k_wide = text.count(f"tensor<2048x{moe.experts_per_token}x{hidden}x")
    assert (k_wide == 0) == bool(scatters)
    into = re.findall(r'"stablehlo\.scatter".*?\}\) : \(.*?\) -> (tensor<[^>]*>)',
                      text, re.S)
    assert len(into) == scatters
    if scatters:
        assert set(into) == {
            f"tensor<2048x{min(hidden, ROW_SLAB_WIDTH)}xf32>",
            "tensor<8192xf32>"}


# The row walk of rows no wider than `WHOLE_ROW_WIDTH` must stay what it was
# before `way_back` read a width: the nemotron3super120b cell runs it (4,096
# tokens, 22 of 512 experts a token, 8 held here, a 2,560-row buffer: 35.2
# pairs a row, the latent 1,024 wide).  The digests are of the layer's
# lowered gradient at the parent commit of PR 33 (jax 0.9.0).
NARROW_WALK_DIGESTS = {
    1024: "4b073db8900cd7f29cdb0bee1adaa94a82e5a0c0a60be0fe2db9723a1f2345d4",
    64: "bd20536a70e30c68db1fe8525c4f9d6cc0a8e665801621136c0c1f3ab5594fa4"}


@pytest.mark.parametrize("hidden", list(NARROW_WALK_DIGESTS))
def test_narrow_rows_walk_as_the_parent_lowered_them(hidden):
    text = lowered_layer_gradient(MoEConfig(512, 22, WIDTH, (0, 64), 1.5),
                                  4096, hidden, jnp.bfloat16)
    assert text.count('"stablehlo.scatter"') == 3
    assert hashlib.sha256(text.encode()).hexdigest() \
        == NARROW_WALK_DIGESTS[hidden]


# The tiled kernels (`ops.moe.tiled_product`, interpreted here) against
# libtpu's forms at the two cells' widths that take them, a few hundred rows
# in two tiles of 256: k in two tiles at the first pair of widths and n in
# two at the second, so both the summing and the one-tile body run, and the
# kernels' inner loops make one pass or three (`ops.moe._chunk`).
TILED_ROWS, TILED_GROUPS, TILE_ROWS = 512, 4, 256
TILED_LAYOUTS = {
    "even": [128, 128, 128, 128],
    "a_group_of_no_rows": [256, 0, 200, 56],
    "a_boundary_inside_a_tile": [100, 60, 250, 102],
    "fewer_rows_than_the_buffer": [50, 60, 170, 0],
    "one_group_holds_everything": [0, TILED_ROWS, 0, 0]}
TILED_WIDTHS = {(2304, 896): (1152, 896), (2048, 768): (2048, 384)}


# The `held_pairs` kernel (`ops.moe.pair_rows`, interpreted here) against the
# `pairs` form's gather, mask, products and sum: 256 tokens in two tiles, 4
# choices of 16 experts, the second quarter of them held here.  The first
# tokens choose by hand — all 4 choices here (and all of ONE expert: a run of
# four rows a token), one here, none here — and the rest by a seeded router;
# the buffer holds every pair that is routed here or, "cut", fewer: the last
# expert's run ends at the bound and the rest is counted.
PAIR_TOKENS, PAIR_CHOICES, PAIR_EXPERTS, PAIR_WIDTH = 256, 4, 16, 256
PAIR_BOUNDS = {"every_pair": PAIR_TOKENS * PAIR_CHOICES, "cut": 160}


def pair_routing(bound, dtype, seed=52):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    probs = jax.nn.softmax(2 * jax.random.normal(
        keys[0], (PAIR_TOKENS, PAIR_EXPERTS)))
    weight, expert = jax.lax.top_k(probs, PAIR_CHOICES)
    for token, chosen in {0: [4, 5, 6, 7], 1: [5, 5, 5, 5], 2: [0, 6, 9, 12],
                          3: [0, 1, 2, 3], 130: [7, 6, 5, 4],
                          255: [12, 13, 14, 15]}.items():
        expert = expert.at[token].set(jnp.array(chosen, expert.dtype))
    sent = dispatch_rows(expert, 4, 4, bound)
    of_token = np.asarray(sent.valid).sum(axis=1)
    assert {0, 1, PAIR_CHOICES} <= set(of_token.tolist())
    buffer = jax.random.normal(keys[1], (bound, PAIR_WIDTH), dtype)
    return sent, buffer, weight


def pairs_form(buffer, sent, weight=None):
    return moe_ops._pairs_summed(buffer, sent, weight).astype(buffer.dtype)


@pytest.fixture
def held_pairs_here(monkeypatch):
    """A process that takes `held_pairs` at any buffer's size: the constant at
    zero, the backend said to be a TPU, the kernel interpreted."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(moe_ops, "HELD_PAIRS_BUFFER_BYTES", 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture
def held_pairs_quietly(held_pairs_here, monkeypatch):
    """`held_pairs_here` with the kernels run by Pallas's own interpreter
    (`interpret=True`, as the kernels' own tests run them) for a test that
    reads what a MODEL of several layers sows and no kernel's name: the TPU
    interpreter runs its callbacks on the CPU client's threads and, with the
    workers of a whole run beside it, stopped for good in the test below — one
    run in two on the parent of PR 54 under eight busy cores, twice in two
    whole runs of the suite."""
    for kernel in ("pair_rows", "tiled_product"):
        monkeypatch.setattr(moe_ops, kernel, functools.partial(
            getattr(moe_ops, kernel), interpret=True))


# A layer of whole tiles: 256 tokens of 128 wide, a buffer of 512 rows.
def tiled_layer(row_bound=None):
    layer = SparseExperts(MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (0, 4),
                                    row_bound), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(52), 3)
    x = jax.random.normal(keys[0], (2, SEQ, 128))
    mix = jax.random.normal(keys[1], (2, SEQ, 128))
    return layer, layer.init(keys[2], x)["params"], x, mix


# The form the way back takes in every benchmark cell's expert layers, from
# the cell's static shapes: (tokens a step a chip, form; None: no experts).
# OLMoE's builder pins its step's custom calls: its 101 MB buffer is under
# the one constant and its 2.67 pairs a row under the other; Mellum's 226 MB
# is past the first, SDAR's and Trinity's 5.33 pairs a row past the second.
CELL_WAYS_BACK = {
    "resnet50": (None, None), "pythia410m": (None, None),
    "olmoe1b7b": (8192, "pairs"), "nemotron3super120b": (4096, "rows"),
    "ling3flash": (8192, "row_slabs"), "trinitymini": (8192, "held_pairs"),
    "sdar30ba3b": (8192, "held_pairs"), "qwen3next80b": (4096, "row_slabs"),
    "mellum2": (16384, "held_pairs"), "ouro2p6b": (None, None),
    "keyevl2": (8192, "held_pairs"), "olmohybrid7b": (None, None),
    "granite4hmicro": (None, None), "joyaiflash": (8192, "row_slabs")}


# Tokens a step a chip of every benchmark cell's configuration, and the
# kernel its expert layers' products take on the chip (None: no experts).
# OLMoE's, Nemotron's and Ling's builders pin libtpu's custom calls in their
# step: a rule that moved them would read `correct: false` there.
CELL_KERNELS = {
    "resnet50": (None, None), "pythia410m": (None, None),
    "olmoe1b7b": (8192, "ragged_dot"),
    "nemotron3super120b": (4096, "ragged_dot"),
    "ling3flash": (8192, "ragged_dot"), "trinitymini": (8192, "ragged_dot"),
    "sdar30ba3b": (8192, "tiled"), "qwen3next80b": (4096, "ragged_dot"),
    "mellum2": (16384, "tiled"), "ouro2p6b": (None, None),
    "keyevl2": (8192, "tiled"), "olmohybrid7b": (None, None),
    "granite4hmicro": (None, None), "joyaiflash": (8192, "ragged_dot")}


CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")


def cell_experts(name):
    """(MoEConfig, the rows' width, the experts' width, local experts) of a
    benchmark configuration's expert layers; None where it has none."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        config = json.load(f)
    experts = config.get("num_experts") or config.get("n_routed_experts")
    if experts is None:
        return None
    moe = MoEConfig(experts, config["num_experts_per_tok"], 0,
                    tuple(config["expert_shard"]), config["row_bound"])
    return (moe, config.get("moe_latent_size") or config["hidden_size"],
            config.get("moe_intermediate_size") or config["intermediate_size"],
            experts // moe.expert_shard[1])


# The dense default: the parameter tree and the program TransformerLM lowers
# to must be what they were before the sparse-expert layer existed (the
# three pythia410m cells of the benchmark run it).  The digest is of
# `jax.jit(grad).lower(...).as_text()` (jax 0.9.0): the parent commit of PR
# 26's until PR 42, which re-recorded it on purpose — `rope` turns the pairs
# by a product with a signed permutation and has a backward of its own; a
# later change to the dense block re-records it again.
DENSE_DIGEST = (
    "400852088294e0dbc5b908d64a84738b13133dd814620311aa6fcd4e618ce15e")


def dense_lm():
    return TransformerLM(vocab_size=VOCAB, d_model=HIDDEN, n_layers=LAYERS,
                         n_heads=HEADS, d_ff=128, dtype=jnp.bfloat16,
                         logits_dtype=jnp.bfloat16, use_flash=False)


# The sparse-expert defaults: the program OLMoE's model lowers to must be
# what it was before MoEConfig grew the latent layer's fields (scoring,
# renormalised and scaled weights, non-gated experts, a latent width, a shared
# expert) and Attention its grouped heads, rotary switch and head share: the
# olmoe1b7b cell runs it.  The digests are of `jax.jit(grad).lower(...)
# .as_text()` (jax 0.9.0): the parent commit of PR 30's until PR 42, which
# re-recorded them on purpose with `rope`'s new form.
OLMOE_DIGESTS = {
    ((0, 4), 1.5):
    "b5acec99aefbd636a07145829e752d1c89a1914f461c77b4ab5e75585988cbae",
    ((0, 1), None):
    "22e3811ab4daf100a7b54f82fb5e7c1d5cf66c0e6ee3667f81969cede055fb9c"}
