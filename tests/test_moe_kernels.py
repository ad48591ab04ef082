"""The sparse-expert layer's kernels (the grouped products in libtpu's and in
the tiled form, the rows' way back by pairs), the form each benchmark
configuration takes, the dense default's unchanged program, the train step and
the example: the second half of tests/test_moe.py, whose sizes, helpers and
tolerances it reads.
"""

import functools
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.jax.train import build_train_step
from horovod_tpu.common import metrics
from horovod_tpu.models import (MoEConfig, TransformerLM, moe_next_token_loss,
                                next_token_loss, record_expert_rows)
from horovod_tpu.ops import moe as moe_ops
from horovod_tpu.ops.moe import (HELD_PAIRS_BUFFER_BYTES, HELD_PAIRS_PER_ROW,
                                 TILED_FORMS, WAYS_BACK, dispatch_rows,
                                 grouped_kernel, grouped_matmul, grouped_rows,
                                 grouped_weights, pair_rows, pair_rows_tiles,
                                 pass_back, product_kernel, walks_rows,
                                 way_back)
from tests.test_moe import (CELL_KERNELS, CELL_WAYS_BACK, CONFIGS,
                            DENSE_DIGEST, EXPERTS, FEW_EXPERTS, HEADS, HIDDEN,
                            LAYERS, OLMOE_DIGESTS, PAIR_BOUNDS, PAIR_TOKENS,
                            PER_TOKEN, RTOL, SEQ, TILED_GROUPS, TILED_LAYOUTS,
                            TILED_ROWS, TILED_WIDTHS, TILE_ROWS, VOCAB, WIDTH,
                            cell_experts, dense_lm, lm, pair_routing,
                            pairs_form, rel, seeded, system_loss, tiled_layer)
from tests.test_moe import held_pairs_here, held_pairs_quietly  # noqa: F401


@pytest.mark.parametrize("sizes", [[10, 0, 30, 5], [0, 0, 0, 0],
                                   [64, 0, 0, 0]], ids=str)
def test_grouped_matmul_and_its_gradients(sizes):
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    rows = jax.random.normal(keys[0], (64, 16))
    weights = jax.random.normal(keys[1], (4, 16, 8))
    mix = jax.random.normal(keys[2], (64, 8))
    sizes = jnp.array(sizes, jnp.int32)
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(64), side="right")

    def plain(rows, weights):
        return sum(jnp.where((group == g)[:, None], rows @ weights[g], 0.0)
                   for g in range(4))

    np.testing.assert_allclose(grouped_matmul(rows, weights, sizes),
                               plain(rows, weights), atol=1e-5)
    got = jax.grad(lambda r, w: (grouped_matmul(r, w, sizes) * mix).sum(),
                   (0, 1))(rows, weights)
    want = jax.grad(lambda r, w: (plain(r, w) * mix).sum(),
                    (0, 1))(rows, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("widths", list(TILED_WIDTHS), ids=str)
@pytest.mark.parametrize("layout", list(TILED_LAYOUTS))
@pytest.mark.parametrize("form", TILED_FORMS)
def test_tiled_kernels_match_libtpus_forms(form, layout, widths, dtype):
    (k, n), (tk, tn) = widths, TILED_WIDTHS[widths]
    keys = jax.random.split(jax.random.PRNGKey(50), 3)
    wide = jax.random.normal(keys[0], (TILED_ROWS, k), dtype)
    narrow = jax.random.normal(keys[1], (TILED_ROWS, n), dtype)
    weights = jax.random.normal(keys[2], (TILED_GROUPS, k, n), dtype)
    sizes = jnp.array(TILED_LAYOUTS[layout], jnp.int32)
    product, operands, tiles = {
        "fwd": (grouped_rows, (wide, weights, sizes), (TILE_ROWS, tk, tn)),
        "drows": (functools.partial(grouped_rows, transposed=True),
                  (narrow, weights, sizes), (TILE_ROWS, tn, tk)),
        "dweights": (grouped_weights, (wide, narrow, sizes),
                     (TILE_ROWS, tk, tn)),
    }[form]
    got = product(*operands, "tiled", tiles=tiles, interpret=True)
    want = product(*operands, "ragged_dot")
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    held = sum(TILED_LAYOUTS[layout])
    if form != "dweights":      # the tail: read back as zero, not garbage
        assert not got[held:].any() and not want[held:].any()
    # float32 sums in another order, rounded once: an ulp or two of the
    # largest entry.
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -20
    assert np.abs(got - want).max() <= 2 * ulp * max(np.abs(want).max(), 1)


@pytest.mark.parametrize("shape,kernel", [
    ((1024, 384, 128, 2), "tiled"), ((1024, 512, 512, 2), "ragged_dot"),
    ((512, 384, 128, 2), "ragged_dot")], ids=str)
def test_grouped_matmul_takes_the_kernel_its_shapes_choose(monkeypatch,
                                                           shape, kernel):
    """On a TPU backend (said here, the kernels interpreted) `grouped_matmul`
    runs the tiled kernels where `grouped_kernel` says so — a width that is
    no multiple of 512 and 512 rows a group — forward and both gradients,
    `ragged_dot` elsewhere, and gives the plain products' gradients either
    way."""
    from jax.experimental.pallas import tpu as pltpu

    from tests.test_ops import _pallas_call_names

    m, k, n, groups = shape
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    rows = jax.random.normal(keys[0], (m, k))
    weights = jax.random.normal(keys[1], (groups, k, n))
    mix = jax.random.normal(keys[2], (m, n))
    sizes = jnp.array([m // 4 + 3, m // 2], jnp.int32)
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(m), side="right")

    def plain(rows, weights):
        return sum(jnp.where((group == g)[:, None], rows @ weights[g], 0.0)
                   for g in range(groups))

    def loss(rows, weights):
        return (grouped_matmul(rows, weights, sizes) * mix).sum()

    assert grouped_kernel(k, n, m // groups) == kernel \
        == grouped_kernel(n, k, m // groups)
    assert product_kernel(rows, weights) == "ragged_dot"    # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert product_kernel(rows, weights) == kernel
    grad = jax.grad(loss, (0, 1))
    program = jax.make_jaxpr(grad)(rows, weights)
    names = sorted(_pallas_call_names(program.jaxpr))
    assert names == (["hvd_grouped_" + form for form in sorted(TILED_FORMS)]
                     if kernel == "tiled" else [])
    assert str(program).count(" ragged_dot_general[") \
        == (0 if kernel == "tiled" else 3)
    with pltpu.force_tpu_interpret_mode():
        got = grad(rows, weights)
    want = jax.grad(lambda r, w: (plain(r, w) * mix).sum(),
                    (0, 1))(rows, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain"])
@pytest.mark.parametrize("bound", list(PAIR_BOUNDS))
def test_pair_rows_kernel_matches_the_pairs_form(bound, weighted, dtype):
    sent, buffer, weight = pair_routing(PAIR_BOUNDS[bound], dtype)
    assert (int(sent.rows_over_bound) > 0) == (bound == "cut")
    assert pair_rows_tiles(PAIR_TOKENS, *buffer.shape)
    weight = weight if weighted else None
    got = pair_rows(buffer, sent, weight, interpret=True)
    want = pairs_form(buffer, sent, weight)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    # float32 sums in another order, rounded once: an ulp or two of the
    # largest entry; a token with no row here reads zero, not garbage.
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -20
    assert np.abs(got - want).max() <= 2 * ulp * max(np.abs(want).max(), 1)
    assert not got[~np.asarray(sent.valid).any(axis=1)].any()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain"])
def test_pair_rows_kernel_reads_no_row_a_pair_does_not_own(weighted, dtype):
    """NaN in every buffer row that no valid pair owns — the rows at and past
    `group_sizes.sum()`, which the kernel's last block of a run fetches with
    the run — changes not a bit of the result.  NaN in a row that a pair does
    own reaches that pair's token, and no token of another tile, whose blocks
    may hold the row too (inside the owner's tile the product's zeros carry
    it: `pair_rows` says so)."""
    sent, buffer, weight = pair_routing(PAIR_BOUNDS["every_pair"], dtype)
    weight = weight if weighted else None
    held = int(sent.group_sizes.sum())
    assert 0 < held < buffer.shape[0] and held % 16
    row = jnp.arange(buffer.shape[0])[:, None]
    clean = pair_rows(buffer, sent, weight, interpret=True)
    dirty = pair_rows(jnp.where(row >= held, jnp.nan, buffer), sent, weight,
                      interpret=True)
    assert np.isfinite(np.asarray(dirty, np.float32)).all()
    np.testing.assert_array_equal(clean, dirty)
    owned = held // 2
    one = np.asarray(pair_rows(jnp.where(row == owned, jnp.nan, buffer), sent,
                               weight, interpret=True), np.float32)
    owner = int(sent.token_of_row[owned])
    assert np.isnan(one[owner]).all()
    others = np.arange(PAIR_TOKENS) // 128 != owner // 128
    np.testing.assert_array_equal(one[others],
                                  np.asarray(clean, np.float32)[others])


def test_the_layer_under_held_pairs_matches_the_pairs_form(request):
    """The layer's output and every gradient, by its weights and by its
    input, with the way back through the kernel (both callers: the
    combine's forward, the dispatch's backward) against the same layer
    through the `pairs` form: the two differ in the order of a token's k
    float32 terms."""
    from tests.test_ops import _pallas_call_names

    layer, params, x, mix = tiled_layer()

    def loss(params, x):
        return (layer.apply({"params": params}, x) * mix).sum()

    grad = jax.value_and_grad(loss, (0, 1))
    want = grad(params, x)
    assert "hvd_moe_pair_rows" not in _pallas_call_names(
        jax.make_jaxpr(grad)(params, x).jaxpr)
    request.getfixturevalue("held_pairs_here")
    names = _pallas_call_names(jax.make_jaxpr(grad)(params, x).jaxpr)
    assert names.count("hvd_moe_pair_rows") == 2
    got = grad(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        assert rel(g, w) <= 10 * RTOL


def test_held_pairs_reaches_the_gauge_and_the_registry(held_pairs_quietly):
    """The fourth form under its own name: in the `intermediates`
    collection, in `metrics_snapshot()["moe"]` and in the exposition, with a
    pass's rows the buffer's and not every pair's."""
    model = lm(moe=MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (0, 4), 1.0),
               hidden=128)
    params, batch = seeded(model, seed=8, batch=8)
    _, state = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"]))(params, batch[0])
    was_on = metrics.registry.enabled
    metrics.registry.enable()
    try:
        seen = record_expert_rows(state["intermediates"])
        mirrored = metrics.registry.snapshot()["moe"]
    finally:
        if not was_on:
            metrics.registry.disable()
    assert seen["way_back"] == ["held_pairs"] * LAYERS == mirrored["way_back"]
    assert seen["rows_walked"] == [512] * LAYERS == mirrored["rows_walked"]
    assert 512 < 8 * SEQ * PER_TOKEN
    text = metrics.prometheus_text(
        {**metrics.registry.snapshot(), "moe": mirrored})
    assert 'hvd_tpu_moe_way_back{layer="1",form="held_pairs"} 1' in text
    assert 'hvd_tpu_moe_rows_walked{layer="1"} 512' in text


@pytest.mark.parametrize("name", list(CELL_WAYS_BACK))
def test_the_way_back_on_the_benchmark_configurations(monkeypatch, name):
    assert sorted(CELL_WAYS_BACK) == sorted(CELL_KERNELS)
    tokens, form = CELL_WAYS_BACK[name]
    if form is None:
        assert cell_experts(name) is None
        return
    moe, width, _, local = cell_experts(name)
    k = moe.experts_per_token
    sent = jax.eval_shape(
        lambda e: dispatch_rows(e, 0, local, moe.buffer_rows(tokens)),
        jax.ShapeDtypeStruct((tokens, k), jnp.int32))
    rows = jax.ShapeDtypeStruct((moe.buffer_rows(tokens), width),
                                jnp.bfloat16)
    assert way_back(sent, width, 2) == form
    assert (rows.shape[0] * width * 2 > HELD_PAIRS_BUFFER_BYTES
            or tokens * k >= HELD_PAIRS_PER_ROW * rows.shape[0]) \
        == (form == "held_pairs") or walks_rows(sent)
    # In a process off the TPU the kernel's form falls to the gather's.
    assert pass_back(rows, sent) == ("pairs" if form == "held_pairs"
                                     else form)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pass_back(rows, sent) == form


@pytest.mark.parametrize("tokens,rows,width,whole", [
    (256, 512, 128, True), (200, 512, 128, False), (256, 500, 128, False),
    (256, 512, 192, False)], ids=str)
def test_held_pairs_wants_whole_tiles(monkeypatch, tokens, rows, width,
                                      whole):
    """Past the constant, on a TPU: the kernel where the tokens are whole
    tiles of 128, the buffer whole blocks of 16 rows and a row whole lanes;
    the `pairs` form elsewhere."""
    monkeypatch.setattr(moe_ops, "HELD_PAIRS_BUFFER_BYTES", 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sent = jax.eval_shape(lambda e: dispatch_rows(e, 0, 2, rows),
                          jax.ShapeDtypeStruct((tokens, 4), jnp.int32))
    buffer = jax.ShapeDtypeStruct((rows, width), jnp.bfloat16)
    assert way_back(sent, width, 2) == "held_pairs" == WAYS_BACK[3]
    assert pair_rows_tiles(tokens, rows, width) is whole
    assert pass_back(buffer, sent) == ("held_pairs" if whole else "pairs")
    assert sent.rows_walked("held_pairs") == rows
    assert sent.rows_walked("pairs") == tokens * 4


def test_every_benchmark_configuration_is_in_the_table():
    assert sorted(CELL_KERNELS) == sorted(
        name[:-5] for name in os.listdir(CONFIGS))


@pytest.mark.parametrize("name", list(CELL_KERNELS))
def test_the_rule_on_the_benchmark_configurations(monkeypatch, name):
    tokens, kernel = CELL_KERNELS[name]
    if kernel is None:
        assert cell_experts(name) is None
        return
    moe, k, n, local = cell_experts(name)
    rows = jax.ShapeDtypeStruct((moe.buffer_rows(tokens), k), jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for a, b in ((k, n), (n, k)):       # up and down
        weights = jax.ShapeDtypeStruct((local, a, b), jnp.bfloat16)
        assert product_kernel(rows, weights) == kernel, (a, b)


def test_dense_default_parameter_tree_is_unchanged():
    shapes = jax.eval_shape(
        lambda: dense_lm().init(jax.random.PRNGKey(0),
                                jnp.zeros((1, SEQ), jnp.int32))["params"])
    flat = {jax.tree_util.keystr(path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    layer = {"['attn']['o_kernel']": (HEADS, HIDDEN // HEADS, HIDDEN),
             "['attn']['qkv_kernel']": (HIDDEN, 3, HEADS, HIDDEN // HEADS),
             "['attn_norm']['scale']": (HIDDEN,),
             "['down']['kernel']": (128, HIDDEN),
             "['mlp_norm']['scale']": (HIDDEN,),
             "['up']['kernel']": (HIDDEN, 128)}
    want = {"['embed']['embedding']": (VOCAB, HIDDEN),
            "['final_norm']['scale']": (HIDDEN,),
            "['lm_head_kernel']": (HIDDEN, VOCAB)}
    for i in range(LAYERS):
        want.update({f"['layer_{i}']{k}": v for k, v in layer.items()})
    assert flat == want


def test_dense_default_lowers_to_the_same_program():
    model = dense_lm()
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])

    def loss(params, tokens):
        return next_token_loss(model.apply({"params": params}, tokens),
                               tokens)

    text = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    assert "ragged" not in text and "top_k" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_DIGEST


@pytest.mark.parametrize("shard,row_bound", list(OLMOE_DIGESTS))
def test_sparse_expert_defaults_lower_to_the_same_program(shard, row_bound):
    model = TransformerLM(
        vocab_size=VOCAB, d_model=HIDDEN, n_layers=LAYERS, n_heads=HEADS,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, use_flash=False,
        qk_norm=True, norm_eps=1e-5,
        moe=MoEConfig(EXPERTS, PER_TOKEN, WIDTH, shard, row_bound))
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])

    def loss(params, tokens):
        logits, wrote = model.apply({"params": params}, tokens,
                                    mutable=["router", "intermediates"])
        return moe_next_token_loss(logits, tokens, wrote["router"])

    text = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    assert "sigmoid" not in text and "logistic" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == OLMOE_DIGESTS[shard, row_bound]


@pytest.mark.parametrize("moe,batch_size", [
    (MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (0, 4)), 2), (FEW_EXPERTS, 16)],
    ids=["(0, 4)", "(0, 16)"])
def test_trains_through_build_train_step_and_replicas_stay_equal(moe,
                                                                 batch_size):
    """Two CPU devices, data parallel: the step of the dense LM, with the
    sparse-expert loss, the way back to the tokens a gather (a quarter of
    the experts) or a scatter-add of the buffer's rows (a sixteenth: 1,024
    tokens a device, 8 pairs a row).  The replicated weights stay equal on
    both devices and the loss of a repeated batch falls.  The flash kernel
    (interpreted here), as in the benchmark's step: blockwise_attention does
    not pass shard_map's vma check (PERF.md section 7)."""
    model = lm(moe=moe, use_flash=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))
    params, batch = seeded(model, seed=7, batch=batch_size)
    tx = optax.adamw(1e-2)

    def loss_fn(params, batch):
        return system_loss(model, params, batch)

    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd",
                            batch_spec=(P("hvd"), P("hvd")))
    state = (params, tx.init(params))
    losses = []
    for _ in range(4):
        *state, loss = step(*state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    for leaf in jax.tree.leaves(state[0]):
        first, second = (np.asarray(s.data) for s in leaf.addressable_shards)
        np.testing.assert_array_equal(first, second)


def test_lm_example_takes_an_olmoe_config(tmp_path):
    """examples/jax_transformer_lm.py trains the sparse-expert model through
    the same TransformerLM + build_train_step lines (here under sequence
    parallelism over two CPU devices): no second script."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = tmp_path / "config.json"
    config.write_text(json.dumps({
        "vocab_size": VOCAB, "hidden_size": HIDDEN,
        "num_hidden_layers": LAYERS, "num_attention_heads": HEADS,
        "rms_norm_eps": 1e-5, "num_experts": EXPERTS,
        "num_experts_per_tok": PER_TOKEN, "intermediate_size": WIDTH}))
    env = dict(os.environ, PYTHONPATH=root,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "examples",
                                      "jax_transformer_lm.py"),
         "--dp", "1", "--sp", "2", "--seq-len", "128", "--batch", "2",
         "--steps", "12", "--olmoe-config", str(config)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    losses = [float(line.split()[-1]) for line in proc.stdout.splitlines()
              if line.startswith("step")]
    assert len(losses) == 3 and losses[-1] < losses[0]
