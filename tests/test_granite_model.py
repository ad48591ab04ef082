"""Granite-4.0-H-Micro's whole model through models.TransformerLM (Mamba-2
mixers on one group, grouped-query attention with no position embedding and a
softmax scale of its own, a dense gated MLP behind every mixer, the tied head
and the family's four multipliers) against the plain float32 reference the
benchmark keeps (benchmark/reference/granite_lm.py), at the sizes the cell's
rehearsal runs; its fields are tests/test_granite.py's.  CPU, float32, seeded
weights.

Tolerances: float32 rounding accumulated over eight pattern entries and the
chunked scan's sums in another order than the recurrence's: 2e-5 of the loss,
1e-4 of the largest value of a gradient.  A model that drops one multiplier,
unties the head or takes head_dim ** -0.5 for the softmax scale is off by
more than a hundred times that (`test_a_model_without_one_field_is_refused`).
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_count_granite
from benchmark.builders import granite_lm as builder
from benchmark.layer_metrics import (_granite, ssm_carry_live_pct,
                                     ssm_conv_time_share_pct,
                                     ssm_gate_norm_time_share_pct,
                                     ssm_scan_carry_time_share_pct,
                                     ssm_scan_decay_time_share_pct,
                                     ssm_scan_ends_time_share_pct,
                                     ssm_scan_intra_time_share_pct,
                                     ssm_scan_roofline)
from benchmark.reference import granite_lm as reference
from benchmark.tests import test_manifest
from tests.test_hybrid import (close, relative_error, seeded, system_loss,
                               trains_and_replicas_stay_equal, trees_close,
                               with_highest)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "granite4hmicro_1chip_pp4share_1x8k"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "granite4hmicro.json")) as f:
    PUBLISHED = json.load(f)
# The cell's rehearsal: the published configuration at the sizes run.py
# --rehearse walks.
CONFIG = dict(PUBLISHED, **PUBLISHED["rehearsal"])
VOCAB = CONFIG["vocab_size"]


@functools.cache
def lm(vocab=VOCAB, use_flash=False, **fields):
    """(the builder's model at the rehearsal's sizes, its kinds): on the
    blockwise attention unless told otherwise (under `build_train_step` the
    flash kernels, which the interpreter runs here)."""
    model, kinds = builder.model_of(dict(CONFIG, vocab_size=vocab))
    return model.clone(use_flash=use_flash, **fields), kinds


def reference_config(**more):
    return dict(builder.reference_config_of(CONFIG, lm()[1]), **more)


@functools.cache
def case(seed=0):
    return seeded(lm()[0], seed, vocab=VOCAB)


@functools.cache
def system_side(**fields):
    params, batch = case()
    return jax.jit(jax.value_and_grad(functools.partial(
        system_loss, lm(**fields)[0])))(params, batch)


@functools.cache
def reference_side(**more):
    params, batch = case()
    return with_highest(jax.value_and_grad(
        lambda p, b: reference.loss(p, b, **reference_config(**more))))(
            params, batch)


# --- the model is the reference ---------------------------------------------

def test_the_pattern_is_derived_from_layer_types():
    assert builder.layer_kinds(PUBLISHED) == (
        ("ssm", "gated_mlp") * 5 + ("attention", "gated_mlp")
        + ("ssm", "gated_mlp") * 4)
    model, kinds = lm()
    assert kinds == ("ssm", "gated_mlp", "attention", "gated_mlp",
                     "ssm", "gated_mlp", "ssm", "gated_mlp") == model.layers
    for wrong in (dict(num_hidden_layers=9),
                  dict(layer_types=["mamba"] * 9 + ["full_attention"])):
        with pytest.raises(ValueError, match="layer_types"):
            builder.layer_kinds(dict(PUBLISHED, **wrong))
    params, _ = case()
    assert set(params) == {"embed", "final_norm"} | {
        f"layer_{i}" for i in range(8)}          # tied: no head of its own
    for i in range(8):
        assert set(params[f"layer_{i}"]) == {"mixer", "norm"}
    assert (model.embed_scale, model.residual_scale, model.logits_divisor,
            model.attn_scale, model.tie_head, model.rope) == (
                12.0, 0.22, 8.0, 0.015625, True, False)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("position_embedding_type", "rope"),
    ("mamba_conv_bias", False), ("time_step_max", 0.2),
    ("shared_intermediate_size", 4096), ("mamba_expand", 1)])
def test_the_builder_refuses_another_model(key, value):
    with pytest.raises(ValueError, match="as published"):
        builder.model_of(dict(PUBLISHED, **{key: value}))


def test_loss_and_gradients_are_the_references():
    got, got_grads = system_side()
    want, want_grads = reference_side()
    np.testing.assert_allclose(got, want, rtol=2e-5)
    trees_close(got_grads, want_grads, 1e-4)


@pytest.mark.parametrize("field,without", [
    ("residual_scale", None), ("logits_divisor", None), ("attn_scale", None),
    ("embed_scale", None)])
def test_a_model_without_one_field_is_refused(field, without):
    """Dropping a multiplier (the softmax then scales by head_dim ** -0.5 =
    0.25, not 1 / 64) moves the loss or the gradients by far more than the
    tolerance the comparison with the reference holds."""
    want, want_grads = reference_side()
    got, got_grads = system_side(**{field: without})
    off = max(abs(float(got) - float(want)) / float(want),
              float(relative_error(got_grads, want_grads)))
    assert off > 1e-2, off


def test_an_untied_head_is_refused():
    """The same numbers through a head of its own: the loss is the tied
    model's and the table's gradient is not (the head's part goes to
    `lm_head_kernel`)."""
    params, batch = case()
    untied = lm(tie_head=False)[0]
    twin = {**params, "lm_head_kernel": params["embed"]["embedding"].T}
    got, got_grads = jax.jit(jax.value_and_grad(functools.partial(
        system_loss, untied)))(twin, batch)
    want, want_grads = reference_side()
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert float(relative_error(got_grads["embed"], want_grads["embed"])) \
        > 0.1


def test_the_builders_rows_pass_and_group_the_layer_kinds():
    params, batch = case()
    loss_s, grads_s = system_side()
    rows = builder.compare_rows(loss_s, with_highest(functools.partial(
        builder.against_reference, reference_config()))(
            params, batch, grads_s))
    assert [r["name"] for r in rows] == [
        "loss_rel_error", "grad_norm_rel_error"] + [
        f"{g}_grad_rel_l2_error" for g in builder.GROUPS]
    for row in rows:
        assert 0 <= row["value"] < 1e-4 < row["limit"], row
    kinds = lm()[1]
    assert [builder.group_of(n, kinds) for n in (
        "layer_0", "layer_1", "layer_2", "embed", "final_norm")] == [
        "ssm", "gated_mlp", "attention", "embedding", "embedding"]


@pytest.mark.parametrize("control,group", [
    (dict(operand_dtype=jnp.float8_e4m3fn), "gated_mlp"),
    (dict(decay_dtype=jnp.bfloat16), "ssm"),
    (dict(state_dtype=jnp.bfloat16, state_every=32), "ssm")])
def test_the_reference_in_a_lower_precision_reads_wrong(control, group):
    """What the cell's limits are read against: every matmul operand at
    float8, a token's decay rounded to bfloat16, and the recurrence's state
    rounded to bfloat16 between chunks (of 32 here), each move the group's gradient by
    more than float32's rounding by orders."""
    _, exact = reference_side()
    _, rounded = reference_side(**control)
    kinds = lm()[1]
    names = [n for n in exact if builder.group_of(n, kinds) == group]
    off = float(relative_error({n: rounded[n] for n in names},
                               {n: exact[n] for n in names}))
    # At 128 tokens a decay hardly compounds, and three states are rounded:
    # 8e-4 and 1.8e-4 here, where two float32 sides stand 4e-7 apart.
    assert off > (0.05 if "operand_dtype" in control else 5e-5), off


def test_vocabulary_slices_concatenate():
    """A sliced vocabulary is a smaller vocabulary, the tied table too: the
    slice's rows serve its lookups and its logits."""
    model = lm()[0]
    params, _ = case()
    rows = VOCAB // 8
    whole, sliced = jax.jit(model.apply), jax.jit(lm(vocab=rows)[0].apply)
    for i in range(8):
        ids = jax.random.randint(jax.random.PRNGKey(9), (1, 128), 0, rows)
        held = slice(i * rows, (i + 1) * rows)
        share = dict(params,
                     embed={"embedding": params["embed"]["embedding"][held]})
        close(sliced({"params": share}, ids),
              whole({"params": params}, ids + i * rows)[..., held])


def test_trains_through_build_train_step():
    params, batch = case()
    # A copy: the step donates its state, and `case()` is every test's.
    losses = trains_and_replicas_stay_equal(
        lm(use_flash=True)[0], jax.tree.map(jnp.copy, params), batch)
    np.testing.assert_allclose(losses[0], reference_side()[0], rtol=2e-5)


def test_every_entry_recomputes():
    model, kinds = lm()
    assert model.recompute is True is builder.recomputed(PUBLISHED)
    assert builder.recomputed(dict(PUBLISHED, recompute_layers=["ssm"])) \
        == ("ssm",)
    params, batch = case()
    grad = jax.value_and_grad(functools.partial(system_loss, model))
    assert str(jax.make_jaxpr(grad)(params, batch)).count("remat2[") \
        == len(kinds)
    want, want_grads = jax.jit(jax.value_and_grad(functools.partial(
        system_loss, model.clone(recompute=False))))(params, batch)
    got, got_grads = system_side()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    trees_close(got_grads, want_grads, 1e-4)    # XLA fuses the two its own way


# --- the configuration and the benchmark's entries ---------------------------

def test_the_published_configuration_counts_its_parameters():
    """No number differs from the catalog's row but the three reduced keys,
    and the parameters are what the issue's arithmetic says."""
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    for key, value in row["config"].items():
        if key not in PUBLISHED["reduced"]:
            assert PUBLISHED[key] == value, key
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "layer_types",
                                    "vocab_size"]
    assert PUBLISHED["layer_types"] == row["config"]["layer_types"][:10]
    assert PUBLISHED["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert set(PUBLISHED["published"]) == set(PUBLISHED["reduced"])
    for said in ("4 chips", "pipeline", "over 8"):
        assert said in PUBLISHED["deployment"], said
    model, _ = builder.model_of(PUBLISHED)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"])
    sizes = {name: sum(leaf.size for leaf in jax.tree.leaves(tree))
             for name, tree in shapes.items()}
    mamba = 2048 * (2 * 4096 + 2 * 128 + 64) + 5 * (4096 + 256) + 3 * 64 \
        + 4096 + 4096 * 2048 + 2048
    attention = 2048 * (2048 + 2 * 512) + 2048 * 2048 + 2048
    mlp = 3 * 2048 * 8192 + 2048
    assert [sizes[f"layer_{i}"] for i in (0, 10, 1)] == [mamba, attention,
                                                         mlp]
    assert "lm_head_kernel" not in sizes
    assert sum(sizes.values()) == 9 * mamba + attention + 10 * mlp \
        + 12544 * 2048 + 2048 == 772_160_448
    shape = {"hidden": 2048, "vocab": 12544, "intermediate": 8192,
             "ssm_layers": 9, "attention_layers": 1, "mlp_layers": 10,
             "ssm": {"heads": 64, "head_dim": 64, "groups": 1, "state": 128,
                     "conv": 4, "chunk": 256},
             "attention": {"heads": 32, "kv_heads": 8, "head_dim": 64}}
    assert ops_count_granite.parameters(shape) == 772_160_448


def test_ops_count_is_the_shapes_arithmetic():
    shape = {"hidden": 2048, "vocab": 12544, "intermediate": 8192,
             "ssm_layers": 9, "attention_layers": 1, "mlp_layers": 10,
             "ssm": {"heads": 64, "head_dim": 64, "groups": 1, "state": 128,
                     "conv": 4, "chunk": 256},
             "attention": {"heads": 32, "kv_heads": 8, "head_dim": 64}}
    ops = ops_count_granite.granite_lm_train_ops_per_token(shape, 8192)
    assert ops["mlp"] == 10 * 6 * 3 * 2048 * 8192
    assert ops["head"] == 6 * 2048 * 12544     # the tied table multiplies once
    assert ops["attention"] == 3 * 2 * 8192 * 2048
    scan = 256 * 128 + 64 * (256 * 64 + 2 * 128 * 64)
    assert ops["ssm"] == 9 * 6 * (
        2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048 + scan)
    assert ops["total"] == ops["visible_to_compiler"] + ops["attention"] \
        == ops["ssm"] + ops["mlp"] + ops["head"] + ops["attention"] \
        + 6 * 2048 * 64 * (2 * 32 + 2 * 8)
    assert 4.8e9 < ops["total"] < 4.9e9          # the issue's 4.85 GFLOP


@pytest.mark.parametrize("check", [
    test_manifest.test_keys_and_limits, test_manifest.test_configs,
    test_manifest.test_workloads,
    test_manifest.test_files_under_paths_are_named_from_a_names_characters])
def test_the_manifest_holds_with_the_new_entries(check):
    """benchmark/tests/test_manifest.py's checks with the new entries in
    (`test_metrics` there refuses every `moves: setup_s` reader since the
    set-up readers came, at the parent too: its rules for THIS cell's metrics
    are `test_the_cells_metrics_are_whole` below)."""
    check()
    manifest = test_manifest.manifest()
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 2
    assert len(manifest["configs"]) >= 13 and len(manifest["workloads"]) >= 16
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite4hmicro", "1chip_1x8k_grad", 1)


def test_the_cells_metrics_are_whole():
    manifest = test_manifest.manifest()
    here = [m for m in manifest["per_layer"] if CELL in m.get("workloads", ())]
    names = {m["name"] for m in here}
    new = {f"ssm_scan_{stage}_time_share_pct" for stage in _granite.STAGES} \
        | {"ssm_conv_time_share_pct", "ssm_gate_norm_time_share_pct",
           "ssm_carry_live_pct"}
    assert new <= names and {"mfu_pct", "ssm_scan_roofline", "step_hbm_gb",
                             "recompute_time_share_pct"} <= names
    assert [m["name"] for m in manifest["per_layer"][103 - 7:103]] == [
        f"ssm_scan_{stage}_time_share_pct" for stage in _granite.STAGES] + [
        "ssm_conv_time_share_pct", "ssm_gate_norm_time_share_pct",
        "ssm_carry_live_pct"]                   # appended, in the issue's order
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for metric in here:
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in ("tokens_per_s_chip", "setup_s")
        assert f"| {metric['layer']} |" in perf
    end_to_end = {m["name"] for m in manifest["end_to_end"]
                  if CELL in m.get("workloads", [CELL])}
    assert end_to_end == {"tokens_per_s_chip", "setup_s"}


READERS = {"decay": ssm_scan_decay_time_share_pct,
           "intra": ssm_scan_intra_time_share_pct,
           "ends": ssm_scan_ends_time_share_pct,
           "carry": ssm_scan_carry_time_share_pct}


def test_the_new_readers_read_the_scopes_and_none_without(monkeypatch):
    """Each stage's share from a trace's time under its scope; the conv's and
    the gated norm's from theirs; `ssm_scan_roofline` from the builder's
    kernel shape with no edit, under 100; `ssm_carry_live_pct` from the
    probe's counts; a run without the scope, the shape or the counters (any
    other cell, the parent) reads None."""
    from benchmark import program_trace

    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    scan = "jit(step)/jvp(hvd_loss)/layer_0/mixer/hvd_ssm_scan/hvd_ssm_scan_"
    names = {f"fusion.{i}": scan + stage + "/x"
             for i, stage in enumerate(_granite.STAGES)}
    names.update({
        "fusion.4": "jit(step)/transpose(jvp(hvd_loss))/layer_0/mixer/"
                    "hvd_ssm_conv/y",
        "fusion.5": "jit(step)/jvp(hvd_loss)/layer_0/mixer/"
                    "hvd_ssm_gate_norm/z",
        "fusion.6": "jit(step)/jvp(hvd_loss)/layer_1/mixer/hvd_mlp/w"})
    shape = {"heads": 64, "head_dim": 64, "groups": 1, "state": 128,
             "chunk": 256, "layers": 9, "itemsize": 2}
    run = {"kernels": {"ssm_scan": shape}, "peak": peak,
           "profiled_steps": 1, "samples": 8192, "steps": 1, "chips": 1,
           "probes": {"optimizer_time_share_pct": {"op_names": names},
                      "ssm_carry_live_pct": {"carried": [3, 5],
                                             "chunks": [16, 16]}}}
    events = [[f"fusion.{i}|fusion||", 0, (i + 1) * 1e7] for i in range(7)]
    program = {"devices": {"/device:TPU:0": events}, "program_spans": []}
    monkeypatch.setattr(program_trace, "of_run", lambda run: program)
    total = sum(e[2] for e in events)
    for i, stage in enumerate(_granite.STAGES):
        assert READERS[stage].read(run) == pytest.approx(
            100.0 * (i + 1) * 1e7 / total)
    assert ssm_conv_time_share_pct.read(run) == pytest.approx(
        100.0 * 5e7 / total)
    assert ssm_gate_norm_time_share_pct.read(run) == pytest.approx(
        100.0 * 6e7 / total)
    assert ssm_carry_live_pct.read(run) == 25.0
    # The four stages are all the time under hvd_ssm_scan: 0.1 s here.
    assert 0 < ssm_scan_roofline.read(run) < 100.0
    other = dict(run, kernels={}, probes={"optimizer_time_share_pct": {
        "op_names": {"fusion.6": names["fusion.6"]}}})
    for reader in list(READERS.values()) + [
            ssm_conv_time_share_pct, ssm_gate_norm_time_share_pct,
            ssm_carry_live_pct, ssm_scan_roofline]:
        assert reader.read(other) is None, reader.__name__
    monkeypatch.setattr(program_trace, "of_run", lambda run: None)
    assert ssm_scan_decay_time_share_pct.read(run) is None
    assert _granite.carry_probe({"built": object()}) is None
