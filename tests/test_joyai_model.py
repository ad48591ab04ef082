"""JoyAI-LLM-Flash's configuration, cell, operation count and readers: the
file holds every width of the catalog's row as published, the manifest holds
with the new entries, the count is the shapes' arithmetic, and the three
readers read their scopes and counter and give None without."""

import json
import os

import pytest

from benchmark import ops_count_joyai
from benchmark.layer_metrics import (_joyai, mla_q_latent_time_share_pct,
                                     mtp_loss_over_main, mtp_time_share_pct)
from benchmark.tests import test_manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "joyaiflash_1chip_ep16share_1x8k"
# The catalog row's `config` (the source's config.json less the keys that say
# nothing of its shape), as published.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}
SHAPE = {"hidden": 2048, "vocab": 16160, "mtp_modules": 1,
         "latent_attention_layers": 5, "mlp_layers": 1, "expert_layers": 4,
         "mlp_width": 7168,
         "latent_attention": {"kv_rank": 512, "nope_dim": 128, "rope_dim": 64,
                              "v_dim": 128, "q_rank": 1536, "heads": 32},
         "experts": {"num_experts": 256, "expert_width": 768, "shared": 768,
                     "local_experts": 16}}


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyaiflash.json")) as f:
        return json.load(f)


def test_the_file_holds_every_width_of_the_row_as_published():
    held = config()
    assert held["reduced"] == ["num_hidden_layers", "expert_shard",
                               "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in held["reduced"]:
            assert held[key] == value, key
    assert (held["num_hidden_layers"], held["kept_layers"]) == (
        5, [0, 1, 2, 3, 4])
    assert held["expert_shard"] == [0, 16]
    assert held["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert set(held["published"]) == set(held["reduced"])
    assert {"mtp_loss_weight", "mtp_input", "mtp_block", "selection_bias",
            "row_bound", "embedding_std", "logits_dtype", "optimizer",
            "recomputed"} <= set(held["assumed"])
    assert held["departures"] and held["deployment"] and held["rehearsal"]
    # The floors: a whole period and four layers behind the dense one, 8
    # routed experts or more, an eighth of the vocabulary or more.
    assert len(held["kept_layers"]) - held["first_k_dense_replace"] >= 4
    assert held["n_routed_experts"] // held["expert_shard"][1] >= 8


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
    assert len(manifest["configs"]) == 14 and len(manifest["workloads"]) == 17
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyaiflash", "1chip_1x8k_grad", 1)


def test_the_cells_metrics_are_whole():
    manifest = test_manifest.manifest()
    here = [m for m in manifest["per_layer"] if CELL in m.get("workloads", ())]
    names = {m["name"] for m in here}
    new = ["mtp_time_share_pct", "mla_q_latent_time_share_pct",
           "mtp_loss_over_main"]
    assert [m["name"] for m in here[-3:]] == new    # the last the cell lists
    for metric in here[-3:]:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tokens_per_s_chip"
    assert {"mfu_pct", "step_hbm_gb", "mla_time_share_pct",
            "mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
            "moe_time_share_pct", "moe_dispatch_time_share_pct",
            "moe_experts_roofline", "moe_load_max_over_mean",
            "moe_shared_time_share_pct", "mlp_time_share_pct"} <= names
    # The cell recomputes nothing: the reader would find nothing to read.
    assert "recompute_time_share_pct" not in names
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


def test_ops_count_is_the_shapes_arithmetic():
    ops = ops_count_joyai.joyai_lm_train_ops_per_token(
        SHAPE, 8192, 8 / 16, 6144 / 8192)
    block = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 2048
    assert ops["latent_projections"] == 6 * 6 * block
    assert ops["attention"] == 6 * 3 * 8192 * 32 * (192 + 128)
    assert ops["head"] == 2 * 6 * 2048 * 16160      # applied twice
    assert ops["mlp"] == 6 * 3 * 2048 * 7168
    assert ops["experts"] == 5 * 6 * 3 * 2048 * 768 * 0.5
    dense = 6 * (2048 * 256 + 3 * 2048 * 768)
    assert ops["total"] == ops["latent_projections"] + ops["attention"] \
        + ops["head"] + ops["mlp"] + ops["experts"] + 5 * dense \
        + 6 * 2 * 2048 * 2048
    assert ops["visible_to_compiler"] == ops["total"] - ops["attention"] \
        + ops["experts"] / 2              # 6,144 buffer rows for 4,096 routed
    assert ops["mtp"] == 6 * block + ops["attention"] / 6 + dense \
        + ops["experts"] / 5 + ops["head"] / 2 + 6 * 2 * 2048 * 2048
    assert 0.20 < ops["mtp"] / ops["total"] < 0.21       # a fifth
    assert 3.39e9 < ops["total"] < 3.41e9
    counted = ops_count_joyai.parameters(SHAPE)
    assert counted["total"] == 680_439_808               # the builder's tree
    assert counted["latent_attention_a_block"] == block + 1536 + 512 + 2048


def test_the_new_readers_read_the_scopes_and_none_without(monkeypatch):
    """`mtp_time_share_pct` from the time under `hvd_mtp`, a pathless
    grouped-matmul kernel filed behind the operation that ran before it;
    `mla_q_latent_time_share_pct` from its scope, the module's too;
    `mtp_loss_over_main` from the probe's two losses; a run without the
    scope or the counter (any other cell, the parent) reads None."""
    from benchmark import program_trace

    main = "jit(step)/jvp(hvd_loss)/TransformerLM/layer_3/mixer/"
    module = "jit(step)/jvp(hvd_loss)/TransformerLM/hvd_mtp/"
    names = {
        "fusion.0": main + "hvd_mla_q_latent/dot_general",
        "fusion.1": main + "hvd_moe_dispatch/gather",
        "ragged-dot-none.1": "ragged-dot-none",          # layer_3's: outside
        "fusion.2": module + "hvd_mtp_proj/dot_general",
        "fusion.3": module + "mtp_0_layer_0/mixer/hvd_mla_q_latent/dot_general",
        "hvd_flash_fwd.5": module + "mtp_0_layer_0/mixer/hvd_mla_attend/"
                           "hvd_flash_fwd/pallas_call",
        "fusion.4": module + "mtp_0_layer_1/mixer/hvd_moe_dispatch/gather",
        "ragged-dot-none.2": "ragged-dot-none",          # the module's
        "fusion.5": module + "hvd_lm_head/dot_general"}
    order = ["fusion.0", "fusion.1", "ragged-dot-none.1", "fusion.2",
             "fusion.3", "hvd_flash_fwd.5", "fusion.4", "ragged-dot-none.2",
             "fusion.5"]
    events = [[f"{name}|fusion||", i, 1e7] for i, name in enumerate(order)]
    program = {"devices": {"/device:TPU:0": events}, "program_spans": []}
    run = {"probes": {"optimizer_time_share_pct": {"op_names": names},
                      "mtp_loss_over_main": {"main": 10.0,
                                             "modules": [10.5]}}}
    monkeypatch.setattr(program_trace, "of_run", lambda run: program)
    assert mtp_time_share_pct.read(run) == pytest.approx(100.0 * 6 / 9)
    assert mla_q_latent_time_share_pct.read(run) == pytest.approx(
        100.0 * 2 / 9)
    assert mtp_loss_over_main.read(run) == pytest.approx(1.05)
    other = {"probes": {"optimizer_time_share_pct": {"op_names": {
        name: path for name, path in names.items() if "hvd_mtp" not in path
        and "q_latent" not in path}}}}
    for reader in (mtp_time_share_pct, mla_q_latent_time_share_pct,
                   mtp_loss_over_main):
        assert reader.read(other) is None, reader.__name__
    assert mtp_loss_over_main.read({"probes": {"mtp_loss_over_main": {
        "main": None, "modules": []}}}) is None
    monkeypatch.setattr(program_trace, "of_run", lambda run: None)
    assert mtp_time_share_pct.read(run) is None
    assert _joyai.losses_probe({"built": object()}) is None
