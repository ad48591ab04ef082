"""The chip's compiler, without the chip — whole steps read as programs.

A few layers at each benchmark configuration's per-head widths through
`build_train_step`, compiled by libtpu for the DESCRIBED v5e 2x2 host (the
`v5e` fixture of tests/conftest.py; tests/test_chip_kernels.py has the kernels'
own compiles and says what such a compile can and cannot show): which kernels,
loops, collectives and scopes the compiled text holds, and which arrays it
writes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models import next_token_loss
from horovod_tpu.parallel import data_parallel_mesh


def _lowered_step(model, devices, rows=(1, 2048), loss_fn=None,
                  batch=("tokens", "tokens"), **init_inputs):
    """(the step, its parameters' shapes, the step lowered): ``model`` through
    `build_train_step` under AdamW on a data-parallel mesh of the described
    ``devices``.  Parameters are `jax.eval_shape`'s, replicated; the batch is
    ``rows`` of int32 tokens (a name of ``batch`` may be a dtype: an array of
    that type instead) split over the mesh's axis; the loss is
    `next_token_loss` unless ``loss_fn`` is given."""
    mesh = data_parallel_mesh(devices, axis_name="hvd")
    tx = optax.adamw(1e-4)

    def next_token(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    def init(key):
        blank = jnp.zeros((1, 128), jnp.int32)
        params = model.init(key, blank,
                            **{name: blank for name in init_inputs})["params"]
        return params, tx.init(params)

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params, opt_state = shaped(jax.eval_shape(init, jax.random.PRNGKey(0)),
                               P())
    batch = tuple(shaped(jax.ShapeDtypeStruct(
        rows, jnp.int32 if kind == "tokens" else kind), P("hvd"))
        for kind in batch)
    step = build_train_step(loss_fn or next_token, tx, mesh, axis_name="hvd")
    return step, params, step.lower(params, opt_state, batch)


_LM_STEPS = {}     # devices -> what _compile_lm_step gave for them


def _compile_lm_step(devices):
    """A two-layer dense LM at pythia-410m's widths (a smaller vocabulary,
    512 tokens a chip) through `build_train_step` on a data-parallel mesh
    of the described ``devices``: (the step, its compiled text, the weights
    whose gradient is over a megabyte in either dtype, ``{path: elements}``).
    Compiled once a process for a number of devices."""
    if len(devices) not in _LM_STEPS:
        _LM_STEPS[len(devices)] = _compiled_lm_step(devices)
    return _LM_STEPS[len(devices)]


def _compiled_lm_step(devices):
    from horovod_tpu.jax.train import _EXCHANGE_OVERLAP
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=8192, d_model=1024, n_layers=2,
                          n_heads=16, d_ff=4096, dtype=jnp.bfloat16,
                          logits_dtype=jnp.bfloat16, use_flash=True)
    step, params, lowered = _lowered_step(model, devices,
                                          rows=(len(devices), 512))
    try:
        text = lowered.compile().as_text()
    except Exception as exc:  # noqa: BLE001 - libtpu names the option
        pytest.fail("this libtpu refuses the step under the compiler options "
                    f"of jax/train.py _EXCHANGE_OVERLAP "
                    f"{sorted(_EXCHANGE_OVERLAP)}: {exc}")
    # Over 2**19 elements a gradient is over a megabyte in bf16 and in f32;
    # the model's other leaves (norm scales) are under it in both.
    sizes = {jax.tree_util.keystr(path): x.size
             for path, x in jax.tree_util.tree_leaves_with_path(params)}
    assert all(n >= 2**19 or n * 4 < 2**20 for n in sizes.values()), sizes
    return step, text, {k: n for k, n in sizes.items() if n >= 2**19}


def test_dp_step_exchanges_large_gradients_asynchronously(v5e, monkeypatch):
    """What `build_train_step` promises of the gradient exchange, asked of
    the chip's compiler.  Over four described chips every weight gradient
    over a megabyte is an `async-collective-start`/`-done` pair of its own,
    no all-reduce the core waits in has an operand that large (the small
    leaves and the loss still travel, together), and the text still holds
    an all-reduce for the benchmark's count; over one described chip the
    step takes no option and holds neither.  This is the test that fails
    when a libtpu upgrade renames, drops or re-reads one of the options."""
    import math

    from horovod_tpu.jax.train import _EXCHANGE_OVERLAP, count_all_reduces

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    options = (f"(jax/train.py _EXCHANGE_OVERLAP: {sorted(_EXCHANGE_OVERLAP)}"
               "; PERF.md section 6, PR 29)")

    step, text, large = _compile_lm_step(v5e[:4])
    assert step.exchange_overlap["compiler_options"] == "applied"
    n_async, n_sync = count_all_reduces(text)
    dones = len(re.findall(r"^\s*%async-collective-done[\w.\-]* = ", text,
                           re.M))
    assert n_async == dones == len(large), (
        f"{len(large)} gradients over a megabyte, {n_async} asynchronous "
        f"all-reduces, {dones} dones: one of the options lost its meaning "
        f"{options}")
    assert n_sync >= 1 and re.search(r"\ball-reduce\(", text)
    waiting = [line.split(" all-reduce(")[0]
               for line in _instructions_outside_fusions(text)
               if " all-reduce(" in line]
    assert len(waiting) == n_sync
    width = {"bf16": 2, "f32": 4}
    for result in waiting:
        for dtype, dims in re.findall(r"\b(bf16|f32)\[([\d,]*)\]", result):
            nbytes = width[dtype] * math.prod(
                int(d) for d in dims.split(",") if d)
            assert nbytes < 2**20, (
                f"a synchronous all-reduce carries {nbytes} bytes: {result} "
                f"{options}")

    step, text, _ = _compile_lm_step(v5e[:1])
    assert step.exchange_overlap["compiler_options"] == "not applied"
    assert count_all_reduces(text) == (0, 0)
    assert "async-collective-start" not in text
    assert "all-reduce" not in text


def test_dp_step_accounts_for_its_exchange(v5e, monkeypatch):
    """`compiled_collectives` on the same two programs.  Over four described
    chips every weight over a megabyte is one asynchronous entry with a
    start, a done, at least one carrier, that weight's bytes in the dtype it
    is summed in (bf16), role `gradient` and the done's op_name, which ends
    in the scope the weight is used under; every synchronous entry is under
    a megabyte, and one of them is the tuple of the small leaves and the
    loss (the combiner names it after one member: `gradient` here, `report`
    in the ResNet step, PERF.md section 3); the counts are
    `count_all_reduces`'s.  Over one described chip there is no table, and
    the step says so without reading a text."""
    from horovod_tpu.jax.train import compiled_collectives, count_all_reduces

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, text, large = _compile_lm_step(v5e[:4])
    table = compiled_collectives(text)
    assert all(e["op"] == "all-reduce" and e["replica_groups"]
               == "{{0,1,2,3}}" for e in table)
    pairs = [e for e in table if e["asynchronous"]]
    waiting = [e for e in table if not e["asynchronous"]]
    assert (len(pairs), len(waiting)) == count_all_reduces(text)
    assert len(pairs) == len(large)
    for e in pairs:
        assert e["start"].startswith("async-collective-start")
        assert e["done"].startswith("async-collective-done")
        assert e["instruction"] is None and len(e["carriers"]) >= 1
        assert len(e["carrier_op_names"]) == len(e["carriers"])
        assert e["role"] == "gradient" and e["dtype"] == "bf16"
        assert "/transpose(jvp(hvd_loss))/TransformerLM/" in e["op_name"]
        assert e["op_name"].endswith("/psum_invariant")
    # The weights by the scope they are used under, two bytes an element.
    d, ff, vocab = 1024, 4096, 8192
    want = {"hvd_embed/embed/jit(_take)": vocab * d,
            "hvd_lm_head/bsd,dv->bsv": d * vocab}
    for layer in ("layer_0", "layer_1"):
        want.update({f"{layer}/attn/hvd_attn_qkv": d * 3 * d,
                     f"{layer}/attn/hvd_attn_out/bhse,hed->bsd": d * d,
                     f"{layer}/hvd_mlp/up": d * ff,
                     f"{layer}/hvd_mlp/down": ff * d})
    got = {e["op_name"].split("/TransformerLM/")[1].rsplit("/", 1)[0]:
           e["bytes"] for e in pairs}
    assert got == {scope: 2 * n for scope, n in want.items()}
    assert sorted(want.values()) == sorted(large.values())
    # Every carrier is named: by the weight-gradient product it is, or (a
    # fusion that carries a collective's state loses its own op_name) by
    # what its computation computes — a backward pass, an optimizer update.
    named = [path for e in pairs for path in e["carrier_op_names"]]
    assert all("transpose(jvp(hvd_loss))" in path or "hvd_optimizer" in path
               for path in named), named
    assert any("hvd_optimizer" in path for path in named)

    assert waiting and all(e["bytes"] < 2**20 and e["instruction"]
                           and not e["carriers"] for e in waiting)
    leaves = [e for e in waiting if e["dtype"] == "f32"]
    assert len(leaves) == 1 and leaves[0]["role"] in ("gradient", "report")
    # 2 scales a layer and the final norm's, 1,024 wide, and the loss.
    assert leaves[0]["bytes"] == 4 * (5 * d + 1)

    step, text, _ = _compile_lm_step(v5e[:1])
    assert compiled_collectives(text) == []
    monkeypatch.setattr(step, "lower", None)     # nothing is lowered for it
    assert step.collectives() == []


def _assert_scopes_forward_and_backward(text, scopes):
    """Every scope of ``scopes`` is in the compiled text's op_names under
    `jvp(hvd_loss)` and under `transpose(jvp(hvd_loss))`."""
    for scope in scopes:
        assert re.search(rf'op_name="jit\([^"]*/jvp\(hvd_loss\)/[^"]*{scope}/',
                         text), f"{scope} is not in the forward pass"
        assert re.search(
            rf'op_name="jit\([^"]*transpose\(jvp\(hvd_loss\)\)/[^"]*{scope}/',
            text), f"{scope} is not in the backward pass"


def test_dense_step_names_its_layers(v5e, monkeypatch):
    """The dense LM's step compiled for one described chip: the embedding,
    the three parts of attention, the MLP, the head and the loss's own pass
    each keep a scope of their own in the compiled text's op_names, forward
    and backward (benchmark/layer_metrics/_layers.py sorts a device trace by
    them), and the flash kernels lie beneath `hvd_attn_attend`."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, text, _ = _compile_lm_step(v5e[:1])
    _assert_scopes_forward_and_backward(
        text, ("hvd_embed", "hvd_attn_qkv", "hvd_attn_attend", "hvd_attn_out",
               "hvd_mlp", "hvd_lm_head", "hvd_token_xent"))
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd"):
        assert re.search(rf'%{kernel}[.\d]* = .*op_name="[^"]*/hvd_attn_attend/'
                         rf'{kernel}/pallas_call"', text), kernel


def _instructions_outside_fusions(text):
    """The instruction lines of a compiled program's text that are not in a
    fused computation: what the core runs one after another."""
    fused = set(re.findall(r"\bfusion\(.*calls=%([\w.\-]+)", text))
    skipping = False
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if opened:
            skipping = opened.group(1) in fused
        elif not skipping and " = " in line:
            yield line


def _written_float32_elements(text):
    """Element counts of the float32 arrays that instructions OUTSIDE fused
    computations yield in a compiled program's text: what is written to
    memory, where a fusion's body holds values that never leave the core."""
    import math

    counts = []
    for line in _instructions_outside_fusions(text):
        yielded = line.split(" = ", 1)[1].split(", metadata=")[0]
        counts += [math.prod(map(int, dims.split(",")))
                   for dims in re.findall(r"\bf32\[([\d,]+)\]", yielded)]
    return counts


def test_lm_loss_keeps_no_float32_logits(v5e):
    """The gradient of a small TransformerLM under next_token_loss, bf16
    logits of 2,048 tokens x 8,192 classes, compiled for the described
    chip, writes no float32 array of the logits' size: the softmax is
    float32 inside fusions only (plain autodiff of a cross-entropy on
    ``logits.astype(float32)`` wrote that copy out for its backward)."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import TransformerLM

    tokens, vocab = 2048, 8192
    model = TransformerLM(vocab_size=vocab, d_model=128, n_layers=1,
                          n_heads=2, d_ff=256, dtype=jnp.bfloat16,
                          logits_dtype=jnp.bfloat16, use_flash=False)
    shape = jax.ShapeDtypeStruct((1, tokens), jnp.int32)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], shape)
    on_chip = SingleDeviceSharding(v5e[0])
    params, inputs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_chip),
        (params, shape))

    def loss(params, inputs, targets):
        return next_token_loss(model.apply({"params": params}, inputs),
                               targets)

    text = jax.jit(jax.grad(loss)).lower(params, inputs,
                                         inputs).compile().as_text()
    written = _written_float32_elements(text)
    assert written, "the text's float32 arrays were not found"
    assert tokens * vocab not in written


def test_hybrid_step_is_products_and_kernels_with_no_loop(v5e, monkeypatch):
    """A Mamba-2, an attention (4 query heads on 1 key/value head of 128, no
    rotary) and a latent sparse-expert layer at Nemotron-3's per-head widths
    through `build_train_step`, compiled for the described chip: the chunked
    scan is products over chunks (no `while` anywhere in the step), attention
    is the two flash kernels, the experts are libtpu's grouped-matmul kernels
    (six and two tile schedules), and every scope of the layers is in the
    text forward and backward."""
    from horovod_tpu.models import Mamba2Config, MoEConfig, TransformerLM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=4, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=True, norm_eps=1e-5,
        layers=("ssm", "experts", "attention"),
        ssm=Mamba2Config(16, 64, 1, 128, 4, 128), n_kv_heads=1, rope=False,
        moe=MoEConfig(64, 8, 512, (0, 8), 1.5, "sigmoid", True, 5.0, "relu2",
                      256, 1024))
    text = _lowered_step(model, v5e[:1])[2].compile().as_text()
    assert not re.search(r"\bwhile\(", text)
    assert len(re.findall(r"%hvd_flash_fwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%hvd_flash_bwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 6
    # 16,384 pairs for a buffer of 3,072 rows: under the row walk's 8 pairs a
    # row (the cell's own 35 are over it) and past `HELD_PAIRS_PER_ROW`, so
    # the way back is the kernel's two calls.
    assert len(re.findall(r"%hvd_moe_pair_rows[.\d]* = ", text)) == 2
    assert text.count('"tpu_custom_call"') == 2 + 6 + 2 + 2
    _assert_scopes_forward_and_backward(
        text, ("hvd_ssm_in_proj", "hvd_ssm_conv", "hvd_ssm_scan",
               "hvd_ssm_gate_norm", "hvd_ssm_out_proj", "hvd_moe_latent",
               "hvd_moe_shared", "hvd_moe_router", "hvd_moe_dispatch",
               "hvd_moe_combine", "hvd_embed", "hvd_attn_qkv",
               "hvd_attn_attend", "hvd_attn_out", "hvd_lm_head"))


def test_granite_step_is_one_groups_scan_by_stage_under_a_tied_head(
        v5e, monkeypatch):
    """A Mamba-2 layer of 64 heads of 64 on ONE group at a chunk of 256, an
    attention layer of 32 query heads on 8 key/value heads of 64 at a softmax
    scale of its own, and the 8,192-wide gated MLP behind each, at
    Granite-4.0-H-Micro's widths with its four multipliers, every entry
    recomputing, through `build_train_step`, compiled for the described chip:
    no `while` anywhere in the step, attention is the two flash kernels, the
    scan is its pair of kernels — the forward's in the forward pass and
    again where the entry recomputes, the backward's once —
    each body traced ONCE for the step (`ops.ssm._scan_call` is jitted: the
    cell's nine mixers share a call a direction), the tree has no head of its
    own, and the scan's scopes are in the compiled text's op_names forward
    and backward (`decay` for the cumulative sums, `intra` for the
    kernels)."""
    from horovod_tpu.common.metrics import setup_table
    from horovod_tpu.models import Mamba2Config, TransformerLM
    from horovod_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ssm._scan_call.clear_cache()    # what another test traced is not again

    def entries():
        table = setup_table.process()["kernels"]
        return {name: table.get(name, {"calls": 0})["calls"] for name in (
            "hvd_ssm_scan_intra_fwd", "hvd_ssm_scan_intra_bwd")}

    before = entries()
    model = TransformerLM(
        vocab_size=2048, d_model=2048, n_heads=32, d_ff=8192,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, use_flash=True,
        norm_eps=1e-5, layers=("ssm", "gated_mlp", "attention", "gated_mlp"),
        ssm=Mamba2Config(64, 64, 1, 128, 4, 256), n_kv_heads=8, head_dim=64,
        rope=False, recompute=True, embed_scale=12.0, tie_head=True,
        residual_scale=0.22, logits_divisor=8.0, attn_scale=1.0 / 64)
    model = model.clone(layers=("ssm", "gated_mlp") + model.layers)
    _, params, lowered = _lowered_step(model, v5e[:1])
    assert set(params) == {"embed", "final_norm"} | {
        f"layer_{i}" for i in range(6)}
    # (the model's `init` runs 128 tokens: one chunk of 128, on the products)
    assert {name: n - before[name] for name, n in entries().items()} == {
        "hvd_ssm_scan_intra_fwd": 1, "hvd_ssm_scan_intra_bwd": 1}
    text = lowered.compile().as_text()
    assert not re.search(r"\bwhile\(", text)
    assert len(re.findall(r"%hvd_flash_fwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%hvd_flash_bwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%hvd_ssm_scan_intra_fwd[.\d]* = ", text)) == 4
    assert len(re.findall(r"%hvd_ssm_scan_intra_bwd[.\d]* = ", text)) == 2
    assert text.count('"tpu_custom_call"') == 2 + 3 * 2
    # The states between chunks stay on the chip: no operation of XLA's is
    # an end state's or the carry's.
    assert "hvd_ssm_scan_ends" not in text
    assert "hvd_ssm_scan_carry" not in text
    _assert_scopes_forward_and_backward(
        text, ("hvd_ssm_in_proj", "hvd_ssm_conv", "hvd_ssm_scan",
               "hvd_ssm_scan_decay", "hvd_ssm_scan_intra",
               "hvd_ssm_gate_norm", "hvd_ssm_out_proj", "hvd_mlp",
               "hvd_embed", "hvd_attn_qkv", "hvd_attn_attend",
               "hvd_attn_out", "hvd_lm_head"))


def test_ling_step_is_products_kernels_and_one_loop_a_pass(v5e, monkeypatch):
    """A Kimi-delta layer, a dense gated MLP, a latent-attention layer (4
    heads, 192 and 128 wide) and group-limited gated experts with a shared one
    at Ling-3.0-flash's per-head widths through `build_train_step`, compiled
    for the described chip: the delta rule's recurrence between chunks is the
    step's only loops (one `while` forward, one backward, each carrying the
    state alone through a handful of fusions), latent attention is
    the flash forward and the split backward pair at two widths, the experts
    are libtpu's grouped-matmul kernels (nine and two tile schedules), and
    every scope of the layers and every stage of the delta rule is in the text
    forward and backward."""
    from horovod_tpu.models import (DeltaConfig, LatentConfig, MoEConfig,
                                    TransformerLM)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=4, d_ff=1024,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, use_flash=True,
        layers=("delta", "gated_mlp", "latent_attention", "experts"),
        delta=DeltaConfig(4, 128), latent=LatentConfig(512, 128, 64, 128, 6e6),
        moe=MoEConfig(64, 8, 256, (0, 8), 1.5, "sigmoid", True, 2.5,
                      shared_width=256, n_group=8, topk_group=4))
    text = _lowered_step(model, v5e[:1])[2].compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 2
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 9
    # The experts' rows come back by the kernel, as in the hybrid step above.
    assert len(re.findall(r"%hvd_moe_pair_rows[.\d]* = ", text)) == 2
    assert text.count('"tpu_custom_call"') == 3 + 9 + 2 + 2
    _assert_scopes_forward_and_backward(
        text, ("hvd_kda_in_proj", "hvd_kda_conv", "hvd_kda_gate",
               "hvd_kda_scan", "hvd_kda_gate_norm", "hvd_kda_out_proj",
               "hvd_mla_q_proj", "hvd_mla_kv_latent", "hvd_mla_attend",
               "hvd_mla_out_proj", "hvd_moe_router", "hvd_moe_shared",
               "hvd_embed", "hvd_mlp", "hvd_lm_head",
               "hvd_kda_scan/hvd_kda_scan_decays",
               "hvd_kda_scan/hvd_kda_scan_chunk",
               "hvd_kda_scan/hvd_kda_scan_solve",
               "hvd_kda_scan/hvd_kda_scan_carry"))
    # The stages partition the scope: no operation, a cast either, lies under
    # `hvd_kda_scan` and under no stage (their shares must sum to its own).
    under = re.findall(r'op_name="([^"]*/hvd_kda_scan/[^"]*)"', text)
    assert len(under) > 100 and all(re.search(
        r"/hvd_kda_scan/hvd_kda_scan_(decays|chunk|solve|carry)/", path)
        for path in under), [p for p in under if "scan_" not in p][:3]
    # The two loops are the carry stage's, one a pass.
    loops = re.findall(r'^\s*%[\w.\-]+ = .* while\(.*op_name="([^"]*)"', text,
                       re.M)
    assert len(loops) == 2 and all(
        "/hvd_kda_scan/hvd_kda_scan_carry/" in path for path in loops), loops
    assert sum("transpose(jvp(hvd_loss))" in path for path in loops) == 1
    # Each loop carries the state alone: its body is the state's two products
    # and what stores them; an iteration costs a microsecond a fusion whatever
    # it computes, so nothing else belongs in it.
    bodies = re.findall(r"\bwhile\(.*body=%([\w.\-]+)", text)
    for body in bodies:
        start = text.index(f"\n%{body} ")
        fusions = text[start:text.index("\n}", start)].count(" fusion(")
        assert 2 <= fusions <= 5, (body, fusions)


def test_joyai_step_is_three_kernels_a_block_and_a_module_under_one_scope(
        v5e, monkeypatch):
    """Latent attention WITH a query latent and no gate (4 heads, 192 and 128
    wide), sigmoid-routed gated experts with a shared one and a
    multi-token-prediction module of one more such layer, at
    JoyAI-LLM-Flash's per-head widths, every entry recomputing, through
    `build_train_step` and `mtp_next_token_loss`, compiled for the described
    chip: two blocks (the module's the second) are the flash forward and the
    split backward pair at two widths, by their names, once each (a
    recomputing entry keeps the forward kernel's outputs); the tree holds ONE
    table and ONE head; the query latent has its scope; and everything the
    module adds — its lookup, its projection, its block's scopes, its head —
    nests under `hvd_mtp`, forward and backward."""
    from horovod_tpu.models import (LatentConfig, MoEConfig, TransformerLM,
                                    mtp_next_token_loss)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=4, d_ff=1024,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, use_flash=True,
        layers=("latent_attention", "experts"),
        latent=LatentConfig(512, 128, 64, 128, 3.2e7, q_rank=384, gate=False),
        moe=MoEConfig(64, 8, 256, (0, 8), 1.5, "sigmoid", True, 2.5,
                      shared_width=256), recompute=True,
        mtp=(1, ("latent_attention", "experts")))

    def loss(params, batch):
        return mtp_next_token_loss(
            model.apply({"params": params}, batch[0]), batch[0])

    _, params, lowered = _lowered_step(model, v5e[:1], rows=(1, 1024),
                                       loss_fn=loss)
    names = [name for name in params if not name.startswith(("layer_",
                                                             "mtp_0_"))]
    assert sorted(names) == ["embed", "final_norm", "lm_head_kernel"]
    assert {"mtp_0_embed_norm", "mtp_0_state_norm", "mtp_0_proj",
            "mtp_0_layer_0", "mtp_0_layer_1", "mtp_0_final_norm"} <= set(
                params)
    assert "gate_kernel" not in params["layer_0"]["mixer"]
    text = lowered.compile().as_text()
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 2
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 2 * 9
    _assert_scopes_forward_and_backward(
        text, ("hvd_mla_q_latent", "hvd_mla_q_proj", "hvd_mla_kv_latent",
               "hvd_mla_attend", "hvd_mla_out_proj", "hvd_moe_router",
               "hvd_moe_shared", "hvd_embed", "hvd_lm_head",
               "hvd_mtp", "hvd_mtp/hvd_mtp_proj", "hvd_mtp/hvd_embed",
               "hvd_mtp/hvd_lm_head",
               r"hvd_mtp/[^\"]*hvd_mla_q_latent",
               r"hvd_mtp/[^\"]*hvd_mla_attend",
               r"hvd_mtp/[^\"]*hvd_moe_router",
               r"hvd_mtp/[^\"]*hvd_moe_shared"))
    # The module's flash kernels keep its scope in their own op_names: a
    # reader files them by path (benchmark/layer_metrics/_joyai.py).
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"):
        assert len(re.findall(
            rf'%{kernel}[.\d]* = [^\n]*op_name="[^"]*/hvd_mtp/', text)) == 1


@pytest.mark.parametrize("width", [1024, 1280, 2560])
def test_embedding_gradient_is_slabs_under_its_scope(v5e, width):
    """The gradient of a one-layer dense LM compiled for the described chip,
    2,048 tokens into a table of 1,536 rows.  Past `ops.moe.WHOLE_ROW_WIDTH`
    the table's cotangent is one scatter-add a slab of `ROW_SLAB_WIDTH`
    columns (the last narrower at 1,280) and none of the whole width; at
    pythia-410m's 1,024 it is one scatter-add of whole rows.  Every
    instruction that yields the table in the compute dtype, its cotangent or
    a slab of it carries an op_name under `hvd_embed`, forward and backward:
    `embed_time_share_pct` reads the slabs and their join, and
    `model_unscoped_pct` does not take them."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.ops.moe import ROW_SLAB_WIDTH, WHOLE_ROW_WIDTH

    vocab, tokens = 1536, 2048
    model = TransformerLM(vocab_size=vocab, d_model=width, n_layers=1,
                          n_heads=width // 128, d_ff=256, dtype=jnp.bfloat16,
                          logits_dtype=jnp.bfloat16, use_flash=False)
    shape = jax.ShapeDtypeStruct((1, tokens), jnp.int32)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], shape)
    on_chip = SingleDeviceSharding(v5e[0])
    params, inputs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_chip),
        (params, shape))

    def loss(params, inputs, targets):
        with jax.named_scope("hvd_loss"):
            return next_token_loss(model.apply({"params": params}, inputs),
                                   targets)

    text = jax.jit(jax.grad(loss)).lower(params, inputs,
                                         inputs).compile().as_text()
    slabs = [width] if width <= WHOLE_ROW_WIDTH else \
        [min(ROW_SLAB_WIDTH, width - at)
         for at in range(0, width, ROW_SLAB_WIDTH)]
    scattered = re.findall(rf"= bf16\[{vocab},(\d+)\]\S* scatter\(", text)
    assert sorted(map(int, scattered)) == sorted(slabs)
    _assert_scopes_forward_and_backward(text, ("hvd_embed",))
    table_shaped = re.compile(
        rf"= \(?bf16\[{vocab},(?:{'|'.join(map(str, {width, *slabs}))})\]")
    found = 0
    for line in text.splitlines():
        if table_shaped.search(line) and "op_name=" in line \
                and " parameter(" not in line:
            found += 1
            assert re.search(r'op_name="[^"]*/hvd_embed/', line), line
    assert found >= 2 * len(slabs)



def test_trinity_step_is_banded_and_causal_kernels_and_a_named_gate(
        v5e, monkeypatch):
    """A windowed layer, a dense gated MLP, a full layer and sigmoid-routed
    experts with a shared one at Trinity-Mini's per-head widths (heads of 128
    on 2 key/value heads, the gate, the per-head norms, the post-norms, the
    embedding multiplier) through `build_train_step`, compiled for the
    described chip: the windowed layer's kernels are the banded ones, the
    full layer's the causal ones, no loop, and the gate's scope is in the
    text forward and backward beside the other scopes of `Attention`."""
    from horovod_tpu.models import MoEConfig, TransformerLM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=8, d_ff=1024,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, use_flash=True,
        norm_eps=1e-5,
        layers=("window_attention", "gated_mlp", "attention", "experts"),
        n_kv_heads=2, rope=False, head_dim=128, window=512, head_norm=True,
        attn_gate=True, post_norm=True, embed_scale=512 ** 0.5,
        moe=MoEConfig(64, 8, 256, (0, 8), 1.5, "sigmoid", True, 2.826,
                      shared_width=256))
    text = _lowered_step(model, v5e[:1])[2].compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 0
    for kernel in ("hvd_flash_fwd_window", "hvd_flash_bwd_window",
                   "hvd_flash_fwd", "hvd_flash_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 9
    _assert_scopes_forward_and_backward(
        text, ("hvd_attn_qkv", "hvd_attn_attend", "hvd_attn_gate",
               "hvd_attn_out", "hvd_mlp", "hvd_moe_router", "hvd_moe_shared",
               "hvd_embed", "hvd_lm_head"))
    # q of the windowed layer, once a direction; the full layer does not turn
    # and keeps the norm alone.
    assert _prep_kernels(text)[0] == (1, 1)



@pytest.mark.parametrize("d_model,experts,grouped", [
    (512, 64, {"ragged-dot-none": 18}),
    (384, 32, {"hvd_grouped_fwd": 6, "hvd_grouped_drows": 6,
               "hvd_grouped_dweights": 6})], ids=["ragged_dot", "tiled"])
def test_mellum_step_recomputes_its_layers_under_jaxs_marker(
        v5e, monkeypatch, d_model, experts, grouped):
    """A windowed layer at the plain rotary frequencies, a full one at
    YaRN's, each followed by softmax-routed experts, every pattern entry
    recomputed (`TransformerLM(recompute=True)`), through `build_train_step`
    for the described chip: every flash kernel is in the step once a layer,
    as without recomputation, and so is every grouped matmul, nine a layer
    (a recomputing layer keeps the forward kernel's outputs, the grouped
    products' and its router's decision) — libtpu's `ragged_dot` kernels at
    512 wide on 384 rows a group, the tiled kernels of `ops/moe.py` at 384
    wide on 768, three of each form a layer — no loop; what is computed
    again — the projections, the rotation of either kind, the rows'
    movement — carries `rematted_computation` inside the backward phase and
    keeps its layer's scope, and nothing of the first forward pass does."""
    from horovod_tpu.models import MoEConfig, RopeScaling, TransformerLM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=d_model, n_heads=8, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=True, norm_eps=1e-6,
        layers=("window_attention", "experts", "attention", "experts"),
        n_kv_heads=2, head_dim=128, window=512, head_norm=True,
        rope_theta=500000.0, rope_scaling=RopeScaling(16, 8192),
        window_rope=(500000.0, None), recompute=True,
        moe=MoEConfig(experts, 8, 256, (0, 4), 1.5, renormalize=True))
    text = _lowered_step(model, v5e[:1])[2].compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 0
    for kernel in ("hvd_flash_fwd_window", "hvd_flash_fwd",
                   "hvd_flash_bwd_window", "hvd_flash_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    found = {name: len(re.findall(rf"%\w*{name}[.\d]* = ", text))
             for name in ("ragged-dot-none", "hvd_grouped_fwd",
                          "hvd_grouped_drows", "hvd_grouped_dweights")}
    assert {name: n for name, n in found.items() if n} == grouped
    paths = re.findall(r'op_name="([^"]*)"', text)
    again = [path for path in paths if "rematted_computation" in path]
    assert again and all("transpose(jvp(hvd_loss))" in path
                         for path in again)
    for layer in (0, 2):
        assert any(f"layer_{layer}" in path and "hvd_attn_rotate" in path
                   for path in again)
        assert any(f"layer_{layer}" in path and "hvd_attn_qkv" in path
                   for path in again)
    assert not any("hvd_flash" in path or "hvd_grouped" in path
                   for path in again)
    assert any("hvd_moe_dispatch" in path for path in again)
    assert not any("hvd_lm_head" in path or "hvd_embed" in path
                   for path in again)
    _assert_scopes_forward_and_backward(
        text, ("hvd_attn_qkv", "hvd_attn_rotate", "hvd_attn_attend",
               "hvd_attn_out", "hvd_moe_router", "hvd_embed", "hvd_lm_head"))
    # q of each of the two layers: forward twice (the recomputation),
    # backward once; the key heads keep `rope`'s products.
    calls, swaps = _prep_kernels(text)
    assert calls == (4, 2) and swaps


@pytest.mark.parametrize("heads,kv_heads,head_dim",
                         [(32, 4, 128), (16, 2, 256)],
                         ids=["trinity", "qwen3next"])
def test_gated_attention_writes_no_float32_array_of_the_kernels_output(
        v5e, monkeypatch, heads, kv_heads, head_dim):
    """`Attention(gate=True)` at the two cells' heads over 2,048 rows, forward
    and backward, compiled for the described chip: no instruction under
    `hvd_attn_gate` writes a float32 array of the kernels' output's size (the
    float32 gate plain autodiff kept, 134 MB a layer in the Trinity cell);
    the gate's product writes the gated output and the rounded gate from one
    fusion, and the backward's two products are there under the scope."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models.transformer import Attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq = 2048
    layer = Attention(heads, jnp.bfloat16, use_flash=True, n_kv_heads=kv_heads,
                      head_dim=head_dim, head_norm=True, gate=True)
    on_chip = SingleDeviceSharding(v5e[0])

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=on_chip), tree)

    # A hidden width that is not the rows: the weight's float32 gradient
    # then has a size of its own.
    x = jax.ShapeDtypeStruct((1, seq, 1024), jnp.bfloat16, sharding=on_chip)
    params = shaped(jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros(x.shape, x.dtype))["params"]))

    def loss(params, x):
        with jax.named_scope("hvd_loss"):
            return layer.apply({"params": params}, x).astype(
                jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    _assert_scopes_forward_and_backward(text, ("hvd_attn_gate",))
    entry = text[text.index("ENTRY "):]
    under = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\(.*"
                       r"op_name=\"([^\"]*/hvd_attn_gate/[^\"]*)\"", entry,
                       re.M)
    for result, op_name in under:
        for dims in re.findall(r"f32\[([\d,]+)\]", result):
            assert np.prod([int(n) for n in dims.split(",")]) \
                != heads * seq * head_dim, (result, op_name)
    kernels_output = (rf"bf16\[(?:1,)?{heads},"
                      rf"(?:{seq},{head_dim}|{head_dim},{seq})\]")
    assert [result for result, op_name in under
            if "/jvp(hvd_loss)/" in op_name
            and len(re.findall(kernels_output, result)) == 2], under
    for product in ("bhse,dhe->bsd", "bsd,bhse->dhe"):
        assert any(f"hvd_attn_gate/{product}/dot_general" in op_name
                   and "transpose(" in op_name
                   for _, op_name in under), product



def test_sdar_step_is_blockdiff_kernels_and_a_named_loss(v5e, monkeypatch):
    """Two published layers of SDAR's pattern (block-diffusion attention at
    heads of 128 on 2 key/value heads with the per-head norms, softmax-routed
    experts with renormalised weights) through `build_train_step` with
    `masked_diffusion_loss`, compiled for the described chip: every attention
    kernel is a block-diffusion one, no causal kernel and no loop is in the
    text, the head's product has the noised half's rows alone, and the loss's
    scope is in the text forward and backward beside the attention's."""
    from horovod_tpu.models import (MoEConfig, TransformerLM,
                                    masked_diffusion_loss)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=8, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=True,
        layers=("blockdiff_attention", "experts") * 2, n_kv_heads=2,
        head_dim=128, head_norm=True, block_diffusion=4, rope_theta=1e6,
        moe=MoEConfig(64, 8, 256, (0, 8), 1.5, renormalize=True))
    def loss_fn(params, batch):
        tokens, noised, masked, level = batch
        return masked_diffusion_loss(
            model.apply({"params": params}, tokens, noised=noised), tokens,
            masked, level)

    text = _lowered_step(
        model, v5e[:1], (1, 1024), loss_fn,
        ("tokens", "tokens", jnp.bool_, jnp.float32),
        noised=True)[2].compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 0
    for kernel in ("hvd_flash_fwd_blockdiff", "hvd_flash_bwd_blockdiff"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 2, kernel
    assert not re.search(r"%hvd_flash_(fwd|bwd)[.\d]* = ", text)
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 18
    # The head over the noised half: logits of 1,024 rows, not 2,048.
    assert re.search(r"bf16\[(1,)?1024,2048\]", text)
    assert not re.search(r"bf16\[(1,)?2048,2048\]", text)
    _assert_scopes_forward_and_backward(
        text, ("hvd_attn_qkv", "hvd_attn_attend", "hvd_attn_out",
               "hvd_moe_router", "hvd_embed", "hvd_lm_head",
               "hvd_diffusion_loss"))
    assert _prep_kernels(text)[0] == (2, 2)


def _prep_kernels(text):
    """(forward, backward) calls of `ops.attn_prep`'s kernels in a compiled
    text, and the `rope` products (the signed pair permutation's) under the
    attention's scopes."""
    calls = tuple(len(re.findall(rf"%hvd_attn_prep_{way}[.\d]* = ", text))
                  for way in ("fwd", "bwd"))
    swaps = [path for path in re.findall(r'op_name="([^"]*)"', text)
             if "hvd_attn_" in path and "...d,de->...e" in path]
    return calls, swaps


def _pair_swaps(text, heads):
    """The products with the (128, 128) signed pair permutation of a tensor
    ``heads`` heads of 128 wide in a lowered text."""
    return len(re.findall(
        rf"dot_general .*tensor<1x{heads}x\d+x128xbf16>, "
        r"tensor<128x128xbf16>", text))


def _small_prepared_models():
    """name -> (`TransformerLM`'s keywords, `_lowered_step`'s further
    arguments): 8 heads of 128 on 2 key/value heads with the per-head norms,
    the layer kinds and the rotations of the four cells whose q takes
    `ops.attn_prep`'s pass."""
    from horovod_tpu.models import (MoEConfig, RopeScaling,
                                    masked_diffusion_loss)
    from horovod_tpu.models.transformer import IndexerConfig

    sizes = dict(vocab_size=2048, d_model=512, n_heads=8, n_kv_heads=2,
                 head_dim=128, head_norm=True, dtype=jnp.bfloat16,
                 logits_dtype=jnp.bfloat16, use_flash=True)
    experts = MoEConfig(64, 8, 256, (0, 8), 1.5, renormalize=True)

    def diffusion(model):
        def loss_fn(params, batch):
            tokens, noised, masked, level = batch
            return masked_diffusion_loss(
                model.apply({"params": params}, tokens, noised=noised),
                tokens, masked, level)
        return dict(loss_fn=loss_fn, noised=True,
                    batch=("tokens", "tokens", jnp.bool_, jnp.float32))

    return {
        # Both kinds, each at its own table, every entry recomputed: three
        # prepared layers, five forward calls in the program.
        "mellum": (dict(
            sizes, layers=("window_attention", "experts", "attention",
                           "experts", "window_attention"),
            window=512, rope_theta=5e5, rope_scaling=RopeScaling(16, 8192),
            window_rope=(5e5, None), recompute=True, moe=experts), None),
        # Two banded layers that turn and a full one that does not.
        "trinity": (dict(
            sizes, layers=("window_attention", "gated_mlp", "attention",
                           "window_attention", "experts"),
            d_ff=1024, norm_eps=1e-5, rope=False, window=512, attn_gate=True,
            post_norm=True, embed_scale=512 ** 0.5,
            moe=experts._replace(scoring="sigmoid")), None),
        "sdar": (dict(sizes, layers=("blockdiff_attention", "experts") * 2,
                      block_diffusion=4, rope_theta=1e6, moe=experts),
                 diffusion),
        "keye": (dict(sizes, layers=("selected_attention", "experts"),
                      rope_theta=1e7, indexer=IndexerConfig(4, 64, 512),
                      moe=experts), None),
    }


@pytest.mark.parametrize("cell", ["mellum", "trinity", "sdar", "keye"])
def test_qs_norm_and_turn_is_one_traced_body_each_way(v5e, monkeypatch, cell):
    """The tracing bill (ROADMAP S6): a small model of each of the four cells
    whose q takes `ops.attn_prep`'s pass, lowered once through
    `build_train_step` for the described chip, adds to the process's kernels
    table ONE `hvd_attn_prep_fwd` entry for the step and one for the model's
    `init` at 128 rows, and ONE `hvd_attn_prep_bwd` entry — whatever the
    number of layers and of kinds, recomputed or not; the lowered text holds
    both kernels, once a function the layers call, no product of q
    with the signed pair permutation, and the key heads' `rope` products,
    forward and backward, as before."""
    from horovod_tpu.common.metrics import setup_table
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.ops import attn_prep

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    keywords, further = _small_prepared_models()[cell]
    model = TransformerLM(**keywords)
    rows = 2048
    # What another test of this process traced is not traced again.
    attn_prep._fwd_call.clear_cache()
    attn_prep._bwd_call.clear_cache()

    def entries():
        table = setup_table.process()["kernels"]
        return {name: table.get(name, {"calls": 0})["calls"]
                for name in ("hvd_attn_prep_fwd", "hvd_attn_prep_bwd")}

    before = entries()
    lowered = _lowered_step(model, v5e[:1], (1, rows),
                            **(further(model) if further else {}))[2]
    added = {name: n - before[name] for name, n in entries().items()}
    assert added == {"hvd_attn_prep_fwd": 2, "hvd_attn_prep_bwd": 1}
    text = lowered.as_text(debug_info=True)
    # One function a direction, which the layers call (the recomputed
    # forward is lowered as a function of its own).
    for name, most in (("hvd_attn_prep_fwd", 1 + bool(model.recompute)),
                       ("hvd_attn_prep_bwd", 1)):
        held = [line for line in text.splitlines()
                if "custom_call @tpu_custom_call" in line and name in line]
        assert 1 <= len(held) <= most, (name, len(held))
    assert _pair_swaps(text, model.n_heads) == 0
    turned = sum(kind != "attention" or model.rope for kind in model.layers
                 if kind.endswith("attention"))
    assert _pair_swaps(text, model.n_kv_heads) >= 2 * turned
    assert re.search(r'hvd_attn_qkv/[^"]*hvd_attn_rotate', text)
    assert "hvd_attn_attend/hvd_attn_rotate" not in text


def _composed_layers():
    return {
        "qwen3next": dict(n_heads=16, n_kv_heads=2, head_dim=256, gate=True,
                          rope_theta=1e7, rotary_dim=64),
        "rope_off": dict(n_heads=32, n_kv_heads=4, head_dim=128, rope=False,
                         gate=True, norm_eps=1e-5),
        "decode": dict(n_heads=32, n_kv_heads=4, head_dim=128),
        "ring": dict(n_heads=32, n_kv_heads=4, head_dim=128, seq_axis="sp"),
    }


@pytest.mark.parametrize("cell", ["qwen3next", "rope_off", "decode", "ring"])
def test_layers_that_keep_the_composition_hold_neither_kernel(
        v5e, monkeypatch, cell):
    """A grouped, per-head-normed `Attention` over 2,048 rows lowered for the
    described chip where `attn_prep.prep_rows` refuses: Qwen3-Next's heads
    of 256 with 64 channels turned, a layer that does not turn (Trinity's
    full ones), a cached decode (positions a batch row) and a ring step
    (`seq_axis`).  Neither kernel's name is in the text, and where the layer
    turns, `rope`'s products are."""
    from jax import shard_map
    from jax.sharding import Mesh, SingleDeviceSharding

    from horovod_tpu.models.transformer import Attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq, d_model = 2048, 1024
    layer = Attention(dtype=jnp.bfloat16, use_flash=True, head_norm=True,
                      **_composed_layers()[cell])
    mesh = Mesh(np.array(v5e).reshape(1, 4), ("dp", "sp"))
    on_chip = NamedSharding(mesh, P()) if cell == "ring" \
        else SingleDeviceSharding(v5e[0])
    rows = NamedSharding(mesh, P("dp", "sp", None)) if cell == "ring" \
        else on_chip

    def shaped(tree, sharding=on_chip):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    plain = layer.clone(seq_axis=None)      # the parameters need no mesh
    x = jax.ShapeDtypeStruct((1, seq, d_model), jnp.bfloat16, sharding=rows)
    params = shaped(jax.eval_shape(
        lambda: plain.init(jax.random.PRNGKey(0),
                           jnp.zeros(x.shape, x.dtype))["params"]))

    def apply(params, x, *ctx):
        out = layer.apply({"params": params}, x, *ctx,
                          mutable=["intermediates"])[0]
        return out[0] if ctx else out

    if cell == "decode":
        heads, ctx_len = layer.n_heads, 1024
        ctx = shaped((
            jnp.zeros((1, heads, ctx_len, 128), jnp.bfloat16),
            jnp.zeros((1, heads, ctx_len, 128), jnp.bfloat16),
            jnp.zeros((1, ctx_len), bool), jnp.zeros((1, seq), jnp.int32)))
        text = jax.jit(apply).lower(params, x, ctx).as_text(debug_info=True)
    else:
        if cell == "ring":
            apply = shard_map(apply, mesh=mesh,
                              in_specs=(P(), P("dp", "sp", None)),
                              out_specs=P("dp", "sp", None))

        def loss(params, x):
            with jax.named_scope("hvd_loss"):
                return apply(params, x).astype(jnp.float32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).as_text(debug_info=True)
    assert "hvd_attn_prep" not in text
    assert ("...d,de->...e" in text) == layer.rope


def _equations(jaxpr):
    """The equations of a jaxpr and of every jaxpr its equations hold."""
    inner = [getattr(value, "jaxpr", value)
             for eqn in jaxpr.eqns for held in eqn.params.values()
             for value in (held if isinstance(held, (list, tuple))
                           else [held])]
    return len(jaxpr.eqns) + sum(_equations(j) for j in inner
                                 if hasattr(j, "eqns"))


def _kernel_body(fn, *operands):
    """The jaxpr of the first `pallas_call` in ``fn(*operands)``."""
    def found(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["jaxpr"]
            for held in eqn.params.values():
                inner = getattr(held, "jaxpr", held)
                if hasattr(inner, "eqns") \
                        and (body := found(inner)) is not None:
                    return body
    return found(jax.make_jaxpr(fn)(*operands).jaxpr)


@pytest.mark.parametrize("rows,bound", [(128, (40, 60)), (2048, (90, 125))],
                         ids=["one_chunk", "a_tile_of_eight"])
def test_a_prep_body_is_not_written_out_a_chunk(rows, bound):
    """What Python traces for a tile: a body of eight chunks holds two chunks'
    equations and a loop's (the first chunk's row sums, the loop's step, the
    last chunk's turn), forward 67 and backward 95 where one chunk is 32 and
    47 — eight written out would be some 260 and 380, and sixteen of 128 rows
    took the chip machine's host 1.2 to 2.1 s a backward body (PERF.md
    section 6, PR 62 and PR 63).  A later change that unrolls the body in
    Python again fails here, not on the driver's `setup_s`."""
    from horovod_tpu.ops import attn_prep

    x = jax.ShapeDtypeStruct((2, 4096, 128), jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32)
    table = jax.ShapeDtypeStruct((4096, 128), jnp.float32)
    forward = _kernel_body(
        lambda x, s, c, si: attn_prep._fwd_call(x, s, c, si, 1e-6, rows,
                                                False), x, scale, table, table)
    backward = _kernel_body(
        lambda d, x, s, c, si: attn_prep._bwd_call(d, x, s, c, si, 1e-6, rows,
                                                   False),
        x, x, scale, table, table)
    assert _equations(forward) <= bound[0], _equations(forward)
    assert _equations(backward) <= bound[1], _equations(backward)
