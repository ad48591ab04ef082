"""The model's layers name themselves in the program: every heavy operation of
the loss of each LM cell's step (the benchmark's builders at their rehearsal
sizes, lowered with locations and not compiled) lies under exactly one
family of layer scopes, forward and backward, and the delta rule's four
stages partition its scope.  The families are listed once, in
benchmark/layer_metrics/_layers.py, whose readers sort a device trace by
them (PERF.md section 3 has the table of names)."""

import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import run as harness
from benchmark.layer_metrics import _layers

# The operations whose time a trace shows on a line of its own: products,
# row movement, sorts, loops, kernels.
HEAVY = ("dot_general", "convolution", "gather", "scatter", "sort", "while",
         "custom_call")
FORWARD, BACKWARD = "/jvp(hvd_loss)/", "/transpose(jvp(hvd_loss))/"
# cell -> the families its configuration's model has
CELLS = {
    "pythia410m_1chip_4x2k": {"head", "embed", "mlp", "attn_proj"},
    "pythia410m_1chip_1x8k": {"head", "embed", "mlp", "attn_proj"},
    "olmoe1b7b_1chip_ep4share_2x4k": {"head", "embed", "attn_proj", "moe"},
    "nemotron3super120b_1chip_tp8ep64share_1x4k": {
        "head", "embed", "attn_proj", "moe", "ssm"},
    "ling3flash_1chip_tp8ep64share_1x8k": {
        "head", "embed", "mlp", "moe", "kda", "mla"},
    "trinitymini_1chip_ep8share_1x8k": {
        "head", "embed", "mlp", "attn_proj", "moe"},
    "sdar30ba3b_1chip_ep8share_1x4k_noised": {
        "head", "embed", "attn_proj", "moe"},
    "granite4hmicro_1chip_pp4share_1x8k": {
        "head", "embed", "mlp", "attn_proj", "ssm"},
    "joyaiflash_1chip_ep16share_1x8k": {
        "head", "embed", "mlp", "moe", "mla"},
}
# What a multi-token-prediction module adds beside layers of the families:
# its two norms and `W_eh`.  `_layers.SCOPES` lists no family for it (a
# traced run files it as unscoped; PERF.md section 7).
MODULE_PROJECTION = "/hvd_mtp/hvd_mtp_proj/"

_ALIAS = re.compile(r'^(#loc\d*) = loc\((.*)\)$')
_NAMED = re.compile(r'^"([^"]*)"')
_USE = re.compile(r"loc\((#loc\d*|\"[^\"]*\"[^)]*)\)\s*$")
_FUNCTION = re.compile(r"^\s*func\.func (?:public |private )?@([\w.]+)\(")
_CALL = re.compile(r"= (?:func\.)?call @([\w.]+)\(")
_OP = re.compile(r'^(\s*)(?:%%[\w:#]+ = )?"?stablehlo\.(%s)\b'
                 % "|".join(HEAVY))


def heavy_operations(text):
    """[(opcode, scope path)] of the `HEAVY` operations of a module's text
    printed with locations.  An operation with regions (`while`, `scatter`,
    `sort`) carries its location where its last region closes, at the
    indentation it opened at.  Inside a private function (an inner `jit`:
    `_take`, `argsort`) a path is relative, and is given here once for every
    call of the function, behind the call's own path."""
    aliases = {}
    for line in text.splitlines():
        alias = _ALIAS.match(line)
        if alias:
            aliases[alias.group(1)] = alias.group(2)

    def path(location):
        for _ in range(64):     # "name"(#child), callsite(#callee at ...)
            location = aliases.get(location, location)
            named = _NAMED.match(location)
            if named:
                return named.group(1)
            inner = re.search(r"#loc\d*", location)
            if not inner:
                return ""
            location = inner.group(0)
        return ""

    found, calls = {}, {}        # function -> [(opcode, path)]; callee ->
    function, pending = None, []            # [(caller, path)]
    for line in text.splitlines():
        defined = _FUNCTION.match(line)
        opened, used = _OP.match(line), _USE.search(line)
        called = _CALL.search(line)
        if defined:
            function = defined.group(1)
        elif called and used:
            calls.setdefault(called.group(1), []).append(
                (function, path(used.group(1))))
        elif opened and used:
            found.setdefault(function, []).append(
                (opened.group(2), path(used.group(1))))
        elif opened:
            pending.append((opened.group(1), opened.group(2)))
        elif pending and used and line.startswith(pending[-1][0] + "}"):
            found.setdefault(function, []).append(
                (pending.pop()[1], path(used.group(1))))
    assert not pending, pending

    def prefixes(name):
        if name == "main":
            return [""]
        return [f"{outer}{site}/" for caller, site in calls.get(name, [])
                for outer in prefixes(caller)]

    return [(op, prefix + relative) for name, operations in found.items()
            for prefix in prefixes(name) for op, relative in operations]


def scope_paths(text):
    """Every scope path a module's text names a location by."""
    return {named.group(1) for named in (
        _NAMED.match(alias.group(2)) for alias in map(
            _ALIAS.match, text.splitlines()) if alias) if named}


def lowered_step(cell, **resized):
    """The step of ``cell``'s builder at its rehearsal sizes, lowered;
    ``resized`` are sizes of the configuration this test asks for instead."""
    spec = harness.load_cell(cell, rehearse=True)
    config, traffic = dict(spec["config"], **resized), spec["traffic"]
    devices = jax.devices()[:spec["cell"]["chips"]]
    built = importlib.import_module(
        f"benchmark.builders.{config['builder']}").build(
            config, traffic, devices, seed=0)

    def shaped(tree, spec):
        sharding = NamedSharding(built.mesh, spec)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    state = shaped(jax.eval_shape(built.init_state), P())
    fields = {f["name"]: jax.ShapeDtypeStruct(
        (traffic["batch_per_chip"] * len(devices), *f["shape"]), f["dtype"])
        for f in built.fields}
    batch = shaped(jax.eval_shape(built.make_batch, fields),
                   P(built.mesh.axis_names[0]))
    return built.step.lower(state[0], state[1],
                            tuple(batch) + tuple(state[2:]))


def families_in(path):
    return {_layers.SCOPES[m.group(1)] for m in _layers._LAYER.finditer(path)}


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_heavy_operation_of_the_loss_is_under_one_layer(cell):
    text = lowered_step(cell).as_text(debug_info=True)
    operations = heavy_operations(text)
    def in_loss(path):
        return FORWARD in path or BACKWARD in path

    inside = [(op, path) for op, path in operations if in_loss(path)]
    assert len(inside) > 20 and {"dot_general", "gather"} <= {
        op for op, _ in inside}, "the text's operations were not found"
    seen = {direction: set() for direction in (FORWARD, BACKWARD)}
    for op, path in inside:
        families = families_in(path)
        if MODULE_PROJECTION in path and not families:
            assert op == "dot_general", (op, path)
            continue
        assert len(families) == 1, (
            f"{op} at {path} lies under {sorted(families) or 'no'} layer "
            "scope: a layer of horovod_tpu/models runs without a scope of "
            "its own, or two families nest")
        assert _layers.layer_of(path) in families
        seen[BACKWARD if BACKWARD in path else FORWARD] |= families
        if "hvd_kda_scan" in path:
            stages = set(_layers._STAGE.findall(path))
            assert len(stages) == 1 and _layers.stage_of(path) in stages, (
                f"{op} at {path} lies under {sorted(stages) or 'no'} stage "
                "of the delta rule")
    assert seen[FORWARD] == seen[BACKWARD] == CELLS[cell]
    if "kda" in CELLS[cell]:
        for direction in (FORWARD, BACKWARD):
            assert {_layers.stage_of(path) for _, path in inside
                    if direction in path and "hvd_kda_scan" in path} \
                == set(_layers.STAGES), direction
        # The stages partition the scope: no operation at all, a cast or a
        # reshape either, lies under `hvd_kda_scan` and under no stage.
        under = [path for path in scope_paths(text) if "/hvd_kda_scan/" in path]
        assert len(under) > 50
        for path in under:
            assert len(set(_layers._STAGE.findall(path))) == 1, path
    # Outside the loss the optimizer's scope, and no layer's.
    for op, path in operations:
        assert in_loss(path) or not families_in(path), (op, path)


def test_a_prediction_module_nests_its_layers_scopes_under_its_own():
    """The JoyAI cell's step at its rehearsal sizes: every heavy operation of
    the module — its lookup, `W_eh`, its block's products, gathers and sorts,
    its head — lies under `hvd_mtp`, forward and backward, the pattern's
    under none of it; the module's share of the dot_generals is a whole
    layer's, a projection's and a head's; `hvd_mla_q_latent` holds one
    product a block and a direction (two backward: the input's and the
    weight's), and the tree one table and one head."""
    text = lowered_step("joyaiflash_1chip_ep16share_1x8k").as_text(
        debug_info=True)
    inside = [(op, path) for op, path in heavy_operations(text)
              if FORWARD in path or BACKWARD in path]
    module = [(op, path) for op, path in inside if "/hvd_mtp/" in path]
    for op, path in inside:
        assert ("/hvd_mtp/" in path) == ("mtp_0_" in path
                                         or "/hvd_mtp/hvd_" in path), path
    for direction in (FORWARD, BACKWARD):
        scopes = {scope for _, path in module if direction in path
                  for scope in re.findall(r"/(hvd_\w+)", path)}
        assert {"hvd_embed", "hvd_mtp_proj", "hvd_mla_q_latent",
                "hvd_mla_q_proj", "hvd_mla_kv_latent", "hvd_mla_attend",
                "hvd_mla_out_proj", "hvd_moe_router", "hvd_moe_dispatch",
                "hvd_moe_experts", "hvd_moe_combine", "hvd_moe_shared",
                "hvd_lm_head"} <= scopes, (direction, sorted(scopes))
    latent = [path for op, path in inside
              if op == "dot_general" and "hvd_mla_q_latent" in path]
    blocks = 3                       # two kept layers' and the module's
    assert sum(FORWARD in path and BACKWARD not in path
               for path in latent) == blocks
    assert sum(BACKWARD in path and "rematted" not in path
               for path in latent) == 2 * blocks


# The lowered steps of three accepted cells at their rehearsal sizes, as the
# parent of the PR that added the query latent, the absent gate and the
# prediction module lowered them (PR 66; text for text: sha256 of
# `lowered.as_text()`).  A PR that changes one of these models' programs on
# purpose records the new text's here.
LOWERED_AS_BEFORE = {
    "ling3flash_1chip_tp8ep64share_1x8k":
        "472a13eda393ce1754ae41b8c0d1261fc1eba2b5410834c391c250223c9ada91",
    "trinitymini_1chip_ep8share_1x8k":
        "9294722f4243c277d7d11751938d847744e009699e4bd5537b9b6b623ac64a07",
    "granite4hmicro_1chip_pp4share_1x8k":
        "c273ff8deb8c0bd998939aa4a330a7cbf7d5fcc3e76d35a6dfd649d55ed2d038",
    "mellum2_1chip_ep4share_1x16k":
        "e67be0f0d57818576acc7bd5af14b91a5ffbc2cddbdbf67d86df6c42b3091839",
}


@pytest.mark.parametrize("cell", list(LOWERED_AS_BEFORE))
def test_an_unset_module_and_query_latent_leave_a_lowered_step_as_it_was(
        cell):
    """`LatentConfig`'s and `TransformerLM`'s new fields at their defaults
    trace nothing: Ling's (latent attention with its gate and no query
    latent), Trinity's, Granite's and Mellum's steps lower to the text they
    lowered to before the fields were there."""
    text = lowered_step(cell).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == LOWERED_AS_BEFORE[cell], (
            f"{cell}'s lowered step is not the text recorded here: a model "
            "that sets none of the new fields traces another program")


def test_fused_head_and_loss_is_one_loop_under_the_heads_scope():
    """`TransformerLM(targets=)`: the chunked head-and-loss is a `while`
    forward and one backward, both under `hvd_lm_head`, with every product
    and gather of theirs; `hvd_token_xent` does not exist there."""
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=512, d_model=64, n_layers=1, n_heads=2,
                          d_ff=128, dtype=jnp.float32, use_flash=False)
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])

    def loss(params, tokens):
        with jax.named_scope("hvd_loss"):
            return model.apply({"params": params}, tokens, targets=tokens)

    text = jax.jit(jax.value_and_grad(loss)).lower(params, tokens).as_text(
        debug_info=True)
    assert "hvd_token_xent" not in text
    operations = heavy_operations(text)
    loops = [path for op, path in operations
             if op == "while" and "/hvd_lm_head/" in path]
    assert len(loops) == 2 and sum("transpose(" in p for p in loops) == 1
    for op, path in operations:
        assert len(families_in(path)) == 1, (op, path)
    head = [op for op, path in operations if "/hvd_lm_head/" in path]
    assert head.count("dot_general") >= 3 and "gather" in head


def test_block_diffusion_step_names_its_loss_and_its_kernels(monkeypatch):
    """The SDAR cell's step: the masked-diffusion loss runs under
    `hvd_diffusion_loss`, forward and backward, with the per-token
    cross-entropy's own scope inside it (the head's family); the attention
    keeps `hvd_attn_qkv`, `hvd_attn_attend`, `hvd_attn_out`; and the four
    kernels `ops/attention.py` names under `block_diffusion=` are the ones
    the benchmark's readers look for, filed under `flash`."""
    import horovod_tpu.ops.attention as attn
    from benchmark.layer_metrics import _sdar
    from tests.test_ops import _pallas_call_names

    text = lowered_step("sdar30ba3b_1chip_ep8share_1x4k_noised").as_text(
        debug_info=True)
    paths = scope_paths(text)
    for direction in (FORWARD, BACKWARD):
        assert any(direction in path and "/hvd_diffusion_loss/hvd_token_xent/"
                   in path for path in paths), direction
        for scope in ("hvd_attn_qkv", "hvd_attn_attend", "hvd_attn_out"):
            assert any(direction in path and f"/{scope}/" in path
                       for path in paths), (direction, scope)
    assert _layers.layer_of(
        "jit(f)/jvp(hvd_loss)/hvd_diffusion_loss/hvd_token_xent/exp") == "head"

    shape = jax.ShapeDtypeStruct((1, 2, 512, 64), jnp.bfloat16)

    def loss(q, k, v):
        return attn.flash_attention(q, k, v, block_diffusion=4,
                                    interpret=True).astype(jnp.float32).sum()

    names = set()
    for plan in ("combined", "split"):
        monkeypatch.setattr(attn, "_bwd_plan", lambda q_len, d, bq, bk, bh=1,
                            plan=plan: (plan, min(bq, 128), min(bk, 128)))
        names |= set(_pallas_call_names(jax.make_jaxpr(jax.grad(
            loss, (0, 1, 2)))(shape, shape, shape).jaxpr))
    assert names == {"hvd_flash_fwd_blockdiff", "hvd_flash_bwd_blockdiff",
                     "hvd_flash_bwd_dkdv_blockdiff",
                     "hvd_flash_bwd_dq_blockdiff"}
    for name in names:
        direction = "fwd" if "fwd" in name else "bwd"
        assert _sdar.BLOCKDIFF[direction].match(name + ".7"), name
        assert not _sdar.BLOCKDIFF[direction].match(
            name.replace("_blockdiff", "_window") + ".7")
        assert _layers.column_of(f"{name}.7|custom-call|x", None) == "flash"


GDN_SCOPES = ("in_proj", "conv", "gate", "scan", "gate_norm", "out_proj")
_GDN = re.compile(r"(?:^|/)hvd_gdn_(%s)(?=/|$)" % "|".join(GDN_SCOPES))
_GDN_STAGE = re.compile(r"(?:^|/)hvd_gdn_scan_(%s)(?=/|$)"
                        % "|".join(_layers.STAGES))


@pytest.mark.parametrize("cell,others", [
    ("qwen3next80b_1chip_ep16share_1x4k",
     {"head", "embed", "attn_proj", "moe"}),
    ("olmohybrid7b_1chip_tp2share_1x8k",
     {"head", "embed", "attn_proj", "mlp"})])
def test_gated_delta_layers_name_their_stages_under_scopes_of_their_own(
        cell, others):
    """The Qwen3-Next cell's step, and Olmo-Hybrid's (the same mixer at a key
    and a value width of their own, a scaled step, the output's norm alone —
    no scope of its own: the reordered norm is a layer's norm like any
    other's): every heavy operation of a `gated_delta`
    layer lies under exactly ONE of the six `hvd_gdn_*` scopes, forward and
    backward, and under no `hvd_kda_*` one (those stay Ling's); the delta
    rule's four stages partition `hvd_gdn_scan`, down to its casts; every other
    heavy operation of the loss lies under one family of `_layers.SCOPES`, as
    in the other cells.  `benchmark/layer_metrics/_layers.py`'s table has no
    `gdn_` row, and this PR may not edit that file: `model_unscoped_pct`
    would file the mixers under `unscoped`, so the new cell is not on that
    metric's list (PERF.md section 7 names the edit)."""
    text = lowered_step(cell).as_text(debug_info=True)
    inside = [(op, path) for op, path in heavy_operations(text)
              if FORWARD in path or BACKWARD in path]
    seen = {direction: set() for direction in (FORWARD, BACKWARD)}
    stages = {direction: set() for direction in (FORWARD, BACKWARD)}
    families = set()
    for op, path in inside:
        direction = BACKWARD if BACKWARD in path else FORWARD
        named = set(_GDN.findall(path))
        assert "hvd_kda_" not in path, path
        if not named:
            assert len(families_in(path)) == 1, (op, path)
            families |= families_in(path)
            continue
        assert len(named) == 1 and not families_in(path), (
            f"{op} at {path} lies under {sorted(named)} of the mixer's "
            f"scopes and {sorted(families_in(path))} of another layer's")
        seen[direction] |= named
        if named == {"scan"}:
            stage = set(_GDN_STAGE.findall(path))
            assert len(stage) == 1, (op, path)
            stages[direction] |= stage
    assert families == others
    # The products: both projections, the rule's; the gate and the norm are
    # elementwise and hold none.
    for direction in (FORWARD, BACKWARD):
        assert seen[direction] >= {"in_proj", "scan", "out_proj"}, direction
        assert stages[direction] == set(_layers.STAGES), direction
    paths = scope_paths(text)
    for direction in (FORWARD, BACKWARD):
        for scope in GDN_SCOPES:
            assert any(direction in path and f"/hvd_gdn_{scope}/" in path
                       for path in paths), (direction, scope)
    under = [path for path in paths if "/hvd_gdn_scan/" in path]
    assert len(under) > 50
    # The recurrence's two kernels (here the interpreter's loops over their
    # grids) lie under the carry's scope, each in its own direction.
    for direction, kernel in ((FORWARD, "fwd"), (BACKWARD, "bwd")):
        named = [path for path in under if f"/hvd_gdn_scan_carry_{kernel}/"
                 in path]
        assert named and all(
            direction in path and "/hvd_gdn_scan_carry/" in path
            for path in named), (direction, named[:3])
    for path in under:
        assert len(set(_GDN_STAGE.findall(path))) == 1, path
    # In a `gated_delta` layer nothing but the layer's norm and its residual
    # add lies outside the mixer's scopes.
    for path in paths:
        if "/layer_0/" in path and "/mixer/" in path:
            assert _GDN.search(path), path


SSM_STAGES = ("decay", "intra", "ends", "carry")      # ops.ssm.STAGES
_SSM_STAGE = re.compile(r"(?:^|/)hvd_ssm_scan_(%s)(?=/|$)"
                        % "|".join(SSM_STAGES))


NEMOTRON, GRANITE = ("nemotron3super120b_1chip_tp8ep64share_1x4k",
                     "granite4hmicro_1chip_pp4share_1x8k")
# Granite's rehearsal at ONE chunk of 128: eight heads of 16 on one group then
# hold 4 KB of `L` a token beside 1.2 KB of operands, and `ops.ssm.lowered_plan`
# hands the scan to its kernels, as at the cell's own sizes (at the
# rehearsal's chunk of 32 the matrix is lighter than Nemotron's and stays on
# the products).
WITH_KERNELS = {"scan_chunk": 128}
_SCAN_KERNEL = re.compile(r"/hvd_ssm_scan_intra_(fwd|bwd)/")


@pytest.mark.parametrize("cell,resized", [
    (NEMOTRON, {}), (GRANITE, {}), (GRANITE, WITH_KERNELS)],
    ids=["nemotron", "granite", "granite_with_kernels"])
def test_the_chunked_scans_four_stages_partition_its_scope(cell, resized):
    """Any caller of `ops.ssm.chunked_scan` (Nemotron's 16-head share on one
    group of eight, Granite's 64 whole heads on its one group), whichever
    form carries it: every operation under `hvd_ssm_scan`, a cast or a
    reshape too, lies under exactly ONE of `hvd_ssm_scan_decay`, `_intra`,
    `_ends`, `_carry`, forward and backward.  On the products the three that
    hold products hold them in both directions, and `decay` holds none (sums
    and exponentials), and no operation names a kernel.  On the kernels
    `decay` keeps the cumulative sums and EVERY product lies under
    `hvd_ssm_scan_intra`, inside the pair (here the interpreter's loops over
    their grids), each in its own direction, three calls a mixer — forward,
    the recomputed forward, backward — of ONE function a direction; `_ends`
    and `_carry` name nothing (the states stay on the chip)."""
    from horovod_tpu.ops.ssm import STAGES

    assert STAGES == SSM_STAGES
    staged = set(STAGES) if not resized else {"decay", "intra"}
    text = lowered_step(cell, **resized).as_text(debug_info=True)
    paths = scope_paths(text)
    # What the mixer itself does under its scope: the reshapes of its
    # operands, the softplus of dt, the sown counters.
    under = [path for path in paths if "/hvd_ssm_scan/hvd_ssm_scan_" in path]
    assert len(under) > 50
    for path in under:
        assert len(set(_SSM_STAGE.findall(path))) == 1, path
    for direction in (FORWARD, BACKWARD):
        assert {stage for path in under if direction in path
                for stage in _SSM_STAGE.findall(path)} == staged
    products = {direction: set() for direction in (FORWARD, BACKWARD)}
    for op, path in heavy_operations(text):
        if "/hvd_ssm_scan/" in path and op == "dot_general":
            stage = set(_SSM_STAGE.findall(path))
            assert len(stage) == 1, (op, path)
            products[BACKWARD if BACKWARD in path else FORWARD] |= stage
    assert products[FORWARD] == products[BACKWARD] == staged - {"decay"}
    # Outside the four stages the scan's scope holds the mixer's own
    # preparation alone: no product.
    for op, path in heavy_operations(text):
        if "/hvd_ssm_scan/" in path and not _SSM_STAGE.search(path):
            assert op not in ("dot_general", "while", "custom_call"), path
    # The kernels' own operations, each path behind its call's (a jitted call
    # is a function of the module, and its paths start at it).
    named = [(op, path) for op, path in heavy_operations(text)
             if _SCAN_KERNEL.search(path)]
    calls = re.findall(r"call @(_scan_call[\w.]*)\(", text)
    if not resized:
        assert not named and not calls and "_scan_call" not in text
        return
    for kernel in ("fwd", "bwd"):
        of_kernel = [path for _, path in named
                     if f"/hvd_ssm_scan_intra_{kernel}/" in path]
        assert of_kernel, kernel
        for path in of_kernel:
            assert "/hvd_ssm_scan/hvd_ssm_scan_intra/" in path, path
            again = "rematted_computation" in path
            assert (BACKWARD in path) == (kernel == "bwd" or again), path
    # Every product of the scan is the kernels'.
    assert {path for op, path in heavy_operations(text)
            if "/hvd_ssm_scan/" in path and op == "dot_general"} \
        == {path for op, path in named if op == "dot_general"}
    mixers = harness.load_cell(cell, rehearse=True)["config"][
        "layer_types"].count("mamba")
    # One function a direction, and the recomputed forward's own.
    assert len(calls) == 3 * mixers and len(set(calls)) == 3, calls


def test_a_looped_models_exits_and_the_layers_in_its_loop_name_themselves():
    """The Ouro cell's step, compiled at its rehearsal sizes: the exit gate
    (`hvd_exit_gate`, inside the rolled loop over the passes) and the exit
    loss (`hvd_exit_loss`, behind it) reach an operation's `op_name` forward
    and backward; the layers, the head and the per-token cross-entropy keep
    their scopes inside the loop's body, in both directions, and what is
    computed again there carries `jax.checkpoint`'s marker.  The benchmark's
    readers sort a trace by these names
    (benchmark/layer_metrics/exit_time_share_pct.py)."""
    text = lowered_step("ouro2p6b_1chip_pp6share_1x4k").compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    in_loop = "/while/body/"
    for direction in (FORWARD, BACKWARD):
        def named(scope, inside):
            return any(direction in name and f"/{scope}/" in name
                       and (in_loop in name.split(scope)[0]) == inside
                       for name in names)

        assert named("hvd_exit_loss", False), direction
        for scope in ("hvd_exit_gate", "hvd_attn_qkv", "hvd_attn_attend",
                      "hvd_attn_out", "hvd_mlp", "hvd_lm_head",
                      "hvd_token_xent"):
            assert named(scope, True), (direction, scope)
    again = [name for name in names if "rematted_computation" in name]
    # (A few instructions of a fused computation keep the body's own,
    # relative name.)
    assert again and all(BACKWARD in name and in_loop in name
                         for name in again if name.startswith("jit("))
    for scope in ("hvd_mlp", "hvd_attn_qkv", "hvd_lm_head", "hvd_token_xent"):
        assert any(f"/{scope}/" in name for name in again), scope
    assert not any("hvd_exit_loss" in name for name in again)
