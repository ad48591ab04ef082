"""A step sorted by the model's layers: what the nine readers of the dense
layers' scopes, of the delta rule's stages and of `model_unscoped_pct` share.

Every layer of `horovod_tpu.models` runs under `jax.named_scope`s that reach an
operation's `op_name` in the compiled step's text, forward and backward alike
(`_hybrid.scope_time` has how the device trace's events find it).  The scopes
fall into families, and this file is the one place that lists them:

    head       hvd_lm_head (the head's matmul; the whole chunked loop of
               `fused_next_token_loss`), hvd_token_xent (the loss's own pass)
    embed      hvd_embed (the lookup; its gradient's scatter-add)
    mlp        hvd_mlp (`Block`'s up-gelu-down, `GatedMLP`)
    attn_proj  hvd_attn_qkv, hvd_attn_attend, hvd_attn_out of `Attention`,
               less the flash kernels
    moe        hvd_moe_* (router, dispatch, experts, combine, latent, shared)
               and libtpu's `ragged-dot-*` kernels, whose path libtpu drops
    ssm, kda, mla   hvd_ssm_*, hvd_kda_*, hvd_mla_* (less the flash kernels)
    flash      the custom calls named hvd_flash_fwd / hvd_flash_bwd*, by
               instruction name, whichever layer called them

An operation is filed under ONE family: a kernel known by its instruction name
first; then `program_trace.phase`'s rule (`optimizer`, or `unattributed`
where the path holds no scope of `build_train_step`); then, inside `hvd_loss`,
the INNERMOST layer scope of its path (the last one: a dense MLP inside a
sparse-expert layer would be the MLP's), and `unscoped` where no layer claims
it.  So the families, `optimizer`, `unattributed` and `unscoped` cover every
event once, over the denominator every `*_time_share_pct` has: the time of all
operations, mean over chips.  `unattributed` here is `phase_unattributed_pct`
less the kernels taken by name.

A program that does not name its head (a parent of the PR that added the
scopes, the recorded trace) gives None from every function here: nothing
raises, and `model_unscoped_pct` does not call a model without names 90 %
unnamed.
"""

import re

from benchmark import program_trace
from benchmark.layer_metrics import _ling, _moe
from benchmark.layer_metrics._program import OP_NAMES_PROBE

# scope prefix (after `hvd_`) -> family; `layer_of` has the rule.
SCOPES = {"lm_head": "head", "token_xent": "head", "embed": "embed",
          "mlp": "mlp", "attn_": "attn_proj", "moe_": "moe", "ssm_": "ssm",
          "kda_": "kda", "mla_": "mla"}
FAMILIES = tuple(dict.fromkeys(SCOPES.values())) + ("flash",)
COLUMNS = FAMILIES + ("optimizer", "unattributed", "unscoped")
STAGES = ("decays", "chunk", "solve", "carry")

_LAYER = re.compile(r"(?:^|/)hvd_(%s)" % "|".join(SCOPES))
_STAGE = re.compile(r"(?:^|/)hvd_kda_scan_(%s)(?=/|$)" % "|".join(STAGES))
_HEAD = "lm_head"


def _innermost(pattern, path):
    found = None
    for found in pattern.finditer(path or ""):
        pass
    return found and found.group(1)


def layer_of(path):
    """The family of the innermost layer scope of an op_name, or None."""
    return SCOPES.get(_innermost(_LAYER, path))


def stage_of(path):
    """The delta rule's stage an op_name lies in, or None."""
    return _innermost(_STAGE, path)


def column_of(short: str, path) -> str:
    """The one column of `COLUMNS` an event of the operations line is filed
    under, from its short name and its instruction's op_name."""
    instruction = program_trace.instruction(short)
    if _ling._FLASH.match(instruction):
        return "flash"
    if _moe._GROUPED_MATMUL.match(instruction):
        return "moe"
    phase = program_trace.phase(path)
    if phase in ("optimizer", "unattributed"):
        return phase
    return layer_of(path) or "unscoped"


def sorted_time(run: dict):
    """({column: nanoseconds}, {stage: nanoseconds}, nanoseconds of all
    operations), mean over chips; None where there is no trace, no compiled
    text, or no operation under `hvd_lm_head`."""
    program = program_trace.of_run(run)
    names = run["probes"].get(OP_NAMES_PROBE)
    if not program or not names:
        return None
    paths = names["op_names"]
    filed = {}          # instruction -> (column, stage, names the head)
    columns, stages = dict.fromkeys(COLUMNS, 0.0), dict.fromkeys(STAGES, 0.0)
    everything, head_named = 0.0, False
    chips = max(len(program["devices"]), 1)
    for events in program["devices"].values():
        for short, _, duration in events:
            if short not in filed:
                path = paths.get(program_trace.instruction(short))
                filed[short] = (column_of(short, path), stage_of(path),
                                _innermost(_LAYER, path) == _HEAD)
            column, stage, names_head = filed[short]
            everything += duration / chips
            columns[column] += duration / chips
            if stage:
                stages[stage] += duration / chips
            head_named = head_named or names_head
    return (columns, stages, everything) if head_named else None


def share_pct(run: dict, column: str):
    """A family's share of the time of all operations; None where nothing ran
    under it.  `unscoped` is a gauge: where the model names its layers and
    leaves nothing out it reads 0.0, not None."""
    timed = sorted_time(run)
    if not timed or not (timed[0][column] or column == "unscoped"):
        return None
    return 100.0 * timed[0][column] / timed[2]


def stage_share_pct(run: dict, stage: str):
    """A stage's share of the time of all operations (the four sum to
    `kda_scan_time_share_pct`); None where the program has no such stage."""
    timed = sorted_time(run)
    return 100.0 * timed[1][stage] / timed[2] \
        if timed and timed[1][stage] else None
