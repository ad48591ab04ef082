"""Granite-4.0-H-Micro trained through the program's normal path:
`models.TransformerLM(layers=..., tie_head=True)` — a per-layer pattern of
Mamba-2 mixers whose 64 heads all read ONE group's B and C (`ssm`:
`Mamba2Config(groups=1)`), grouped-query attention with no position embedding
whose softmax scale is the configuration's `attention_multiplier`
(`attention`: `rope=False`, `attn_scale=`), and a dense gated MLP behind every
mixer (`gated_mlp`), every entry joining the stream through
`residual_multiplier` (`residual_scale=`); the embedding times
`embedding_multiplier` (`embed_scale=`), the head the embedding's own table
with its logits divided by `logits_scaling` (`tie_head=`, `logits_divisor=`) —
`models.next_token_loss`, `hvd.jax.build_train_step` on
`data_parallel_mesh(devices)`, AdamW.

A published layer is two pattern entries, its mixer and then its MLP; layer
`i` is what `layer_types[i]` says.  Every layer is whole: the cut is by depth
(one period of ten, a pipeline stage) and by vocabulary (a slice of the tied
table's rows) alone.

The comparison with the reference (`against_reference`, `compare_rows`: module
functions, so that a control can put another program on either side) compiles
two programs: the system's loss and gradients on a one-device mesh of the
step's axis name, and the reference's, each parameter's reference gradient
reduced against the system's where the backward pass makes it
(`trinity_lm._met`), a group of parameters a layer kind, so that the two whole
gradients never stand side by side.  The tied table is met ONCE: what reaches
it is the sum of the lookup's and the head's cotangents, on both sides.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count, ops_count_granite
from benchmark.builders import Built, collectives_expected, dtype_of
from benchmark.builders.olmohybrid_lm import recomputed
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, granite_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "granitemoehybrid", "hidden_act": "silu",
    "attention_bias": False, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "position_embedding_type": "nope", "tie_word_embeddings": True,
    "num_local_experts": 0, "num_experts_per_tok": 0, "rope_scaling": None}
KINDS = {"mamba": "ssm", "attention": "attention"}
# The groups of parameters whose gradients are compared each on its own: a
# wrong recurrence hides inside a right total, the MLPs being two thirds of
# it.  `embedding` is the tied table (the lookup's and the head's gradients,
# summed) with `final_norm`.
GROUPS = ("ssm", "attention", "gated_mlp", "embedding")


@dataclasses.dataclass
class BuiltGranite(Built):
    # (params, (inputs, targets)) -> {"carried": [...], "chunks": [...]}, a
    # Mamba-2 layer each, of one forward pass on one device
    # (layer_metrics/ssm_carry_live_pct.py).
    ssm_carry: Optional[Callable] = None


def layer_kinds(config: dict) -> tuple:
    """The pattern: each published layer's mixer, then its MLP."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - set(KINDS):
        raise ValueError(f"layer_types names one of {tuple(KINDS)} for each "
                         f"of num_hidden_layers, not {types}")
    return tuple(kind for published in types
                 for kind in (KINDS[published], "gated_mlp"))


def model_of(config: dict):
    """(the model, its layer kinds) of a configuration."""
    from horovod_tpu.models import Mamba2Config, TransformerLM
    from horovod_tpu.models.ssm import DT_FLOOR, DT_RANGE

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    heads, hidden = config["num_attention_heads"], config["hidden_size"]
    seeding = (config["time_step_min"], config["time_step_max"]), \
        config["time_step_floor"]
    if wrong or hidden % heads or seeding != (DT_RANGE, DT_FLOOR) \
            or config["mamba_expand"] * hidden \
            != config["mamba_n_heads"] * config["mamba_d_head"] \
            or config["shared_intermediate_size"] \
            != config["intermediate_size"]:
        raise ValueError(
            f"granite_lm builds Granite-4.0-H's dense layers as published "
            f"(a Mamba-2 inner width of mamba_expand x hidden_size, one MLP "
            f"of intermediate_size, models/ssm.py's seeding of the step), "
            f"not {wrong or config}")
    kinds = layer_kinds(config)
    ssm = Mamba2Config(config["mamba_n_heads"], config["mamba_d_head"],
                       config["mamba_n_groups"], config["mamba_d_state"],
                       config["mamba_d_conv"], config["scan_chunk"])
    # A program without the tied head and the three multipliers (the parent
    # of the PR that added them) fails here, at once: no such field.
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=hidden, n_heads=heads,
        d_ff=config["intermediate_size"],
        dtype=dtype_of(config["compute_dtype"]),
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], layers=kinds, ssm=ssm,
        n_kv_heads=config["num_key_value_heads"], head_dim=hidden // heads,
        rope=False, recompute=recomputed(config),
        embed_scale=float(config["embedding_multiplier"]), tie_head=True,
        residual_scale=float(config["residual_multiplier"]),
        logits_divisor=float(config["logits_scaling"]),
        attn_scale=float(config["attention_multiplier"])), kinds


def reference_config_of(config: dict, kinds: tuple) -> dict:
    return dict(layers=kinds, ssm_head_dim=config["mamba_d_head"],
                ssm_state=config["mamba_d_state"],
                norm_eps=config["rms_norm_eps"],
                embedding_multiplier=float(config["embedding_multiplier"]),
                residual_multiplier=float(config["residual_multiplier"]),
                attention_multiplier=float(config["attention_multiplier"]),
                logits_scaling=float(config["logits_scaling"]))


def seeded_parameters(model, config: dict, key):
    """The model's parameters from `key`, the tied table's rows at
    `embedding_std` an element (flax draws them at 1 / sqrt(hidden)): see
    `assumed` in the configuration."""
    params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
    table = params["embed"]["embedding"] * (
        config["embedding_std"] * config["hidden_size"] ** 0.5)
    return {**params, "embed": {"embedding": table}}


def group_of(name: str, kinds: tuple) -> str:
    """The group of `GROUPS` a top-level parameter belongs to."""
    if name.startswith("layer_"):
        return kinds[int(name[len("layer_"):])]
    return "embedding"


def against_reference(reference_config, params, batch, grads_s, **more):
    """(the reference's loss, {group: ||g_s||, ||g_r||, ||g_s - g_r||} for
    each of `GROUPS`), `grads_s` the gradients to compare with.  `more`: the
    reference's other keywords (a control's `operand_dtype`, `decay_dtype`,
    `state_dtype`)."""
    kinds = reference_config["layers"]

    def total(sums):
        met = {name: jax.tree.map(
            lambda p, g, name=name: _met(p, g, sums[group_of(name, kinds)]),
            params[name], grads_s[name]) for name in params}
        return reference.loss(met, batch, **reference_config, **more)

    loss_r, sums = jax.value_and_grad(total)(
        {group: jnp.zeros(3) for group in GROUPS})
    return loss_r, jax.tree.map(jnp.sqrt, sums)


def compare_rows(loss_s, against) -> list:
    """The comparison's rows from the system's loss and what
    `against_reference` returned."""
    loss_r, norms = against
    loss_s, loss_r = float(loss_s), float(loss_r)
    norms = {group: [float(x) for x in norms[group]] for group in GROUPS}
    all_s, all_r = (sum(norms[g][i] ** 2 for g in GROUPS) ** 0.5
                    for i in (0, 1))
    return [
        {"name": "loss_rel_error", "limit": reference.LOSS_RTOL,
         "value": abs(loss_s - loss_r) / abs(loss_r),
         "system": loss_s, "reference": loss_r},
        {"name": "grad_norm_rel_error", "limit": reference.GRAD_NORM_RTOL,
         "value": abs(all_s / all_r - 1.0),
         "system": all_s, "reference": all_r}] + [
        {"name": f"{group}_grad_rel_l2_error",
         "limit": reference.GRAD_RTOL[group],
         "value": norms[group][2] / norms[group][1],
         "system": norms[group][0], "reference": norms[group][1]}
        for group in GROUPS]


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltGranite:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import next_token_loss, record_ssm_carry
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    model, kinds = model_of(config)
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    head_dim = hidden // heads
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"granite_lm builds AdamW, not "
                         f"{config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])
    batch_spec = (P(AXIS), P(AXIS))

    def loss_fn(params, batch):
        inputs, targets = batch
        return next_token_loss(model.apply({"params": params}, inputs),
                               targets)

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=batch_spec)

    def init_state():
        def init(key):
            params = seeded_parameters(model, config, key)
            return params, tx.init(params)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

    @jax.jit
    def sown(params, batch):
        return model.apply({"params": params}, batch[0],
                           mutable=["intermediates"])[1]["intermediates"]

    def ssm_carry(params, batch):         # the traced run's counter probe
        seen = record_ssm_carry(sown(params, batch))
        return {"carried": seen["chunks_carried"], "chunks": seen["chunks"]}

    reference_config = reference_config_of(config, kinds)

    def flash_calls_off_plan(state, pool):
        """The step's own lowered text against `ops/attention.py`'s plan at
        this shape: every Pallas call by its name, one of each an attention
        layer."""
        text = step.lower(state[0], state[1], pool[0]).as_text()
        found = {name: text.count(f'kernel_name = "{name}"')
                 for name in planned}
        return {"name": "flash_calls_off_plan", "limit": 0.0,
                "value": float(sum(abs(found[name] - planned[name])
                                   for name in planned)),
                "found": found, "planned": planned}

    def reference_checks(state, pool):
        params = compare.first_device_copy(state[0])
        inputs, targets = compare.first_device_copy(pool[0])
        n = traffic["reference_check"]["grad_batch"]
        batch = (inputs[:n], targets[:n])
        loss_s, grads_s = compare.system_on_one_device(
            loss_fn, batch_spec, False, devices[0], AXIS)(params, batch)
        rows = compare_rows(loss_s, compare.reference_jit(functools.partial(
            against_reference, reference_config))(params, batch, grads_s))
        del grads_s
        if devices[0].platform == "tpu":     # interpreted elsewhere: no call
            rows.append(flash_calls_off_plan(state, pool))
        return rows

    count = {kind: kinds.count(kind) for kind in set(kinds)}
    ssm_layers, attention_layers = (count.get("ssm", 0),
                                    count.get("attention", 0))
    mode = _bwd_plan(seq, head_dim, 1024, 1024, per_chip * heads)[0]
    planned = dict.fromkeys(FLASH_CALLS[mode], attention_layers)
    no_more, at_least_one = collectives_expected(devices)
    itemsize = jnp.dtype(dtype).itemsize
    ssm = {"heads": config["mamba_n_heads"],
           "head_dim": config["mamba_d_head"],
           "groups": config["mamba_n_groups"],
           "state": config["mamba_d_state"],
           "chunk": min(config["scan_chunk"], seq)}
    shape = {"hidden": hidden, "vocab": vocab, "ssm_layers": ssm_layers,
             "attention_layers": attention_layers,
             "mlp_layers": count["gated_mlp"],
             "intermediate": config["intermediate_size"],
             "ssm": dict(ssm, conv=config["mamba_d_conv"]),
             "attention": {"heads": heads, "kv_heads": kv_heads,
                           "head_dim": head_dim}}
    ops = ops_count_granite.granite_lm_train_ops_per_token(shape, seq)
    attended = heads * head_dim               # the flash kernels' width
    return BuiltGranite(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={
            # Nemotron's kernels (head 64, causal, the repeat the program's).
            "flash": {
                "ops": ops_count.flash_kernel_ops_per_token(
                    seq, attended, attention_layers),
                "bytes": ops_count.flash_kernel_bytes_per_token(
                    attended, attention_layers, itemsize)},
            "ssm_scan": dict(ssm, layers=ssm_layers, itemsize=itemsize)},
        # No collective on one chip and no loop: the scan is products over
        # chunks; which flash calls is `flash_calls_off_plan`'s row, by name.
        program_exactly={"while": 0, **no_more},
        program_at_least_one=["tpu_custom_call"] + at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "layers": list(kinds),
               "recompute_layers": config["recompute_layers"],
               "parameters_counted": ops_count_granite.parameters(shape)},
        ssm_carry=ssm_carry)
