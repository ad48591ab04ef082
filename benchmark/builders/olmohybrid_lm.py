"""Olmo-Hybrid-7B trained through the program's normal path:
`models.TransformerLM(layers=..., post_norm="only")` — a per-layer pattern of
Gated DeltaNet mixers whose keys are 96 wide and whose values 192, with a step
`beta = 2 sigmoid(b)` (`gated_delta`: `DeltaConfig(value_head_dim=,
beta_scale=)`), full attention without rotary whose q and k are RMS-normed
over the whole projection (`attention`: `qk_norm`, `rope=False`), and a dense
gated MLP behind every mixer (`gated_mlp`), every entry under Olmo's reordered
norm, `x + RMSNorm(mixer(x))` — `models.next_token_loss`,
`hvd.jax.build_train_step` on `data_parallel_mesh(devices)`, AdamW.

A published layer is two pattern entries, its mixer and then its MLP; layer
`i` is what `layer_types[i]` says.  The configuration holds one chip's share
of each layer (`tensor_shard`: the mixers' heads; a sliced `vocab_size`; the
MLP and every norm whole).  On one chip the q/k norm's statistic is over the
heads held (`TransformerLM(head_shard_axis=None)`): the layer without its
exchange, in the program and in the reference alike.

The comparison with the reference (`against_reference`, `compare_rows`: module
functions, so that a control can put another program on either side) compiles
two programs: the system's loss and gradients on a one-device mesh of the
step's axis name, and the reference's, each parameter's reference gradient
reduced against the system's where the backward pass makes it
(`trinity_lm._met`), a group of parameters a layer kind, so that the two whole
gradients never stand side by side.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_olmohybrid
from benchmark.builders import Built, collectives_expected, dtype_of
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, olmohybrid_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "olmo_hybrid", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
KINDS = {"linear_attention": "gated_delta", "full_attention": "attention"}
# The groups of parameters whose gradients are compared each on its own (a
# layer's output norm with its mixer, `final_norm` with the head): a wrong
# recurrence hides inside a right total, the MLPs being three quarters of it.
GROUPS = ("gated_delta", "attention", "gated_mlp", "head", "embedding")


@dataclasses.dataclass
class BuiltOlmoHybrid(Built):
    # (params, (inputs, targets)) -> {"over_one": [...], "steps": [...]}, a
    # Gated DeltaNet layer each, of one forward pass on one device
    # (layer_metrics/gdn_beta_over_one_pct.py).
    delta_steps: Optional[Callable] = None


def layer_kinds(config: dict) -> tuple:
    """The pattern: each published layer's mixer, then its MLP."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - set(KINDS):
        raise ValueError(f"layer_types names one of {tuple(KINDS)} for each "
                         f"of num_hidden_layers, not {types}")
    return tuple(kind for published in types
                 for kind in (KINDS[published], "gated_mlp"))


def recomputed(config: dict):
    """`recompute_layers` as `TransformerLM(recompute=)` takes it: a bool, or
    the kinds whose layers recompute."""
    chosen = config["recompute_layers"]
    return tuple(chosen) if isinstance(chosen, list) else bool(chosen)


def model_of(config: dict):
    """(the model, its layer kinds) of a configuration."""
    from horovod_tpu.models import DeltaConfig, TransformerLM

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    heads = config["num_attention_heads"]
    if wrong or config["num_key_value_heads"] != heads \
            or config["hidden_size"] % heads \
            or config["linear_num_key_heads"] \
            != config["linear_num_value_heads"]:
        raise ValueError(
            f"olmohybrid_lm builds Olmo-Hybrid's layers as published (as "
            f"many key/value heads as query heads, as many linear value "
            f"heads as key heads), not {wrong or config}")
    kinds = layer_kinds(config)
    # A program without the two widths, the scaled step or the output norm
    # alone (the parent of the PR that added them) fails here, at once.
    delta = DeltaConfig(
        config["linear_num_key_heads"], config["linear_key_head_dim"],
        config["linear_conv_kernel_dim"], config["chunk_size"],
        value_head_dim=config["linear_value_head_dim"], beta_scale=2.0)
    return TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=heads, d_ff=config["intermediate_size"],
        dtype=dtype_of(config["compute_dtype"]),
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], layers=kinds, delta=delta,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // heads, qk_norm=True, rope=False,
        head_shard=tuple(config["tensor_shard"]), post_norm="only",
        recompute=recomputed(config)), kinds


def reference_config_of(config: dict, kinds: tuple) -> dict:
    return dict(layers=kinds, key_dim=config["linear_key_head_dim"],
                beta_scale=2.0, norm_eps=config["rms_norm_eps"])


def group_of(name: str, kinds: tuple) -> str:
    """The group of `GROUPS` a top-level parameter belongs to."""
    if name.startswith("layer_"):
        return kinds[int(name[len("layer_"):])]
    return "embedding" if name == "embed" else "head"


def against_reference(reference_config, params, batch, grads_s, **more):
    """(the reference's loss, {group: ||g_s||, ||g_r||, ||g_s - g_r||} for
    each of `GROUPS`), `grads_s` the gradients to compare with.  `more`: the
    reference's other keywords (a control's `operand_dtype`,
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


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltOlmoHybrid:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import next_token_loss, record_delta_steps
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.ops.delta_rule import lowered_plan
    from horovod_tpu.parallel import data_parallel_mesh

    model, kinds = model_of(config)
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    shard = tuple(config["tensor_shard"])
    heads = config["num_attention_heads"] // shard[1]
    head_dim = hidden // config["num_attention_heads"]
    linear_heads = config["linear_num_key_heads"] // shard[1]
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"olmohybrid_lm builds AdamW, not "
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
            params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
            # Embedding rows at `embedding_std` an element (flax draws them
            # at 1 / sqrt(hidden)): see `assumed` in the configuration.
            table = params["embed"]["embedding"] * (
                config["embedding_std"] * hidden ** 0.5)
            params = {**params, "embed": {"embedding": table}}
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

    def delta_steps(params, batch):       # the traced run's counter probe
        seen = record_delta_steps(sown(params, batch))
        return {"over_one": seen["beta_over_one"],
                "steps": seen["beta_steps"]}

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
    gdn_layers, attention_layers = (count.get("gated_delta", 0),
                                    count.get("attention", 0))
    mode = _bwd_plan(seq, head_dim, 1024, 1024, per_chip * heads)[0]
    planned = dict.fromkeys(FLASH_CALLS[mode], attention_layers)
    chunk = min(config["chunk_size"], seq)
    no_more, at_least_one = collectives_expected(devices)
    itemsize = jnp.dtype(dtype).itemsize
    gdn = {"heads": linear_heads, "d_k": config["linear_key_head_dim"],
           "d_v": config["linear_value_head_dim"], "chunk": chunk}
    shape = {"hidden": hidden, "vocab": vocab, "gdn_layers": gdn_layers,
             "attention_layers": attention_layers,
             "mlp_layers": count["gated_mlp"],
             "intermediate": config["intermediate_size"], "gdn": gdn,
             "attention": {"heads": heads, "head_dim": head_dim}}
    ops = ops_count_olmohybrid.olmohybrid_lm_train_ops_per_token(shape, seq)
    return BuiltOlmoHybrid(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={
            # OLMoE's kernels (head 128, causal) at the heads held.
            "flash": ops_count_olmohybrid.flash_kernel(
                seq, heads, head_dim, attention_layers, itemsize),
            "gdn_kdv_scan": dict(gdn, layers=gdn_layers, itemsize=itemsize)},
        # No collective on one chip; the loops are the delta rule's own plan
        # at this length, a Gated DeltaNet layer each; which flash calls is
        # `flash_calls_off_plan`'s row, by name.
        program_exactly={"while": gdn_layers * lowered_plan(seq, chunk)[
            "while"], **no_more},
        program_at_least_one=["tpu_custom_call"] + at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "layers": list(kinds),
               "tensor_shard": list(shard),
               "recompute_layers": config["recompute_layers"]},
        delta_steps=delta_steps)
