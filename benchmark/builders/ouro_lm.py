"""Ouro-2.6B trained through the program's normal path:
`models.TransformerLM(layers=..., loops=T, exit_gate=True)` — a pattern of
causal full attention (rotated, 16 heads of 128) and a dense gated MLP, every
mixer's output normed again before its residual (`post_norm`), the whole
pattern run `total_ut_steps` times over ONE set of weights inside a rolled
loop, `final_norm` after every pass, an exit gate and the head on every pass's
state, every pattern entry and every pass's head computing its forward pass
again in the backward pass (`recompute_layers`) — `models.looped_exit_loss`
(the passes' per-token cross-entropies weighed by the exit distribution, less
`beta` times its entropy), `hvd.jax.build_train_step` on
`data_parallel_mesh(devices)`, AdamW.

The configuration holds one pipeline stage's layers (`kept_layers`) and every
width, the whole vocabulary included, as published.

The comparison with the reference (benchmark/reference/ouro_lm.py) compiles two
programs: the system's loss and gradients with each pass's mean cross-entropy
and mean exit probability, on a one-device mesh of the step's axis name; and
the reference's, each parameter's gradient reduced against the system's where
the backward pass makes it (`trinity_lm._met`, here a group of parameters at a
time), so that the two whole gradients never stand side by side.  What the
compiled step holds of the flash kernels is read off its lowered text: each
kernel once a LAYER, not once a layer and pass — the loop is rolled.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count, ops_count_ouro
from benchmark.builders import Built, collectives_expected, dtype_of
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, ouro_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "ouro", "hidden_act": "silu", "rope_scaling": None,
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False}
# The groups of parameters whose gradients are compared each on its own: a
# wrong gate hides inside a right total.
GROUPS = ("layers", "gate", "head", "embedding")


@dataclasses.dataclass
class BuiltOuro(Built):
    # (params, (inputs, targets)) -> models.record_exit_distribution's record
    # of one forward pass, on one device
    # (layer_metrics/exit_expected_passes.py).
    exit_distribution: Optional[Callable] = None


def group_of(name: str) -> str:
    """The group of `GROUPS` a top-level parameter belongs to (`final_norm`
    runs after every pass: with the layers)."""
    if name.startswith("exit_gate"):
        return "gate"
    return {"lm_head_kernel": "head", "embed": "embedding"}.get(name, "layers")


def against_reference(reference_config, params, batch, grads_s):
    """(the reference's loss, each pass's mean cross-entropy and mean exit
    probability; {group: ||g_s||, ||g_r||, ||g_s - g_r||} for each of
    `GROUPS`), `grads_s` the gradients to compare with.  Each parameter's
    reference gradient is reduced against `grads_s`' where the backward pass
    makes it (`trinity_lm._met`), so the two whole gradients never stand side
    by side: 2.4 GB each beside 9.8 of training state."""
    def total(sums):
        met = {name: jax.tree.map(
            lambda p, g, name=name: _met(p, g, sums[group_of(name)]),
            params[name], grads_s[name]) for name in params}
        loss, pass_ce, exit_p = reference.loss_terms(met, batch,
                                                     **reference_config)
        return loss, (pass_ce, exit_p)

    (loss_r, seen), sums = jax.value_and_grad(total, has_aux=True)(
        {group: jnp.zeros(3) for group in GROUPS})
    return (loss_r,) + seen, jax.tree.map(jnp.sqrt, sums)


def compare_rows(system, against) -> list:
    """The comparison's rows from `system` = (loss, pass_ce, exit_p) and what
    `against_reference` returned."""
    (loss_r, pass_ce_r, exit_p_r), norms = against
    loss_s, loss_r = float(system[0]), float(loss_r)
    pass_ce_s, exit_p_s, pass_ce_r, exit_p_r = (
        [float(x) for x in values]
        for values in (system[1], system[2], pass_ce_r, exit_p_r))
    norms = {group: [float(x) for x in norms[group]] for group in GROUPS}
    all_s, all_r = (sum(norms[g][i] ** 2 for g in GROUPS) ** 0.5
                    for i in (0, 1))
    return [
        {"name": "loss_rel_error", "limit": reference.LOSS_RTOL,
         "value": abs(loss_s - loss_r) / abs(loss_r),
         "system": loss_s, "reference": loss_r},
        # A wrong gate, a skipped pass or passes out of order hide inside a
        # right total: each pass's cross-entropy and exit probability.
        {"name": "pass_ce_max_rel_error", "limit": reference.PASS_CE_RTOL,
         "value": max(abs(s - r) / abs(r)
                      for s, r in zip(pass_ce_s, pass_ce_r)),
         "system": pass_ce_s, "reference": pass_ce_r},
        {"name": "exit_p_max_abs_error", "limit": reference.EXIT_P_ATOL,
         "value": max(abs(s - r) for s, r in zip(exit_p_s, exit_p_r)),
         "system": exit_p_s, "reference": exit_p_r},
        {"name": "grad_norm_rel_error", "limit": reference.GRAD_NORM_RTOL,
         "value": abs(all_s / all_r - 1.0),
         "system": all_s, "reference": all_r}] + [
        {"name": f"{group}_grad_rel_l2_error",
         "limit": reference.GATE_GRAD_RTOL if group == "gate"
         else reference.GRAD_RTOL,
         "value": norms[group][2] / norms[group][1],
         "system": norms[group][0], "reference": norms[group][1]}
        for group in GROUPS]


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltOuro:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (TransformerLM, log_exit_distribution,
                                    looped_exit_loss,
                                    record_exit_distribution)
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    depth = config["num_hidden_layers"]
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    if wrong or set(config["layer_types"]) != {"full_attention"} \
            or not len(config["layer_types"]) == len(
                config["kept_layers"]) == depth \
            or config["num_key_value_heads"] != heads \
            or heads * head_dim != hidden:
        raise ValueError(f"ouro_lm builds Ouro's layers as published (full "
                         f"attention over {hidden // head_dim} heads of "
                         f"{head_dim}, then the gated MLP, a layer), not "
                         f"{wrong or config['layer_types']}")
    passes, beta = config["total_ut_steps"], config["exit_entropy_beta"]
    recompute = bool(config["recompute_layers"])
    kinds = ("attention", "gated_mlp") * depth
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    theta = float(config["rope_theta"])
    # A program without the looped model (the parent of the PR that added
    # it) has failed by now, at the import above: at once, as it must.
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_heads=heads,
        d_ff=config["intermediate_size"], dtype=dtype,
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], layers=kinds, post_norm=True,
        rope_theta=theta, recompute=recompute, loops=passes, exit_gate=True)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"ouro_lm builds AdamW, not {config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])
    batch_spec = (P(AXIS), P(AXIS))

    def loss_and_passes(params, batch):
        """(loss, {each pass's mean cross-entropy, each pass's mean exit
        probability}), both (passes,)."""
        inputs, targets = batch
        ce, gate_logits = model.apply({"params": params}, inputs,
                                      targets=targets)
        p = jnp.exp(log_exit_distribution(gate_logits))
        return looped_exit_loss(ce, gate_logits, beta), {
            "pass_ce": ce.mean(axis=(1, 2)), "exit_p": p.mean(axis=(1, 2))}

    def loss_fn(params, batch):
        return loss_and_passes(params, batch)[0]

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=batch_spec)

    def init_state():
        def init(key):
            params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
            return params, tx.init(params)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

    @jax.jit
    def gate_logits_of(params, batch):
        return model.apply({"params": params}, batch[0], targets=batch[1],
                           mutable=["intermediates"])[1]["intermediates"]

    def exit_record(params, batch):       # the traced run's counter probe
        return record_exit_distribution(gate_logits_of(params, batch))

    def system_on_one_device():
        """(params, batch) -> (loss, gradients, the passes' means), the
        step's own loss on a one-device mesh of the step's axis name:
        compare.system_on_one_device with the aux kept."""
        def local(params, batch):
            (loss, seen), grads = jax.value_and_grad(
                loss_and_passes, has_aux=True)(params, batch)
            return lax.pmean(loss, AXIS), grads, jax.tree.map(
                lambda x: lax.pmean(x, AXIS), seen)

        return jax.jit(jax.shard_map(
            local, mesh=data_parallel_mesh(devices[:1], axis_name=AXIS),
            in_specs=(P(), batch_spec), out_specs=P()))

    reference_config = dict(layers=kinds, passes=passes, beta=beta,
                            rope_theta=theta, norm_eps=config["rms_norm_eps"])

    def flash_calls_in_program(state, pool):
        """The step's own lowered text against `ops/attention.py`'s plan at
        this shape: every Pallas call by its name, once a layer — the rolled
        loop holds the layers' bodies once, four unrolled passes would hold
        each `passes` times."""
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
        loss_s, grads_s, seen_s = system_on_one_device()(params, batch)
        rows = compare_rows(
            (loss_s, seen_s["pass_ce"], seen_s["exit_p"]),
            compare.reference_jit(functools.partial(
                against_reference, reference_config))(params, batch, grads_s))
        del grads_s
        if devices[0].platform == "tpu":     # interpreted elsewhere: no call
            rows.append(flash_calls_in_program(state, pool))
        return rows

    mode = _bwd_plan(seq, head_dim, 1024, 1024, per_chip * heads)[0]
    planned = {name: depth for name in FLASH_CALLS[mode]}
    no_more, at_least_one = collectives_expected(devices)
    ops = ops_count_ouro.ouro_lm_train_ops_per_token(
        hidden, config["intermediate_size"], depth, vocab, seq, passes,
        recompute)
    calls = depth * passes
    return BuiltOuro(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        # OLMoE's kernels (head 128, causal) at `depth x passes` calls a
        # step, forward once a call: a recomputing layer keeps the forward
        # kernel's outputs.  `_program.flash_roofline_pct` divides by the time
        # of every `hvd_flash_*` event, the loop's iterations included.
        kernels={"flash": {
            "ops": ops_count.flash_kernel_ops_per_token(seq, hidden, calls),
            "bytes": ops_count.flash_kernel_bytes_per_token(
                hidden, calls, jnp.dtype(dtype).itemsize)}},
        program_exactly=no_more,
        program_at_least_one=["tpu_custom_call", "while"] + at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "flash_calls_planned": planned,
               "passes": passes, "beta": beta,
               "recompute_layers": recompute, "layers": list(kinds)},
        exit_distribution=exit_record)
