"""JoyAI-LLM-Flash trained through the program's normal path:
`models.TransformerLM(layers=..., mtp=...)` — a per-layer pattern of latent
attention with a query latent and no output gate (flash kernels at two head
widths, every head whole), one dense gated MLP and sigmoid-routed sparse
experts with a shared one, then a multi-token-prediction module (one more
published layer behind a projection of [the next token's embedding | the main
model's state], through the same table and the same head) —
`models.mtp_next_token_loss`, `hvd.jax.build_train_step` on
`data_parallel_mesh(devices)`, AdamW: the Ling builder's step with this
pattern's configuration.

A published layer is two pattern entries, latent attention and then its MLP
or experts; the configuration names the published layers it keeps
(`kept_layers`) and each one's kind follows from `first_k_dense_replace` and
`moe_layer_freq`.  It holds one chip's share of each layer (`expert_shard`:
the routed experts, the main layers' and the module's alike; a sliced
`vocab_size`; attention whole) and a bound on the rows of the sorted expert
buffer (`row_bound`).  What the step trains is `{"params": the model's,
"buffers": the routers' balance bias}`, the bias set once in set-up as
benchmark/builders/hybrid_lm.py sets Nemotron's (the module's router too), and
a row the buffer could not hold makes the step's loss NaN, as there.

The loss is `L_main + mtp_loss_weight * L_mtp`, the two next-token losses at
two shifts, of the two sets of logits by `mtp_next_token_loss`; the counter
pass takes them under `targets=`, where the model sows them.

The comparison with the reference compiles the Ling builder's two programs
(the system's losses and gradients with what its expert layers counted and
chose; the reference's with what it chose), each parameter's reference
gradient reduced against the system's where the backward pass makes it
(benchmark/builders/trinity_lm.py's `_met`), the table and the head in a
group of their own.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_joyai, ops_count_ling
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.hybrid_lm import balanced_selection_bias
from benchmark.builders.moe_lm import BuiltMoE
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, joyai_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "joyai_llm_flash", "hidden_act": "silu",
    "attention_bias": False, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "n_shared_experts": 1, "moe_layer_freq": 1,
    "rope_interleave": True, "rope_scaling": None,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 1}
MTP_KINDS = ("latent_attention", "experts")      # a whole published layer
# The two parameters that take the sum of two uses' gradients, and the rest.
GROUPS = ("shared", "layers")
SHARED = ("embed", "lm_head_kernel")
COUNTERS = ("rows_per_local_expert", "rows_over_bound", "chosen_experts")


@dataclasses.dataclass
class BuiltJoyAI(BuiltMoE):
    # (state, (inputs, targets)) -> {"main": L_main, "modules": [L_mtp]} of
    # one forward pass, what the model sows as `mtp_losses`
    # (layer_metrics/mtp_loss_over_main.py).
    mtp_losses: Optional[Callable] = None


def layer_kinds(config: dict) -> tuple:
    """The pattern: each kept published layer's latent attention, then its
    dense MLP or its experts."""
    if len(config["kept_layers"]) != config["num_hidden_layers"]:
        raise ValueError("kept_layers names a published layer for each of "
                         "num_hidden_layers")
    kinds = []
    for index in config["kept_layers"]:
        kinds += ["latent_attention",
                  "gated_mlp" if index < config["first_k_dense_replace"]
                  else "experts"]
    return tuple(kinds)


def model_of(config: dict):
    """(the model, its pattern's kinds, its expert entries' names in order,
    the module's last)."""
    from horovod_tpu.models import LatentConfig, MoEConfig, TransformerLM

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"joyai_lm builds JoyAI-LLM-Flash's layers as "
                         f"published, not {wrong}")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is qk_nope_head_dim + "
                         "qk_rope_head_dim")
    kinds = layer_kinds(config)
    # A program without the query latent and the module (the parent of the PR
    # that added them) fails here, at once: no such field.
    latent = LatentConfig(
        config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"],
        float(config["rope_theta"]), q_rank=config["q_lora_rank"],
        gate=False)
    moe = MoEConfig(
        config["n_routed_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], tuple(config["expert_shard"]),
        config["row_bound"], scoring="sigmoid", renormalize=True,
        weight_scale=float(config["routed_scaling_factor"]),
        shared_width=config["n_shared_experts"]
        * config["moe_intermediate_size"])
    model = TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        dtype=dtype_of(config["compute_dtype"]),
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], moe=moe, layers=kinds,
        latent=latent, recompute=bool(config["recompute_layers"]),
        mtp=(config["num_nextn_predict_layers"], MTP_KINDS))
    expert_layers = [f"layer_{i}" for i, kind in enumerate(kinds)
                     if kind == "experts"] + [
        f"mtp_0_layer_{j}" for j, kind in enumerate(MTP_KINDS)
        if kind == "experts"]
    return model, kinds, expert_layers


def reference_config_of(config: dict, kinds: tuple) -> dict:
    return dict(
        layers=kinds, mtp_layers=MTP_KINDS,
        mtp_weight=float(config["mtp_loss_weight"]),
        nope_dim=config["qk_nope_head_dim"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        num_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_shard=tuple(config["expert_shard"]),
        weight_scale=float(config["routed_scaling_factor"]))


def seeded_state(model, config: dict, expert_layers, batch_shape, key):
    """`{"params", "buffers"}` from `key`: the embedding rows at
    `embedding_std` an element (flax draws them at 1 / sqrt(hidden); see
    `assumed` in the configuration), the routers' balance bias as
    `balanced_selection_bias` sets it on those weights."""
    params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
    table = params["embed"]["embedding"] * (
        config["embedding_std"] * config["hidden_size"] ** 0.5)
    params = {**params, "embed": {"embedding": table}}
    return {"params": params,
            "buffers": balanced_selection_bias(
                model, params, jax.random.fold_in(key, 0xB1A5), expert_layers,
                batch_shape, config["vocab_size"])}


def selection_bias(state, expert_layers):
    return jnp.stack([state["buffers"][layer]["mixer"]["selection_bias"]
                      for layer in expert_layers])


def against_reference(reference_config, expert_layers, state, batch, grads_s,
                      chose, **more):
    """(the reference's (L, L_main, L_mtp); {group: ||g_s||, ||g_r||, ||g_s -
    g_r||} over the parameters of each of `GROUPS`; the share of the
    (token, choice) pairs `chose` whose expert the reference did not choose
    for that token), `grads_s` the gradients to compare with.  `more`: the
    reference's other keywords (a control's `operand_dtype`, `score_dtype`,
    `latent_dtype`).  Each parameter's reference gradient is reduced against
    `grads_s`'s where the backward pass makes it (`_met`), so the two whole
    gradients never stand side by side: they would be 5.4 GB beside 8.2 of
    weights and AdamW state."""
    def total(sums):
        params = {name: jax.tree.map(
            lambda p, g, name=name: _met(
                p, g, sums["shared" if name in SHARED else "layers"]),
            state["params"][name], grads_s["params"][name])
            for name in state["params"]}
        return reference.loss_and_parts(
            params, batch,
            selection_bias=selection_bias(state, expert_layers),
            **reference_config, **more)

    (loss_r, (terms_r, want)), sums = jax.value_and_grad(
        total, has_aux=True)({group: jnp.zeros(3) for group in GROUPS})
    same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
    return (jnp.stack([loss_r, *terms_r]), jax.tree.map(jnp.sqrt, sums),
            1.0 - same.mean())


def compare_rows(losses_s, against) -> list:
    """The comparison's rows from the system's (L, L_main, L_mtp) and what
    `against_reference` returned."""
    losses_r, norms, mismatch = against
    losses_s, losses_r = ([float(x) for x in losses]
                          for losses in (losses_s, losses_r))
    norms = {group: [float(x) for x in norms[group]] for group in GROUPS}
    all_s, all_r, all_diff = (sum(norms[g][i] ** 2 for g in GROUPS) ** 0.5
                              for i in range(3))
    shared_s, shared_r, shared_diff = norms["shared"]
    return [
        # The loss as the step's loss function returns it, L_main + lambda
        # L_mtp, and its two terms apart.
        *({"name": name + "loss_rel_error", "limit": reference.LOSS_RTOL,
           "value": abs(loss_s - loss_r) / abs(loss_r), "system": loss_s,
           "reference": loss_r} for name, loss_s, loss_r in zip(
               ("", "main_", "mtp_"), losses_s, losses_r)),
        {"name": "grad_norm_rel_error", "limit": reference.GRAD_NORM_RTOL,
         "value": abs(all_s / all_r - 1.0), "system": all_s,
         "reference": all_r},
        {"name": "grad_rel_l2_error", "limit": reference.GRAD_RTOL,
         "value": all_diff / all_r},
        # The table's and the head's gradients, each the sum of two uses'.
        {"name": "shared_grad_rel_l2_error",
         "limit": reference.SHARED_GRAD_RTOL,
         "value": shared_diff / shared_r, "system": shared_s,
         "reference": shared_r},
        # The pairs of the compared sequence whose expert the float32
        # reference did not choose for that token: a near-tie that bfloat16
        # flips.
        {"name": "routing_mismatch_share",
         "limit": reference.ROUTING_MISMATCH_MAX, "value": float(mismatch)}]


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltJoyAI:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import mtp_next_token_loss, record_mtp_losses
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    model, kinds, expert_layers = model_of(config)
    moe, latent = model.moe, model.latent
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads = config["num_attention_heads"]
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    weight = float(config["mtp_loss_weight"])
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"joyai_lm builds AdamW, not {config['optimizer']}")
    tx = optax.multi_transform(
        {"params": optax.adamw(config["optimizer"]["learning_rate"]),
         "buffers": optax.set_to_zero()},
        {"params": "params", "buffers": "buffers"})

    def counters_of(sown):
        return {name: jnp.stack([sown[layer]["mixer"][name][0]
                                 for layer in expert_layers])
                for name in COUNTERS}

    def loss_and_seen(state, batch):
        """(L_main + weight * L_mtp, NaN where a row fell outside a buffer;
        what the expert entries counted and chose, and `terms`, (L_main,
        L_mtp))."""
        inputs = batch[0]             # the targets are the inputs' own shift
        logits, wrote = model.apply(state, inputs, mutable=["intermediates"])
        loss, terms = mtp_next_token_loss(logits, inputs, weight,
                                          with_terms=True)
        seen = dict(counters_of(wrote["intermediates"]),
                    terms=jnp.stack(terms))
        return jnp.where(seen["rows_over_bound"].sum() > 0, jnp.nan,
                         loss), seen

    def loss_fn(state, batch):
        return loss_and_seen(state, batch)[0]

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=(P(AXIS), P(AXIS)))

    def init_state():
        def init(key):
            state = seeded_state(model, config, expert_layers,
                                 (per_chip, seq), key)
            return state, tx.init(state)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

    @jax.jit
    def expert_rows(state, batch):
        """The traced run's two probes' one forward pass, under `targets=`
        (where the model sows its losses): the expert entries' counters, and
        `mtp_losses` as the model sowed them."""
        sown = model.apply(state, batch[0], targets=batch[1],
                           mutable=["intermediates"])[1]["intermediates"]
        return dict(counters_of(sown), mtp_losses=sown["mtp_losses"])

    def mtp_losses(state, batch):
        return record_mtp_losses(expert_rows(state, batch))

    def system_on_one_device():
        """(state, batch) -> (loss, gradients, the expert entries' counters
        and the loss's terms), the step's own loss on a one-device mesh of
        the step's axis name: compare.system_on_one_device with the counters
        kept."""
        def local(state, batch):
            (loss, seen), grads = jax.value_and_grad(
                loss_and_seen, has_aux=True)(state, batch)
            return lax.pmean(loss, AXIS), grads, {
                "chosen_experts": seen["chosen_experts"],
                "terms": lax.pmean(seen["terms"], AXIS),
                **{name: lax.psum(seen[name], AXIS)
                   for name in COUNTERS[:2]}}

        spec = (P(AXIS), P(AXIS))
        return jax.jit(jax.shard_map(
            local, mesh=data_parallel_mesh(devices[:1], axis_name=AXIS),
            in_specs=(P(), spec),
            out_specs=(P(), P(), {"chosen_experts": P(None, AXIS),
                                  "terms": P(),
                                  **dict.fromkeys(COUNTERS[:2], P())})))

    reference_config = reference_config_of(config, kinds)

    def flash_calls_off_plan(state, pool):
        """The step's own lowered text against `ops/attention.py`'s plan at
        this shape: every Pallas flash call by its name, one of each a
        latent-attention block, the module's among them."""
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
        loss_s, grads_s, seen = system_on_one_device()(params, batch)
        against = compare.reference_jit(
            lambda *a: against_reference(reference_config, expert_layers,
                                         *a))(
            params, batch, grads_s, seen["chosen_experts"])
        del grads_s
        rows = compare_rows(jnp.stack([loss_s, *seen["terms"]]), against)
        # Rows routed here that a bounded buffer could not hold, in the
        # compared batch; in every other batch of the pool one makes a step's
        # loss NaN, and the window counts that step as failed.
        rows.append({"name": "rows_over_bound", "limit": 0.0,
                     "value": float(seen["rows_over_bound"].sum()),
                     "largest_layer_rows": int(
                         seen["rows_per_local_expert"].sum(-1).max()),
                     "bound_rows": bound_rows})
        if devices[0].platform == "tpu":     # interpreted elsewhere: no call
            rows.append(flash_calls_off_plan(state, pool))
        return rows

    count = {kind: kinds.count(kind) for kind in set(kinds)}
    modules = config["num_nextn_predict_layers"]
    blocks = count["latent_attention"] + modules
    d_qk = latent.nope_dim + latent.rope_dim
    mode = _bwd_plan(seq, d_qk, 1024, 1024, per_chip * heads,
                     latent.v_dim)[0]
    planned = dict.fromkeys(FLASH_CALLS[mode], blocks)
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    itemsize = jnp.dtype(dtype).itemsize
    local_experts = moe.num_experts // shard[1]
    shape = {
        "hidden": hidden, "vocab": vocab, "mtp_modules": modules,
        "latent_attention_layers": count["latent_attention"],
        "mlp_layers": count.get("gated_mlp", 0),
        "expert_layers": count.get("experts", 0),
        "mlp_width": config["intermediate_size"],
        "latent_attention": dict(latent._asdict(), heads=heads),
        "experts": {"num_experts": moe.num_experts,
                    "expert_width": moe.expert_width,
                    "shared": moe.shared_width,
                    "local_experts": local_experts}}
    ops = ops_count_joyai.joyai_lm_train_ops_per_token(
        shape, seq, moe.experts_per_token / shard[1],
        bound_rows / tokens_per_chip)
    return BuiltJoyAI(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={
            # Ling's kernels at every head, over the main layers' blocks and
            # the module's.
            "mla_flash": ops_count_ling.flash_two_width_kernel(
                seq, heads, d_qk, latent.v_dim, blocks, itemsize),
            "moe_experts": {"hidden": hidden,
                            "expert_width": moe.expert_width,
                            "local_experts": local_experts,
                            "itemsize": itemsize}},
        # No collective on one chip, kernels in the program; which flash
        # calls is `flash_calls_off_plan`'s row, by name.
        program_exactly=no_more,
        program_at_least_one=["tpu_custom_call"] + at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "layers": list(kinds), "mtp_layers": list(MTP_KINDS),
               "expert_shard": list(shard),
               "recompute_layers": bool(config["recompute_layers"]),
               "parameters_counted": ops_count_joyai.parameters(shape)},
        expert_rows=expert_rows, mtp_losses=mtp_losses)
