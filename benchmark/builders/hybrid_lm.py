"""A hybrid decoder LM (Nemotron-3's layers) trained through the program's
normal path: `models.TransformerLM(layers=...)` — a per-layer pattern of
Mamba-2 mixers, grouped-query attention without rotary (flash kernels) and
latent sparse experts — `models.next_token_loss`, `hvd.jax.build_train_step`
on `data_parallel_mesh(devices)`, AdamW: the dense builder's step with the
pattern's configuration.

The configuration holds one chip's share of each layer (`tensor_shard`: the
mixers' heads; `expert_shard`: the routed experts; a sliced `vocab_size`) and a
bound on the rows of the sorted expert buffer (`row_bound`).  What the step
trains is `{"params": the model's, "buffers": the router's balance bias}`: the
bias is no parameter (the optimizer sets its update to zero, and it takes no
gradient), is set once in set-up (`balanced_selection_bias`) and travels with
the weights as a checkpoint's buffers do.  A row routed to
a local expert that the buffer could not hold would be a token silently short
of an expert: the loss this builder hands the step is NaN whenever the model
counts one, so that such a step is a failed step of the cell, and the
reference check counts them over the whole pool as well.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count, ops_count_hybrid
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.moe_lm import TILE_SCHEDULES_PER_LAYER, BuiltMoE
from benchmark.reference import compare, hybrid_lm as reference

AXIS = "hvd"
KINDS = {"M": "ssm", "*": "attention", "E": "experts"}
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "use_conv_bias": True, "mamba_proj_bias": False,
    "mlp_bias": False, "attention_bias": False, "use_bias": False,
    "tie_word_embeddings": False, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001}


# The balance bias is set in set-up, on the seeded weights, by BALANCE_STEPS
# steps of `bias -= rate * log(load / mean load)` per expert, each on a fresh
# batch of the cell's own traffic, the rate falling from BALANCE_RATE to a
# quarter of it; then it is held (see `assumed.selection_bias` in the
# configuration for why it cannot stay zero).
BALANCE_STEPS = 48
BALANCE_RATE = 0.03


def balanced_selection_bias(model, params, key, expert_layers, batch_shape,
                            vocab):
    """The `buffers` collection of `model` — a `selection_bias` for every
    layer of `expert_layers` — under which the seeded router sends every
    expert its share of uniform random tokens."""
    def as_buffers(bias):
        return {layer: {"mixer": {"selection_bias": bias[i]}}
                for i, layer in enumerate(expert_layers)}

    def step(i, bias):
        tokens = jax.random.randint(jax.random.fold_in(key, i), batch_shape,
                                    0, vocab)
        _, wrote = model.apply({"params": params,
                                "buffers": as_buffers(bias)}, tokens,
                               mutable=["router"])
        chosen = jnp.stack([wrote["router"][layer]["mixer"]["choices"][0]
                            for layer in expert_layers])
        load = chosen / chosen.mean(axis=-1, keepdims=True)
        rate = BALANCE_RATE * (1.0 - 0.75 * i / BALANCE_STEPS)
        return bias - rate * jnp.log(jnp.maximum(load, 0.05))

    experts = params[expert_layers[0]]["mixer"]["router_kernel"].shape[1]
    return as_buffers(jax.lax.fori_loop(
        0, BALANCE_STEPS, step,
        jnp.zeros((len(expert_layers), experts), jnp.float32)))


def _expert_layers(intermediates, name):
    """(expert layers, ...): what each sparse-expert layer sowed as `name`,
    in layer order."""
    layers = sorted(intermediates, key=lambda k: int(k.split("_")[1]))
    return jnp.stack([intermediates[layer]["mixer"][name][0]
                      for layer in layers
                      if name in intermediates[layer].get("mixer", {})])


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltMoE:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (Mamba2Config, MoEConfig, TransformerLM,
                                    next_token_loss)
    from horovod_tpu.models.ssm import DT_FLOOR, DT_RANGE
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    if wrong or (DT_RANGE, DT_FLOOR) != ((0.001, 0.1), 1e-4):
        raise ValueError(f"hybrid_lm builds Nemotron-3's layers as "
                         f"published, not {wrong}")
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    if heads * config["head_dim"] != hidden:
        raise ValueError("Attention takes its head size from hidden / heads")
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError("the pattern has a letter a layer")
    kinds = tuple(KINDS[letter] for letter in pattern)
    tensor, shard = tuple(config["tensor_shard"]), tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    ssm = Mamba2Config(config["mamba_num_heads"], config["mamba_head_dim"],
                       config["n_groups"], config["ssm_state_size"],
                       config["conv_kernel"], config["chunk_size"])
    moe = MoEConfig(
        config["n_routed_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], shard, config["row_bound"],
        scoring="sigmoid", renormalize=True,
        weight_scale=float(config["routed_scaling_factor"]),
        expert_act="relu2", latent_width=config["moe_latent_size"],
        shared_width=config["moe_shared_expert_intermediate_size"])
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_heads=heads, dtype=dtype,
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["norm_eps"], moe=moe, layers=kinds, ssm=ssm,
        n_kv_heads=kv_heads, rope=False, head_shard=tensor)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"hybrid_lm builds AdamW, not {config['optimizer']}")
    tx = optax.multi_transform(
        {"params": optax.adamw(config["optimizer"]["learning_rate"]),
         "buffers": optax.set_to_zero()},
        {"params": "params", "buffers": "buffers"})
    expert_layers = [f"layer_{i}" for i, kind in enumerate(kinds)
                     if kind == "experts"]

    def loss_fn(state, batch):
        inputs, targets = batch
        logits, wrote = model.apply(state, inputs, mutable=["intermediates"])
        over = _expert_layers(wrote["intermediates"], "rows_over_bound").sum()
        return jnp.where(over > 0, jnp.nan, next_token_loss(logits, targets))

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=(P(AXIS), P(AXIS)))

    def init_state():
        def init(key):
            params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
            # Embedding rows at `embedding_std` an element (flax draws them
            # at 1 / sqrt(hidden)): see `assumed` in the configuration.
            table = params["embed"]["embedding"] * (
                config["embedding_std"] * hidden ** 0.5)
            params = {**params, "embed": {"embedding": table}}
            state = {"params": params,
                     "buffers": balanced_selection_bias(
                         model, params, jax.random.fold_in(key, 0xB1A5),
                         expert_layers, (per_chip, seq), vocab)}
            return state, tx.init(state)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

    @jax.jit
    def expert_rows(state, batch):
        _, wrote = model.apply(state, batch[0], mutable=["intermediates"])
        return {name: _expert_layers(wrote["intermediates"], name)
                for name in ("rows_per_local_expert", "rows_over_bound",
                             "chosen_experts")}

    reference_config = dict(
        layers=kinds, ssm_head_dim=ssm.head_dim, ssm_state=ssm.state,
        norm_eps=config["norm_eps"], num_experts=moe.num_experts,
        experts_per_token=moe.experts_per_token, expert_shard=shard,
        weight_scale=moe.weight_scale)

    def selection_bias(state):
        return jnp.stack([state["buffers"][layer]["mixer"]["selection_bias"]
                          for layer in expert_layers])

    def reference_loss(state, batch):
        return reference.loss(state["params"], batch,
                              selection_bias=selection_bias(state),
                              **reference_config)

    def reference_checks(state, pool):
        params = compare.first_device_copy(state[0])
        batches = [compare.first_device_copy(b) for b in pool]
        n = traffic["reference_check"]["grad_batch"]
        batch = (batches[0][0][:n], batches[0][1][:n])
        system = compare.system_on_one_device(
            loss_fn, (P(AXIS), P(AXIS)), False, devices[0], AXIS)
        rows = compare.loss_and_gradients(
            system, reference_loss, params, batch, batch,
            reference.LOSS_RTOL, reference.GRAD_RTOL,
            reference.GRAD_NORM_RTOL)
        # Over every batch the window will cycle through, the rows routed
        # here that the bounded buffer could not hold: none ...
        seen = [expert_rows(params, b) for b in batches]
        per_expert = jnp.stack([s["rows_per_local_expert"] for s in seen])
        rows.append({"name": "rows_over_bound", "limit": 0.0,
                     "value": float(sum(s["rows_over_bound"].sum()
                                        for s in seen)),
                     "largest_layer_rows": int(per_expert.sum(-1).max()),
                     "bound_rows": bound_rows})
        # ... and the pairs of the compared sequences whose expert the
        # float32 reference did not choose for that token: a near-tie that
        # bfloat16 flips.
        chose = seen[0]["chosen_experts"][:, :n * seq]
        want = compare.reference_jit(lambda s, t: reference.chosen_experts(
            s["params"], t, selection_bias=selection_bias(s),
            **reference_config))(params, batch[0])
        same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
        rows.append({"name": "routing_mismatch_share",
                     "limit": reference.ROUTING_MISMATCH_MAX,
                     "value": 1.0 - float(same.mean())})
        return rows

    # What the compiled step must hold: the flash kernels of each attention
    # layer as the backward plan says (combined: 2, split: 3), libtpu's
    # kernels for the six grouped matmuls of an expert layer with its two
    # tile schedules, and no loop: the scan is products over chunks.
    count = {kind: kinds.count(kind) for kind in KINDS.values()}
    local_heads = heads // tensor[1]
    mode = _bwd_plan(seq, config["head_dim"], 1024, 1024,
                     per_chip * local_heads)[0]
    calls = count["attention"] * {"combined": 2, "split": 3}[mode] \
        + count["experts"] * (ops_count_hybrid.GROUPED_MATMULS
                              + TILE_SCHEDULES_PER_LAYER)
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    local_ssm = {"heads": ssm.heads // tensor[1], "head_dim": ssm.head_dim,
                 "groups": ssm.groups // tensor[1], "state": ssm.state,
                 "chunk": min(ssm.chunk, seq)}
    itemsize = jnp.dtype(dtype).itemsize
    shape = {
        "hidden": hidden, "vocab": vocab, "ssm_layers": count["ssm"],
        "attention_layers": count["attention"],
        "expert_layers": count["experts"], "ssm": local_ssm,
        "attention": {"heads": local_heads,
                      "kv_heads": max(1, kv_heads // tensor[1]),
                      "head_dim": config["head_dim"]},
        "experts": {"num_experts": moe.num_experts,
                    "latent": moe.latent_width,
                    "expert_width": moe.expert_width,
                    "shared": moe.shared_width}}
    ops = ops_count_hybrid.hybrid_lm_train_ops_per_token(
        shape, seq, moe.experts_per_token / shard[1],
        bound_rows / tokens_per_chip)
    attended = local_heads * config["head_dim"]   # the flash kernels' width
    return BuiltMoE(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={"flash": {
            "ops": ops_count.flash_kernel_ops_per_token(
                seq, attended, count["attention"]),
            "bytes": ops_count.flash_kernel_bytes_per_token(
                attended, count["attention"], itemsize)},
            "ssm_scan": dict(local_ssm, layers=count["ssm"],
                             itemsize=itemsize),
            "latent_moe_experts": {
                "latent": moe.latent_width,
                "expert_width": moe.expert_width,
                "local_experts": moe.num_experts // shard[1],
                "itemsize": itemsize}},
        program_exactly={"tpu_custom_call": calls, "while": 0, **no_more},
        program_at_least_one=at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "layers": pattern, "tensor_shard": list(tensor),
               "expert_shard": list(shard)},
        expert_rows=expert_rows)
