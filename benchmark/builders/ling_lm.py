"""Ling-3.0-flash's language model trained through the program's normal path:
`models.TransformerLM(layers=...)` — a per-layer pattern of Kimi-delta
linear-attention mixers, latent attention (flash kernels at two head widths),
a dense gated MLP and group-limited sparse experts with a shared one —
`models.next_token_loss`, `hvd.jax.build_train_step` on
`data_parallel_mesh(devices)`, AdamW: the hybrid builder's step with this
pattern's configuration.

A published layer is two pattern entries, its mixer and then its MLP or
experts; the configuration names the published layers it keeps
(`kept_layers`) and each one's kinds follow from `layer_group_size` and
`first_k_dense_replace`.  The configuration holds one chip's share of each
layer (`tensor_shard`: the mixers' heads; `expert_shard`: the routed experts; a
sliced `vocab_size`) and a bound on the rows of the sorted expert buffer
(`row_bound`).  What the step trains is `{"params": the model's, "buffers": the
router's balance bias}`, the bias set once in set-up as
benchmark/builders/hybrid_lm.py sets Nemotron's, and a row the buffer could not
hold makes the step's loss NaN, as there.

The comparison with the reference compiles TWO programs, not the hybrid
builder's four: the system's loss and gradients come back with what its expert
layers counted and chose, the reference's with what it chose.  The machine's
compile cache holds 192 MiB; this cell's step and its one-device twin are 63
and 61 MB, and with a forward pass each for the counters and for the
reference's choice (29 and 23 MB more) no run ever found a program of the run
before (6 of 6 runs, 305–320 s of compiles each; my chip runs, PR 32).  The
other seven batches of the pool are not walked for rows over the bound in
set-up (a third program, or eight more runs of the one-device twin — and after
those a cold run's window read 21,063 and 32,482 tok/s/chip where it reads
40,219 without them; my chip runs, PR 32): a row over the bound in any of them
is a NaN loss, so a failed step, in the window, which steps every batch of the
pool six times.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_ling, ops_count_moe
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.hybrid_lm import (_expert_layers,
                                          balanced_selection_bias)
from benchmark.builders.moe_lm import TILE_SCHEDULES_PER_LAYER, BuiltMoE
from benchmark.reference import compare, ling_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "score_function": "sigmoid", "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True, "q_lora_rank": None,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "use_qk_norm": True, "use_mla_nope": False,
    "use_nGPT": False, "scale_router_input": False, "value_norm": False,
    "up_proj_norm": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True,
    "gated_attention_proj_granularity_type": "head_wise"}
# The flash kernels of a latent-attention layer under the split backward
# plan, and a `while` forward and one backward for each delta layer's
# recurrence between chunks (described-chip compile, PR 32).
FLASH_CALLS = {"combined": 2, "split": 3}
WHILES_PER_DELTA_LAYER = 2


def layer_kinds(config: dict) -> tuple:
    """The pattern: each kept published layer's mixer, then its MLP or
    experts."""
    kinds = []
    for index in config["kept_layers"]:
        kinds.append("latent_attention"
                     if (index + 1) % config["layer_group_size"] == 0
                     else "delta")
        kinds.append("gated_mlp" if index < config["first_k_dense_replace"]
                     else "experts")
        clamps = (config["expert_swiglu_limit_list"][index],
                  config["share_expert_swiglu_limit_list"][index])
        if any(clamps):
            raise ValueError(f"published layer {index} clamps its experts' "
                             f"gate at {clamps}: no layer here does")
    return tuple(kinds)


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltMoE:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (DeltaConfig, LatentConfig, MoEConfig,
                                    TransformerLM, next_token_loss)
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"ling_lm builds Ling-3.0-flash's layers as "
                         f"published, not {wrong}")
    if len(config["kept_layers"]) != config["num_hidden_layers"]:
        raise ValueError("kept_layers names a published layer for each of "
                         "num_hidden_layers")
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    kinds = layer_kinds(config)
    tensor = tuple(config["tensor_shard"])
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    delta = DeltaConfig(heads, head_dim, config["short_conv_kernel_size"],
                        config["chunk_size"], float(config["kda_lower_bound"]))
    latent = LatentConfig(config["kv_lora_rank"], config["qk_nope_head_dim"],
                          config["qk_rope_head_dim"], config["v_head_dim"],
                          float(config["rope_theta"]))
    if latent.rope_dim != config["rotary_dim"]:
        raise ValueError("the rotation is over all of qk_rope_head_dim")
    moe = MoEConfig(
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], shard, config["row_bound"],
        scoring="sigmoid", renormalize=True,
        weight_scale=float(config["routed_scaling_factor"]),
        shared_width=config["moe_shared_expert_intermediate_size"],
        n_group=config["n_group"], topk_group=config["topk_group"])
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_heads=heads,
        d_ff=config["intermediate_size"], dtype=dtype,
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], moe=moe, layers=kinds, delta=delta,
        latent=latent, head_shard=tensor)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"ling_lm builds AdamW, not {config['optimizer']}")
    tx = optax.multi_transform(
        {"params": optax.adamw(config["optimizer"]["learning_rate"]),
         "buffers": optax.set_to_zero()},
        {"params": "params", "buffers": "buffers"})
    expert_layers = [f"layer_{i}" for i, kind in enumerate(kinds)
                     if kind == "experts"]

    counters = ("rows_per_local_expert", "rows_over_bound", "chosen_experts")

    def loss_and_rows(state, batch):
        inputs, targets = batch
        logits, wrote = model.apply(state, inputs, mutable=["intermediates"])
        seen = {name: _expert_layers(wrote["intermediates"], name)
                for name in counters}
        loss = next_token_loss(logits, targets)
        return jnp.where(seen["rows_over_bound"].sum() > 0, jnp.nan,
                         loss), seen

    def loss_fn(state, batch):
        return loss_and_rows(state, batch)[0]

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
    def expert_rows(state, batch):        # the traced run's load probe
        return loss_and_rows(state, batch)[1]

    def system_on_one_device():
        """(state, batch) -> (loss, gradients, the expert layers' counters),
        the step's own loss on a one-device mesh of the step's axis name:
        compare.system_on_one_device with the counters kept."""
        def local(state, batch):
            (loss, seen), grads = jax.value_and_grad(
                loss_and_rows, has_aux=True)(state, batch)
            return lax.pmean(loss, AXIS), grads, {
                "chosen_experts": seen["chosen_experts"],
                **{name: lax.psum(seen[name], AXIS)
                   for name in counters[:2]}}

        spec = (P(AXIS), P(AXIS))
        return jax.jit(jax.shard_map(
            local, mesh=data_parallel_mesh(devices[:1], axis_name=AXIS),
            in_specs=(P(), spec),
            out_specs=(P(), P(), {"chosen_experts": P(None, AXIS),
                                  **dict.fromkeys(counters[:2], P())})))

    reference_config = dict(
        layers=kinds, head_dim=head_dim, lower_bound=delta.lower_bound,
        nope_dim=latent.nope_dim, rope_theta=latent.rope_theta,
        norm_eps=config["rms_norm_eps"], num_experts=moe.num_experts,
        experts_per_token=moe.experts_per_token, expert_shard=shard,
        weight_scale=moe.weight_scale, n_group=moe.n_group,
        topk_group=moe.topk_group)

    def selection_bias(state):
        return jnp.stack([state["buffers"][layer]["mixer"]["selection_bias"]
                          for layer in expert_layers])

    def reference_against(state, batch, grads_s, chose):
        """The reference's loss, the three norms compare.loss_and_gradients
        reads, and the share of the system's (token, choice) pairs whose
        expert the reference did not choose for that token."""
        (loss_r, want), grads_r = jax.value_and_grad(
            lambda s: reference.loss_and_chosen(
                s["params"], batch, selection_bias=selection_bias(s),
                **reference_config), has_aux=True)(state)
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, grads_s,
                            grads_r)
        same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
        return (loss_r, compare._norm(grads_s), compare._norm(grads_r),
                compare._norm(diff), 1.0 - same.mean())

    def reference_checks(state, pool):
        params = compare.first_device_copy(state[0])
        inputs, targets = compare.first_device_copy(pool[0])
        n = traffic["reference_check"]["grad_batch"]
        batch = (inputs[:n], targets[:n])
        loss_s, grads_s, seen = system_on_one_device()(params, batch)
        loss_r, norm_s, norm_r, norm_diff, mismatch = (
            float(x) for x in compare.reference_jit(reference_against)(
                params, batch, grads_s, seen["chosen_experts"]))
        del grads_s
        loss_s = float(loss_s)
        return [
            {"name": "loss_rel_error", "limit": reference.LOSS_RTOL,
             "value": abs(loss_s - loss_r) / abs(loss_r),
             "system": loss_s, "reference": loss_r},
            {"name": "grad_norm_rel_error",
             "limit": reference.GRAD_NORM_RTOL,
             "value": abs(norm_s / norm_r - 1.0),
             "system": norm_s, "reference": norm_r},
            {"name": "grad_rel_l2_error", "limit": reference.GRAD_RTOL,
             "value": norm_diff / norm_r},
            # Rows routed here that the bounded buffer could not hold, in the
            # compared batch; in every other batch of the pool one makes a
            # step's loss NaN, and the window counts that step as failed.
            {"name": "rows_over_bound", "limit": 0.0,
             "value": float(seen["rows_over_bound"].sum()),
             "largest_layer_rows": int(
                 seen["rows_per_local_expert"].sum(-1).max()),
             "bound_rows": bound_rows},
            # The pairs of the compared sequence whose expert the float32
            # reference did not choose for that token: a near-tie that
            # bfloat16 flips.
            {"name": "routing_mismatch_share",
             "limit": reference.ROUTING_MISMATCH_MAX, "value": mismatch}]

    count = {kind: kinds.count(kind) for kind in set(kinds)}
    local_heads = heads // tensor[1]
    d_qk = latent.nope_dim + latent.rope_dim
    mode = _bwd_plan(seq, d_qk, 1024, 1024, per_chip * local_heads,
                     latent.v_dim)[0]
    calls = count.get("latent_attention", 0) * FLASH_CALLS[mode] \
        + count["experts"] * (ops_count_moe.GROUPED_MATMULS
                              + TILE_SCHEDULES_PER_LAYER)
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    itemsize = jnp.dtype(dtype).itemsize
    local_kda = {"heads": local_heads, "head_dim": head_dim,
                 "chunk": min(delta.chunk, seq)}
    shape = {
        "hidden": hidden, "vocab": vocab, "kda_layers": count["delta"],
        "latent_attention_layers": count.get("latent_attention", 0),
        "mlp_layers": count.get("gated_mlp", 0),
        "expert_layers": count["experts"],
        "mlp_width": config["intermediate_size"], "kda": local_kda,
        "latent_attention": dict(latent._asdict(), heads=local_heads),
        "experts": {"num_experts": moe.num_experts,
                    "expert_width": moe.expert_width,
                    "shared": moe.shared_width}}
    ops = ops_count_ling.ling_lm_train_ops_per_token(
        shape, seq, moe.experts_per_token / shard[1],
        bound_rows / tokens_per_chip)
    return BuiltMoE(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={
            "mla_flash": ops_count_ling.flash_two_width_kernel(
                seq, local_heads, d_qk, latent.v_dim,
                shape["latent_attention_layers"], itemsize),
            "kda_scan": dict(local_kda, layers=count["delta"],
                             itemsize=itemsize),
            "moe_experts": {"hidden": hidden,
                            "expert_width": moe.expert_width,
                            "local_experts": moe.num_experts // shard[1],
                            "itemsize": itemsize}},
        program_exactly={"tpu_custom_call": calls,
                         "while": WHILES_PER_DELTA_LAYER * count["delta"],
                         **no_more},
        program_at_least_one=at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "layers": list(kinds), "tensor_shard": list(tensor),
               "expert_shard": list(shard)},
        expert_rows=expert_rows)
