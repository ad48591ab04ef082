"""Qwen3-Next-80B-A3B trained through the program's normal path:
`models.TransformerLM(layers=...)` — a per-layer pattern of Gated DeltaNet
mixers (`gated_delta`: 32 value heads reading 16 key heads, a decay a head),
gated, per-head-normed grouped-query attention at a head of 256 with a quarter
of it rotated (`attention`), and softmax-routed sparse experts beside a shared
expert under an output gate (`experts`) — `models.next_token_loss`,
`hvd.jax.build_train_step` on `data_parallel_mesh(devices)`, AdamW: the
sparse-expert builder's step with this pattern's configuration.

A published layer is two pattern entries, its mixer and then its experts; layer
`i` is gated attention where `(i + 1) % full_attention_interval == 0` and a
Gated DeltaNet mixer otherwise.  The configuration holds one chip's share of
each layer (`expert_shard`: the routed experts; a sliced `vocab_size`; the
mixers whole) and a bound on the rows of the sorted expert buffer
(`row_bound`); a row the buffer could not hold makes the step's loss NaN, as
in benchmark/builders/moe_lm.py.

The comparison with the reference compiles the SDAR builder's two programs
(the system's loss and gradients with what its expert layers counted and
chose; the reference's with what it chose, each parameter's gradient reduced
against the system's where the backward pass makes it: `trinity_lm._met`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_qwen3next
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.hybrid_lm import _expert_layers
from benchmark.builders.moe_lm import BuiltMoE
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, qwen3next_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "qwen3_next", "hidden_act": "silu", "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
    "use_sliding_window": False, "tie_word_embeddings": False}


def layer_kinds(config: dict) -> tuple:
    """The pattern: each published layer's mixer, then its experts."""
    period = config["full_attention_interval"]
    kinds = []
    for index in range(config["num_hidden_layers"]):
        kinds += ["attention" if (index + 1) % period == 0 else "gated_delta",
                  "experts"]
    return tuple(kinds)


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltMoE:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (DeltaConfig, MoEConfig, TransformerLM,
                                    next_token_loss)
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.ops.delta_rule import lowered_plan
    from horovod_tpu.parallel import data_parallel_mesh

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"qwen3next_lm builds Qwen3-Next's layers as "
                         f"published, not {wrong}")
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    head_dim = config["head_dim"]
    rotary_dim = int(head_dim * config["partial_rotary_factor"])
    key_heads, value_heads = (config["linear_num_key_heads"],
                              config["linear_num_value_heads"])
    linear_dim = config["linear_key_head_dim"]
    if linear_dim != config["linear_value_head_dim"]:
        raise ValueError("models.DeltaMixer has one head width for keys and "
                         "values")
    kinds = layer_kinds(config)
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    delta = DeltaConfig(key_heads, linear_dim,
                        config["linear_conv_kernel_dim"],
                        config["chunk_size"], value_heads=value_heads)
    moe = MoEConfig(
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], shard, config["row_bound"],
        renormalize=True,
        shared_width=config["shared_expert_intermediate_size"],
        shared_output_gate=True)
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_heads=heads, dtype=dtype,
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], moe=moe, layers=kinds, delta=delta,
        n_kv_heads=kv_heads, head_dim=head_dim, head_norm=True,
        attn_gate=True, rope_theta=float(config["rope_theta"]),
        rotary_dim=rotary_dim)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"qwen3next_lm builds AdamW, not "
                         f"{config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])
    counters = ("rows_per_local_expert", "rows_over_bound", "chosen_experts")
    batch_spec = (P(AXIS), P(AXIS))

    def loss_and_rows(params, batch):
        inputs, targets = batch
        logits, wrote = model.apply({"params": params}, inputs,
                                    mutable=["intermediates"])
        seen = {name: _expert_layers(wrote["intermediates"], name)
                for name in counters}
        loss = next_token_loss(logits, targets)
        return jnp.where(seen["rows_over_bound"].sum() > 0, jnp.nan,
                         loss), seen

    def loss_fn(params, batch):
        return loss_and_rows(params, batch)[0]

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
    def expert_rows(params, batch):        # the traced run's load probe
        return loss_and_rows(params, batch)[1]

    def system_on_one_device():
        """(params, batch) -> (loss, gradients, the expert layers' counters),
        the step's own loss on a one-device mesh of the step's axis name:
        compare.system_on_one_device with the counters kept."""
        def local(params, batch):
            (loss, seen), grads = jax.value_and_grad(
                loss_and_rows, has_aux=True)(params, batch)
            return lax.pmean(loss, AXIS), grads, {
                "chosen_experts": seen["chosen_experts"],
                **{name: lax.psum(seen[name], AXIS)
                   for name in counters[:2]}}

        return jax.jit(jax.shard_map(
            local, mesh=data_parallel_mesh(devices[:1], axis_name=AXIS),
            in_specs=(P(), batch_spec),
            out_specs=(P(), P(), {"chosen_experts": P(None, AXIS),
                                  **dict.fromkeys(counters[:2], P())})))

    reference_config = dict(
        layers=kinds, head_dim=linear_dim,
        rope_theta=float(config["rope_theta"]), rotary_dim=rotary_dim,
        norm_eps=config["rms_norm_eps"], num_experts=moe.num_experts,
        experts_per_token=moe.experts_per_token, expert_shard=shard)

    def reference_against(params, batch, grads_s, chose):
        """The reference's loss, the three norms compare.loss_and_gradients
        reads (||g_s||, ||g_r||, ||g_s - g_r|| over the parameters), and the
        share of the system's (token, choice) pairs whose expert the reference
        did not choose for that token.  Each parameter's reference gradient is
        reduced against the system's where the backward pass makes it
        (`trinity_lm._met`), so the two whole gradients never stand side by
        side: they would be 5 GB beside 10 of training state."""
        def total(sums):
            met = jax.tree.map(lambda p, g: _met(p, g, sums), params, grads_s)
            return reference.loss_and_chosen(met, batch, **reference_config)

        (loss_r, want), sums = jax.value_and_grad(total, has_aux=True)(
            jnp.zeros(3))
        same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
        return loss_r, jnp.sqrt(sums), 1.0 - same.mean()

    def flash_calls_off_plan(state, pool):
        """The step's own lowered text against `ops/attention.py`'s plan at
        this shape: every Pallas call by its name, one of each a gated
        attention layer."""
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
        loss_r, norms, mismatch = compare.reference_jit(reference_against)(
            params, batch, grads_s, seen["chosen_experts"])
        del grads_s
        loss_r, mismatch = float(loss_r), float(mismatch)
        norm_s, norm_r, norm_diff = (float(x) for x in norms)
        loss_s = float(loss_s)
        rows = [
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
        if devices[0].platform == "tpu":     # interpreted elsewhere: no call
            rows.append(flash_calls_off_plan(state, pool))
        return rows

    count = {kind: kinds.count(kind) for kind in set(kinds)}
    gdn_layers, attention_layers = (count.get("gated_delta", 0),
                                    count.get("attention", 0))
    mode = _bwd_plan(seq, head_dim, 1024, 1024, per_chip * heads)[0]
    planned = dict.fromkeys(FLASH_CALLS[mode], attention_layers)
    chunk = min(delta.chunk, seq)
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    itemsize = jnp.dtype(dtype).itemsize
    gdn = {"key_heads": key_heads, "value_heads": value_heads,
           "head_dim": linear_dim, "chunk": chunk}
    shape = {
        "hidden": hidden, "vocab": vocab, "gdn_layers": gdn_layers,
        "attention_layers": attention_layers,
        "expert_layers": count["experts"], "gdn": gdn,
        "attention": {"heads": heads, "kv_heads": kv_heads,
                      "head_dim": head_dim, "rotary_dim": rotary_dim},
        "experts": {"num_experts": moe.num_experts,
                    "expert_width": moe.expert_width,
                    "shared": moe.shared_width}}
    ops = ops_count_qwen3next.qwen3next_lm_train_ops_per_token(
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
            "flash_h256": ops_count_qwen3next.flash_kernel(
                seq, heads, kv_heads, head_dim, attention_layers, itemsize),
            "gdn_scan": dict(gdn, layers=gdn_layers, itemsize=itemsize),
            "moe_experts": {"hidden": hidden,
                            "expert_width": moe.expert_width,
                            "local_experts": moe.num_experts // shard[1],
                            "itemsize": itemsize}},
        # No collective on one chip; the loops are the delta rule's own plan
        # at this length, a Gated DeltaNet layer each; which flash calls is
        # `flash_calls_off_plan`'s row, by name.
        program_exactly={"while": gdn_layers * lowered_plan(seq, chunk)[
            "while"], **no_more},
        program_at_least_one=["tpu_custom_call"] + at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "layers": list(kinds), "expert_shard": list(shard),
               "rotary_dim": rotary_dim},
        expert_rows=expert_rows)
