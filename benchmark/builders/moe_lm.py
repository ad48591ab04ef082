"""A sparse-expert decoder LM (OLMoE's block) trained through the program's
normal path: `models.TransformerLM(moe=..., qk_norm=True)` with flash
attention, `models.moe_next_token_loss` (cross-entropy + load balancing +
router z-loss), `hvd.jax.build_train_step` on `data_parallel_mesh(devices)`,
AdamW — the dense builder's step with the sparse-expert configuration.

The configuration may hold one shard of the experts (`expert_shard`) and a
bound on the rows of the sorted buffer (`row_bound`).  A row routed to a local
expert that the buffer could not hold would be a token silently short of an
expert: the loss this builder hands the step is NaN whenever the model counts
one, so that such a step is a failed step of the cell, and the reference
check counts them over the whole pool as well.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count, ops_count_moe
from benchmark.builders import Built, collectives_expected, dtype_of
from benchmark.reference import compare, moe_lm as reference

AXIS = "hvd"
# libtpu lowers a ragged_dot to a Mosaic kernel of its own and a small custom
# call that turns the group sizes into the kernel's tile schedule; the nine
# grouped matmuls of a layer share their group sizes, and the compiled step
# keeps one schedule for the forward pass and one for the backward
# (described-chip compile, PR 26: 52 custom calls at depth 4).
TILE_SCHEDULES_PER_LAYER = 2


@dataclasses.dataclass
class BuiltMoE(Built):
    # (params, (inputs, targets)) -> {"rows_per_local_expert": (layers,
    # local experts), "rows_over_bound": (layers,), "chosen_experts":
    # (layers, tokens, k)}, jitted, on one device: what the model's layers
    # write to `intermediates` (layer_metrics/moe_load_max_over_mean.py).
    expert_rows: Optional[Callable] = None


def _by_layer(intermediates, name):
    layers = sorted(intermediates, key=lambda k: int(k.split("_")[1]))
    return jnp.stack([intermediates[layer]["moe"][name][0]
                      for layer in layers])


def build(config: dict, traffic: dict, devices, seed: int) -> Built:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (MoEConfig, TransformerLM,
                                    moe_next_token_loss)
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    experts, per_token = config["num_experts"], config["num_experts_per_tok"]
    width = config["intermediate_size"]
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    if heads != config["num_key_value_heads"] or config["hidden_act"] != \
            "silu" or config["norm_topk_prob"] or config["attention_bias"] \
            or config["clip_qkv"] or config["rope_scaling"] \
            or config["rope_theta"] != 10000 \
            or config["tie_word_embeddings"]:
        raise ValueError("moe_lm builds OLMoE's block as published: MHA, "
                         "silu experts, unnormalised top-k weights, no "
                         "biases, no clipping, rotary base 10000, untied")
    moe = MoEConfig(experts, per_token, width, shard, config["row_bound"])
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_layers=layers, n_heads=heads,
        dtype=dtype, logits_dtype=dtype_of(config["logits_dtype"]),
        use_flash=True, qk_norm=True, norm_eps=config["rms_norm_eps"],
        moe=moe)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"moe_lm builds AdamW, not {config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])
    coefs = (config["router_aux_loss_coef"], config["router_z_loss_coef"])
    if coefs != (reference.LOAD_BALANCE_COEF, reference.ROUTER_Z_COEF):
        raise ValueError(f"the reference adds 0.01 and 0.001, not {coefs}")

    def loss_fn(params, batch):
        inputs, targets = batch
        logits, wrote = model.apply({"params": params}, inputs,
                                    mutable=["router", "intermediates"])
        loss = moe_next_token_loss(logits, targets, wrote["router"], *coefs)
        over = _by_layer(wrote["intermediates"], "rows_over_bound").sum()
        return jnp.where(over > 0, jnp.nan, loss)

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
            return params, tx.init(params)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

    @jax.jit
    def expert_rows(params, batch):
        _, wrote = model.apply({"params": params}, batch[0],
                               mutable=["intermediates"])
        return {name: _by_layer(wrote["intermediates"], name)
                for name in ("rows_per_local_expert", "rows_over_bound",
                             "chosen_experts")}

    reference_config = dict(num_experts=experts, experts_per_token=per_token,
                            expert_shard=shard, norm_eps=config["rms_norm_eps"])

    def reference_loss(params, batch):
        return reference.loss(params, batch, **reference_config)

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
        # ... and the pairs of the compared sequences (the first of the
        # flattened batch) whose expert the float32 reference did not choose
        # for that token: a near-tie that bfloat16 flips.
        chose = seen[0]["chosen_experts"][:, :n * seq]
        want = compare.reference_jit(lambda p, t: reference.chosen_experts(
            p, t, **reference_config))(params, batch[0])
        same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
        rows.append({"name": "routing_mismatch_share",
                     "limit": reference.ROUTING_MISMATCH_MAX,
                     "value": 1.0 - float(same.mean())})
        return rows

    # What the compiled step must hold: the flash kernels as the backward
    # plan says (combined: 2 a layer, split: 3), libtpu's kernels for the
    # nine grouped matmuls of a layer, and no blockwise scan (a while loop).
    mode = _bwd_plan(seq, hidden // heads, 1024, 1024, per_chip * heads)[0]
    calls = layers * ({"combined": 2, "split": 3}[mode]
                      + ops_count_moe.GROUPED_MATMULS
                      + TILE_SCHEDULES_PER_LAYER)
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    ops = ops_count_moe.moe_lm_train_ops_per_token(
        hidden, width, layers, vocab, seq, experts, per_token / shard[1],
        bound_rows / tokens_per_chip)
    return BuiltMoE(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={"flash": {
            "ops": ops_count.flash_kernel_ops_per_token(seq, hidden, layers),
            "bytes": ops_count.flash_kernel_bytes_per_token(
                hidden, layers, jnp.dtype(dtype).itemsize)},
            "moe_experts": {"hidden": hidden, "expert_width": width,
                            "local_experts": experts // shard[1],
                            "itemsize": jnp.dtype(dtype).itemsize}},
        program_exactly={"tpu_custom_call": calls, "while": 0, **no_more},
        program_at_least_one=at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "expert_shard": list(shard)},
        expert_rows=expert_rows)
