"""SDAR-30B-A3B's block-diffusion training step through the program's normal
path: `models.TransformerLM(layers=("blockdiff_attention", "experts") * depth,
block_diffusion=B)` — per-head-normed grouped-query attention under the block
mask over `[clean; noised]` rows, rotated at the position in the copy, and
softmax-routed sparse experts with renormalised weights — with
`models.masked_diffusion_loss` on the noised half, `hvd.jax.build_train_step`
on `data_parallel_mesh(devices)`, AdamW: the sparse-expert builder's step with
another training program.

A batch is `(tokens, noised, masked, level)`, each (batch, L): the data
tokens; their noised copy (the mask token where `masked`); and the masking
probability of each token's block.  The noise comes from the seed through
`traffic_gen.make_pool`'s float fields — `block_draw` (one uniform draw a
block of `block_length`) gives `level = eps + (1 - eps) * draw`, `token_draw`
(one a token) is under `level` where the token is masked — and is made into
the batch in set-up (`make_batch`), as a data pipeline would.  The model runs 2 L
positions a sequence; a SAMPLE is one of the L data tokens.

The configuration holds one chip's share of each layer (`expert_shard`: the
routed experts; a sliced `vocab_size`, whose last row is the mask token;
attention whole) and a bound on the rows of the sorted expert buffer
(`row_bound`); a row the buffer could not hold makes the step's loss NaN, as in
benchmark/builders/moe_lm.py.

The comparison with the reference compiles the Trinity builder's programs
(the system's loss and gradients with what its expert layers counted and
chose; the reference's with what it chose; each parameter's gradient reduced
against the system's where the backward pass makes it) and a third, small one:
the block-diffusion kernels alone against the reference's masked softmax at
the cell's length, a few heads, with a sharpened softmax
(reference/sdar_lm.py has why).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_sdar
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.hybrid_lm import _expert_layers
from benchmark.builders.moe_lm import BuiltMoE
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, sdar_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "sdar_moe", "hidden_act": "silu", "norm_topk_prob": True,
    "attention_bias": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False}
PROBE_HEADS = 2        # heads of the block-diffusion kernels' own comparison
SUFFIX = "_blockdiff"     # what `ops/attention.py` names those kernels by


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltMoE:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (MoEConfig, TransformerLM,
                                    masked_diffusion_loss)
    from horovod_tpu.ops.attention import _bwd_plan, flash_attention
    from horovod_tpu.parallel import data_parallel_mesh

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"sdar_lm builds SDAR's layers as published, not "
                         f"{wrong}")
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    head_dim, depth = config["head_dim"], config["num_hidden_layers"]
    block_length, eps = config["block_length"], config["noise"]["eps"]
    if config["noise"] != {"kind": "absorbing", "schedule": "linear",
                           "eps": eps}:
        raise ValueError("sdar_lm draws an absorbing mask on a linear "
                         f"schedule, not {config['noise']}")
    mask_token = vocab - 1                  # the last row the chip holds
    kinds = ("blockdiff_attention", "experts") * depth
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    if seq % block_length:
        raise ValueError(f"{seq} tokens are no whole blocks of "
                         f"{block_length}")
    dtype = dtype_of(config["compute_dtype"])
    moe = MoEConfig(
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], shard, config["row_bound"],
        renormalize=True)
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_heads=heads, dtype=dtype,
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], moe=moe, layers=kinds,
        n_kv_heads=kv_heads, head_dim=head_dim, head_norm=True,
        block_diffusion=block_length,
        rope_theta=float(config["rope_theta"]))
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"sdar_lm builds AdamW, not {config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])
    attention_layers = [f"layer_{i}" for i in range(0, 2 * depth, 2)]
    counters = ("rows_per_local_expert", "rows_over_bound", "chosen_experts")
    batch_spec = (P(AXIS),) * 4

    def loss_and_rows(params, batch):
        tokens, noised, masked, level = batch
        logits, wrote = model.apply({"params": params}, tokens, noised=noised,
                                    mutable=["intermediates"])
        seen = {name: _expert_layers(wrote["intermediates"], name)
                for name in counters}
        seen["attn_blocks"] = jnp.stack([jnp.stack([
            wrote["intermediates"][layer]["mixer"][name][0]
            for name in ("attn_blocks_visited", "attn_blocks_causal")])
            for layer in attention_layers])
        seen["masked_tokens"] = masked.sum()
        loss = masked_diffusion_loss(logits, tokens, masked, level)
        return jnp.where(seen["rows_over_bound"].sum() > 0, jnp.nan,
                         loss), seen

    def loss_fn(params, batch):
        return loss_and_rows(params, batch)[0]

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=batch_spec)
    seeded = config["initialisation"]

    def init_state():
        def init(key):
            blank = jnp.zeros((1, 128), jnp.int32)
            params = model.init(key, blank, noised=blank)["params"]
            # Embedding rows at `embedding_std` an element (flax draws them at
            # 1 / sqrt(hidden)) but the mask token's, which stays as drawn;
            # the FIRST layer's per-head q and k norm scales at
            # `first_layer_qk_norm_scale`, not at one: `assumed.
            # initialisation` in the configuration has why.
            drawn = params["embed"]["embedding"]
            table = (drawn * (seeded["embedding_std"] * hidden ** 0.5)
                     ).at[mask_token].set(drawn[mask_token] * (
                         seeded["mask_embedding_std"] * hidden ** 0.5))
            first = attention_layers[0]
            mixer = dict(params[first]["mixer"])
            for name in ("q_head_norm_scale", "k_head_norm_scale"):
                mixer[name] = mixer[name] * seeded["first_layer_qk_norm_scale"]
            params = {**params, "embed": {"embedding": table},
                      first: {**params[first], "mixer": mixer}}
            return params, tx.init(params)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        level = eps + (1.0 - eps) * jnp.repeat(fields["block_draw"],
                                               block_length, axis=1)
        masked = fields["token_draw"] < level
        return tokens, jnp.where(masked, mask_token, tokens), masked, level

    @jax.jit
    def expert_rows(params, batch):        # the traced run's counter probe
        return loss_and_rows(params, batch)[1]

    def system_on_one_device():
        """(params, batch) -> (loss, gradients, the layers' counters), the
        step's own loss on a one-device mesh of the step's axis name:
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
        block_length=block_length, rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"], num_experts=moe.num_experts,
        experts_per_token=moe.experts_per_token, expert_shard=shard)

    def reference_against(params, batch, grads_s, chose):
        """The reference's loss, the three norms compare.loss_and_gradients
        reads (||g_s||, ||g_r||, ||g_s - g_r|| over the parameters), and the
        share of the system's (row, choice) pairs whose expert the reference
        did not choose for that row.  Each parameter's reference gradient is
        reduced against the system's where the backward pass makes it
        (`trinity_lm._met`), so the two whole gradients never stand side by
        side."""
        def total(sums):
            met = jax.tree.map(lambda p, g: _met(p, g, sums), params, grads_s)
            return reference.loss_and_chosen(met, batch, **reference_config)

        (loss_r, want), sums = jax.value_and_grad(total, has_aux=True)(
            jnp.zeros(3))
        same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
        return loss_r, jnp.sqrt(sums), 1.0 - same.mean()

    sharp = reference.SHARP_SCALE * head_dim ** -0.5

    def flash_calls_off_plan(state, pool):
        """The step's own lowered text against `ops/attention.py`'s plan at
        this shape: every Pallas call by its name, the block-diffusion ones a
        layer each and no causal one."""
        text = step.lower(state[0], state[1], pool[0]).as_text()
        found = {name: text.count(f'kernel_name = "{name}"')
                 for name in planned}
        return {"name": "flash_calls_off_plan", "limit": 0.0,
                "value": float(sum(abs(found[name] - planned[name])
                                   for name in planned)),
                "found": found, "planned": planned}

    def reference_checks(state, pool):
        params = compare.first_device_copy(state[0])
        n = traffic["reference_check"]["grad_batch"]
        batch = tuple(field[:n]
                      for field in compare.first_device_copy(pool[0]))
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
            # The pairs of the compared sequence (both copies' rows) whose
            # expert the float32 reference did not choose for that row: a
            # near-tie that bfloat16 flips.
            {"name": "routing_mismatch_share",
             "limit": reference.ROUTING_MISMATCH_MAX, "value": mismatch}]
        if devices[0].platform == "tpu":     # interpreted elsewhere: no call
            rows.append(flash_calls_off_plan(state, pool))
        # The block-diffusion kernels alone, where a noised query's own block
        # carries weight (reference/sdar_lm.py SHARP_SCALE).
        return rows + compare.kernel_against(
            lambda q, k, v: flash_attention(
                q, k, v, block_diffusion=block_length, sm_scale=sharp),
            lambda q, k, v: reference.masked_attention(
                q, k, v, block_length=block_length, sm_scale=sharp),
            (1, PROBE_HEADS, 2 * seq, head_dim), dtype, seed,
            reference.BLOCKDIFF_FWD_ATOL, reference.BLOCKDIFF_GRAD_RTOL,
            "blockdiff_flash_")

    rows_run = 2 * seq                   # positions of one sequence's pass
    mode = _bwd_plan(rows_run, head_dim, 1024, 1024, per_chip * heads)[0]
    planned = {name + suffix: layers for suffix, layers in (
        (SUFFIX, depth), ("", 0)) for name in FLASH_CALLS[mode]}
    no_more, at_least_one = collectives_expected(devices)
    positions_per_chip = per_chip * rows_run
    bound_rows = moe.buffer_rows(positions_per_chip)
    itemsize = jnp.dtype(dtype).itemsize
    shape = {
        "hidden": hidden, "vocab": vocab, "layers": depth,
        "attention": {"heads": heads, "kv_heads": kv_heads,
                      "head_dim": head_dim, "block_length": block_length},
        "experts": {"num_experts": moe.num_experts,
                    "expert_width": moe.expert_width}}
    ops = ops_count_sdar.sdar_lm_train_ops_per_token(
        shape, seq, moe.experts_per_token / shard[1],
        bound_rows / positions_per_chip)
    return BuiltMoE(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq], "dtype": "int32",
                 "high": mask_token},
                {"name": "block_draw", "shape": [seq // block_length],
                 "dtype": "float32"},
                {"name": "token_draw", "shape": [seq], "dtype": "float32"}],
        make_batch=make_batch,
        # A sample is a DATA token: the step runs two positions for each.
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={
            "flash_blockdiff": ops_count_sdar.flash_kernel(
                seq, heads, head_dim, depth, block_length, itemsize),
            "moe_experts": {"hidden": hidden,
                            "expert_width": moe.expert_width,
                            "local_experts": moe.num_experts // shard[1],
                            "itemsize": itemsize}},
        # No collective on one chip, kernels in the program; which flash
        # calls is `flash_calls_off_plan`'s row, by name.
        program_exactly=no_more,
        program_at_least_one=["tpu_custom_call"] + at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "layers": list(kinds), "expert_shard": list(shard),
               "block_length": block_length, "mask_token": mask_token,
               "positions_per_step": per_chip * len(devices) * rows_run},
        expert_rows=expert_rows)
