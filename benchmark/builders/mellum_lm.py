"""Mellum2-12B-A2.5B trained through the program's normal path:
`models.TransformerLM(layers=...)` — a per-layer pattern of per-head-normed
grouped-query attention, every layer rotated: under a sliding window at the
plain rotary frequencies (`window_attention`, `window_rope`) or over every
earlier key at YaRN's scaled ones with its attention factor (`attention`,
`rope_scaling`), each followed by softmax-routed sparse experts with
renormalised weights — `models.next_token_loss`, `hvd.jax.build_train_step` on
`data_parallel_mesh(devices)`, AdamW: the SDAR builder's step with this
pattern, and with every pattern entry computing its forward pass again in the
backward pass where the configuration says so (`recompute_layers`:
`TransformerLM(recompute=True)`).

The configuration holds one chip's share of each layer (`expert_shard`: the
routed experts; a sliced `vocab_size`; attention whole) and a bound on the rows
of the sorted expert buffer (`row_bound`); a row the buffer could not hold makes
the step's loss NaN, as in benchmark/builders/moe_lm.py.

The comparison with the reference compiles the Trinity builder's programs (the
system's loss and gradients with what its expert layers counted and chose; the
reference's with what it chose; each parameter's gradient reduced against the
system's where the backward pass makes it) and two small ones: the banded and
the causal kernels alone, as `ops.attention._bwd_plan` runs them at this
length, against the reference's masked softmax at the cell's length, a few
heads, with a sharpened softmax (reference/mellum_lm.py has why).  Which
kernels the step holds, and how often, is read off the plan: a recomputing
layer keeps its forward kernel's outputs, so each kernel runs once a layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_mellum
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.hybrid_lm import _expert_layers
from benchmark.builders.moe_lm import BuiltMoE
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, mellum_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "mellum", "hidden_act": "silu", "norm_topk_prob": True,
    "attention_bias": False, "use_sliding_window": True,
    "tie_word_embeddings": False}
KINDS = {"sliding_attention": "window_attention",
         "full_attention": "attention"}
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")
PROBE_HEADS = 2           # heads of the kernels' own comparisons


def rotations(config: dict):
    """((theta, YaRN's numbers) of the full layers, theta of the windowed
    ones) from the source's `rope_parameters`."""
    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default":
        raise ValueError("mellum_lm turns the full layers by YaRN and the "
                         "windowed ones by the plain frequencies, not "
                         f"{config['rope_parameters']}")
    return (float(full["rope_theta"]), [full[key] for key in YARN_KEYS]), \
        float(sliding["rope_theta"])


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltMoE:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (MoEConfig, RopeScaling, TransformerLM,
                                    next_token_loss)
    from horovod_tpu.ops.attention import (_bwd_plan, flash_attention,
                                           flash_grid_steps)
    from horovod_tpu.parallel import data_parallel_mesh

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    types, depth = config["layer_types"], config["num_hidden_layers"]
    if wrong or set(config["mlp_layer_types"]) != {"sparse"} \
            or not len(types) == len(config["mlp_layer_types"]) == depth:
        raise ValueError(f"mellum_lm builds Mellum2's layers as published "
                         f"(attention, then sparse experts, a layer), not "
                         f"{wrong or config['mlp_layer_types']}")
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    head_dim, window = config["head_dim"], config["sliding_window"]
    recompute = bool(config["recompute_layers"])
    attention_kinds = tuple(KINDS[kind] for kind in types)
    kinds = tuple(entry for kind in attention_kinds
                  for entry in (kind, "experts"))
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    (theta, yarn), window_theta = rotations(config)
    scaling = RopeScaling(*yarn)
    moe = MoEConfig(
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], shard, config["row_bound"],
        renormalize=True)
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_heads=heads, dtype=dtype,
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], moe=moe, layers=kinds,
        n_kv_heads=kv_heads, head_dim=head_dim, window=window,
        head_norm=True, rope_theta=theta, rope_scaling=scaling,
        window_rope=(window_theta, None), recompute=recompute)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"mellum_lm builds AdamW, not {config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])
    window_layers = [f"layer_{i}" for i, kind in enumerate(kinds)
                     if kind == "window_attention"]
    counters = ("rows_per_local_expert", "rows_over_bound", "chosen_experts")
    batch_spec = (P(AXIS), P(AXIS))

    def loss_and_rows(params, batch):
        inputs, targets = batch
        logits, wrote = model.apply({"params": params}, inputs,
                                    mutable=["intermediates"])
        seen = {name: _expert_layers(wrote["intermediates"], name)
                for name in counters}
        seen["attn_blocks"] = jnp.stack([jnp.stack([
            wrote["intermediates"][layer]["mixer"][name][0]
            for name in ("attn_blocks_visited", "attn_blocks_causal")])
            for layer in window_layers])
        loss = next_token_loss(logits, targets)
        return jnp.where(seen["rows_over_bound"].sum() > 0, jnp.nan,
                         loss), seen

    def loss_fn(params, batch):
        return loss_and_rows(params, batch)[0]

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=batch_spec)
    embedding_std = config["initialisation"]["embedding_std"]

    def init_state():
        def init(key):
            params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
            # Embedding rows at `embedding_std` an element (flax draws them
            # at 1 / sqrt(hidden)): `assumed.initialisation` has why.
            table = params["embed"]["embedding"] * (embedding_std
                                                    * hidden ** 0.5)
            params = {**params, "embed": {"embedding": table}}
            return params, tx.init(params)

        return jax.jit(init, out_shardings=replicated)(
            jax.random.PRNGKey(seed))

    def make_batch(fields):
        tokens = fields["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

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
        layers=attention_kinds, window=window, rope_theta=theta,
        yarn=scaling._asdict(),       # the reference's names are the fields'
        norm_eps=config["rms_norm_eps"], num_experts=moe.num_experts,
        experts_per_token=moe.experts_per_token, expert_shard=shard)
    if window_theta != theta:
        raise ValueError("reference/mellum_lm.py turns both kinds at one "
                         "theta, as the source does")

    def reference_against(params, batch, grads_s, chose):
        """The reference's loss, the three norms compare.loss_and_gradients
        reads (||g_s||, ||g_r||, ||g_s - g_r|| over the parameters), and the
        share of the system's (token, choice) pairs whose expert the
        reference did not choose for that token.  Each parameter's reference
        gradient is reduced against the system's where the backward pass
        makes it (`trinity_lm._met`), so the two whole gradients never stand
        side by side."""
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
        this shape and the recomputation switch: every Pallas call by its
        name.  Not a total of custom calls and no count of loops, which a
        later kernel may change."""
        text = step.lower(state[0], state[1], pool[0]).as_text()
        found = {name: text.count(f'kernel_name = "{name}"')
                 for name in planned}
        return {"name": "flash_calls_off_plan", "limit": 0.0,
                "value": float(sum(abs(found[name] - planned[name])
                                   for name in planned)),
                "found": found, "planned": planned}

    def kernel_rows(span, prefix):
        """The flash kernels alone under `span` (None: causal), forward and
        the plan's backward, against the reference's masked softmax."""
        return compare.kernel_against(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            window=span, sm_scale=sharp),
            lambda q, k, v: reference.band_attention(
                q, k, v, window=span, sm_scale=sharp),
            (1, PROBE_HEADS, seq, head_dim), dtype, seed,
            reference.FLASH_FWD_ATOL, reference.FLASH_GRAD_RTOL, prefix)

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
        # The kernels alone, where one key at the window's edge carries
        # weight (reference/mellum_lm.py SHARP_SCALE): the band where the
        # call is banded, and the causal kernels of the full layers.
        if windowed:
            rows += kernel_rows(window, "window_flash_")
        return rows + kernel_rows(None, "causal_flash_")

    windowed = kinds.count("window_attention") if window < seq else 0
    full = depth - windowed
    bh = per_chip * heads
    mode, block_q, block_k = _bwd_plan(seq, head_dim, 1024, 1024, bh)
    # Every kernel once a layer, recomputed or not: a recomputing layer keeps
    # its forward kernel's output and log-sum-exp (models/transformer.py
    # `_kept_by_a_recomputing_layer`), and `flash_calls_off_plan` holds the
    # step to that.
    planned = {name + suffix: layers for suffix, layers in (
        ("_window", windowed), ("", full)) for name in FLASH_CALLS[mode]}
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    itemsize = jnp.dtype(dtype).itemsize
    shape = {
        "hidden": hidden, "vocab": vocab, "window_layers": windowed,
        "full_layers": full,
        "attention": {"heads": heads, "kv_heads": kv_heads,
                      "head_dim": head_dim, "window": window},
        "experts": {"num_experts": moe.num_experts,
                    "expert_width": moe.expert_width}}
    ops = ops_count_mellum.mellum_lm_train_ops_per_token(
        shape, seq, moe.experts_per_token / shard[1],
        bound_rows / tokens_per_chip, recompute)
    return BuiltMoE(
        mesh=mesh, step=step, init_state=init_state,
        fields=[{"name": "tokens", "shape": [seq + 1], "dtype": "int32",
                 "high": vocab}],
        make_batch=make_batch,
        samples_per_step=per_chip * len(devices) * seq, sample_unit="token",
        ops_per_sample=ops,
        kernels={
            # The band's exact counts for the layers whose calls are banded
            # (a window no shorter than the sequence is the causal call), the
            # causal half's for the others.
            "flash_window": ops_count_mellum.flash_kernel(
                seq, heads, head_dim, windowed, window, itemsize),
            "flash_full": ops_count_mellum.flash_kernel(
                seq, heads, head_dim, full, None, itemsize),
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
        notes={"flash_backward": mode,
               "flash_backward_blocks": [block_q, block_k],
               "flash_calls_planned": planned,
               # {kernel: (live tile pairs, grid steps, the rectangle's)} a
               # head, off the kernels' own tile tables.
               "flash_grid_window": {
                   name: list(grid) for name, grid in flash_grid_steps(
                       seq, head_dim, bh, causal=True,
                       window=window).items()} if windowed else None,
               "flash_grid_full": {
                   name: list(grid) for name, grid in flash_grid_steps(
                       seq, head_dim, bh, causal=True).items()},
               "recompute_layers": recompute, "buffer_rows": bound_rows,
               "layers": list(kinds), "expert_shard": list(shard),
               "window": window},
        expert_rows=expert_rows)
