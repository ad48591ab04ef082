"""Trinity-Mini trained through the program's normal path:
`models.TransformerLM(layers=...)` — a per-layer pattern of gated,
per-head-normed grouped-query attention, under a sliding window and rotated
(`window_attention`) or over every earlier key and unrotated (`attention`),
a dense gated MLP and sigmoid-routed sparse experts with a shared one, every
mixer's output normed again before its residual (`post_norm`), the embedding
multiplied by `sqrt(hidden)` (`embed_scale`) — `models.next_token_loss`,
`hvd.jax.build_train_step` on `data_parallel_mesh(devices)`, AdamW: the hybrid
builder's step with this pattern's configuration.

A published layer is two pattern entries, its attention and then its MLP or
experts; the configuration names the published layers it keeps (`kept_layers`),
their `layer_types` and how many of them are dense (`num_dense_layers`).  It
holds one chip's share of each layer (`expert_shard`: the routed experts; a
sliced `vocab_size`; attention whole) and a bound on the rows of the sorted
expert buffer (`row_bound`).  What the step trains is `{"params": the model's,
"buffers": the router's balance bias}`, the bias set once in set-up as
benchmark/builders/hybrid_lm.py sets Nemotron's, and a row the buffer could not
hold makes the step's loss NaN, as there.

The comparison with the reference compiles the Ling builder's two programs (the
system's loss and gradients with what its expert layers counted and chose; the
reference's with what it chose) and a third, small one: the banded kernels
alone against the reference's masked softmax at the cell's length, a few heads,
with a sharpened softmax (reference/trinity_lm.py has why).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_trinity
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.hybrid_lm import (_expert_layers,
                                          balanced_selection_bias)
from benchmark.builders.moe_lm import BuiltMoE
from benchmark.reference import compare, trinity_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
    "route_norm": True, "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "num_shared_experts": 1, "mup_enabled": True,
    "rope_scaling": None, "tie_word_embeddings": False}
KINDS = {"sliding_attention": "window_attention",
         "full_attention": "attention"}
# The flash kernels of one attention layer by its backward plan
# (`ops.attention._bwd_plan`), as `pallas_call` names them: the forward, and
# the combined backward or the dk/dv and dq pair; a banded call's carry
# `_window` behind.
FLASH_CALLS = {"combined": ("hvd_flash_fwd", "hvd_flash_bwd"),
               "split": ("hvd_flash_fwd", "hvd_flash_bwd_dkdv",
                         "hvd_flash_bwd_dq")}
PROBE_HEADS = 2           # heads of the banded kernels' own comparison


@jax.custom_vjp
def _met(p, g_system, sums):
    """`p` itself.  Its backward pass takes the cotangent that reaches `p` —
    the reference's gradient of this parameter — and hands `sums`, a float32
    [3], the sums of squares of the system's gradient `g_system`, of the
    reference's and of their difference; nothing else gets a gradient."""
    return p


def _met_fwd(p, g_system, sums):
    return p, g_system


def _met_bwd(g_system, g_reference):
    g_s, g_r = (g.astype(jnp.float32) for g in (g_system, g_reference))
    return (jnp.zeros_like(g_r), jnp.zeros_like(g_s), jnp.stack(
        [jnp.sum(g_s * g_s), jnp.sum(g_r * g_r), jnp.sum(jnp.square(g_s - g_r))]))


_met.defvjp(_met_fwd, _met_bwd)


def layer_kinds(config: dict) -> tuple:
    """The pattern: each kept published layer's attention, then its MLP or
    experts."""
    kept, types = config["kept_layers"], config["layer_types"]
    period = config["global_attn_every_n_layers"]
    if not len(kept) == len(types) == config["num_hidden_layers"]:
        raise ValueError("kept_layers and layer_types name a published layer "
                         "for each of num_hidden_layers")
    kinds = []
    for place, (index, kind) in enumerate(zip(kept, types)):
        published = "full_attention" if (index + 1) % period == 0 \
            else "sliding_attention"
        if kind != published:
            raise ValueError(f"published layer {index} is {published}")
        kinds += [KINDS[kind], "gated_mlp"
                  if place < config["num_dense_layers"] else "experts"]
    return tuple(kinds)


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltMoE:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import MoEConfig, TransformerLM, next_token_loss
    from horovod_tpu.ops.attention import _bwd_plan, flash_attention
    from horovod_tpu.parallel import data_parallel_mesh

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"trinity_lm builds Trinity-Mini's layers as "
                         f"published, not {wrong}")
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    head_dim, window = config["head_dim"], config["sliding_window"]
    kinds = layer_kinds(config)
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    moe = MoEConfig(
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], shard, config["row_bound"],
        scoring="sigmoid", renormalize=True,
        weight_scale=float(config["route_scale"]),
        shared_width=config["moe_intermediate_size"])
    model = TransformerLM(
        vocab_size=vocab, d_model=hidden, n_heads=heads,
        d_ff=config["intermediate_size"], dtype=dtype,
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], moe=moe, layers=kinds,
        n_kv_heads=kv_heads, rope=False, head_dim=head_dim, window=window,
        head_norm=True, attn_gate=True, post_norm=True,
        embed_scale=hidden ** 0.5)
    if config["rope_theta"] != 10000:
        raise ValueError("models.rope turns at base 10000")
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"trinity_lm builds AdamW, not {config['optimizer']}")
    tx = optax.multi_transform(
        {"params": optax.adamw(config["optimizer"]["learning_rate"]),
         "buffers": optax.set_to_zero()},
        {"params": "params", "buffers": "buffers"})
    expert_layers = [f"layer_{i}" for i, kind in enumerate(kinds)
                     if kind == "experts"]
    window_layers = [f"layer_{i}" for i, kind in enumerate(kinds)
                     if kind == "window_attention"]

    counters = ("rows_per_local_expert", "rows_over_bound", "chosen_experts")

    def loss_and_rows(state, batch):
        inputs, targets = batch
        logits, wrote = model.apply(state, inputs, mutable=["intermediates"])
        seen = {name: _expert_layers(wrote["intermediates"], name)
                for name in counters}
        seen["attn_blocks"] = jnp.stack([jnp.stack([
            wrote["intermediates"][layer]["mixer"][name][0]
            for name in ("attn_blocks_visited", "attn_blocks_causal")])
            for layer in window_layers])
        loss = next_token_loss(logits, targets)
        return jnp.where(seen["rows_over_bound"].sum() > 0, jnp.nan,
                         loss), seen

    def loss_fn(state, batch):
        return loss_and_rows(state, batch)[0]

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=(P(AXIS), P(AXIS)))

    def init_state():
        def init(key):
            # The embedding rows stay as flax draws them, 1 / sqrt(hidden) an
            # element: `embed_scale` makes them 1.0 (`assumed.embedding`).
            # The post-norms' scales are seeded at `post_norm_init`, not at
            # one (`assumed.initialisation` has what one does to a seeded
            # router).
            params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
            params = {name: dict(layer, post_norm={
                "scale": layer["post_norm"]["scale"]
                * config["post_norm_init"]})
                if name.startswith("layer_") else layer
                for name, layer in params.items()}
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
        """(state, batch) -> (loss, gradients, the layers' counters), the
        step's own loss on a one-device mesh of the step's axis name:
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
        layers=kinds, embed_scale=hidden ** 0.5, window=window,
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"], num_experts=moe.num_experts,
        experts_per_token=moe.experts_per_token, expert_shard=shard,
        weight_scale=moe.weight_scale)

    def selection_bias(state):
        return jnp.stack([state["buffers"][layer]["mixer"]["selection_bias"]
                          for layer in expert_layers])

    def reference_against(state, batch, grads_s, chose):
        """The reference's loss, the squares of the three norms
        compare.loss_and_gradients reads (||g_s||, ||g_r||, ||g_s - g_r||
        over the parameters; the bias takes no gradient on either side), and
        the share of the system's (token, choice) pairs whose expert the
        reference did not choose for that token.  Each parameter's reference
        gradient is reduced against the system's where the backward pass
        makes it (`_met`), so the two whole gradients never stand side by
        side: they would be 5.3 GiB beside 7.9 of training state."""
        def total(sums):
            params = jax.tree.map(lambda p, g: _met(p, g, sums),
                                  state["params"], grads_s["params"])
            return reference.loss_and_chosen(
                params, batch, selection_bias=selection_bias(state),
                **reference_config)

        (loss_r, want), sums = jax.value_and_grad(total, has_aux=True)(
            jnp.zeros(3))
        same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
        return loss_r, jnp.sqrt(sums), 1.0 - same.mean()

    sharp = reference.SHARP_SCALE * head_dim ** -0.5

    def flash_calls_off_plan(state, pool):
        """The step's own lowered text against `ops/attention.py`'s plan at
        this shape: every Pallas call by its name.  Not a total of custom
        calls and no count of loops, which a later kernel may change."""
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
        # The banded kernels alone, where one key at the window's edge
        # carries weight (reference/trinity_lm.py SHARP_SCALE).
        return rows + compare.kernel_against(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            window=window, sm_scale=sharp),
            lambda q, k, v: reference.band_attention(
                q, k, v, window=window, sm_scale=sharp),
            (1, PROBE_HEADS, seq, head_dim), dtype, seed,
            reference.WINDOW_FWD_ATOL, reference.WINDOW_GRAD_RTOL,
            "window_flash_")

    count = {kind: kinds.count(kind) for kind in set(kinds)}
    windowed = count.get("window_attention", 0) if window < seq else 0
    full = count.get("attention", 0) + count.get("window_attention", 0) \
        - windowed
    mode = _bwd_plan(seq, head_dim, 1024, 1024, per_chip * heads)[0]
    planned = {name + suffix: layers for suffix, layers in (
        ("_window", windowed), ("", full)) for name in FLASH_CALLS[mode]}
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    itemsize = jnp.dtype(dtype).itemsize
    shape = {
        "hidden": hidden, "vocab": vocab,
        "window_layers": count.get("window_attention", 0),
        "full_layers": count.get("attention", 0),
        "mlp_layers": count.get("gated_mlp", 0),
        "expert_layers": count["experts"],
        "mlp_width": config["intermediate_size"],
        "attention": {"heads": heads, "kv_heads": kv_heads,
                      "head_dim": head_dim, "window": window},
        "experts": {"num_experts": moe.num_experts,
                    "expert_width": moe.expert_width,
                    "shared": moe.shared_width}}
    ops = ops_count_trinity.trinity_lm_train_ops_per_token(
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
            # The band's exact counts for the layers whose calls are banded
            # (a window no shorter than the sequence is the causal call), the
            # causal half's for the others.
            "flash_window": ops_count_trinity.flash_kernel(
                seq, heads, head_dim, windowed, window, itemsize),
            "flash_full": ops_count_trinity.flash_kernel(
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
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "layers": list(kinds), "expert_shard": list(shard),
               "window": window},
        expert_rows=expert_rows)
