"""Keye-VL-2.0-30B-A3B's language model trained through the program's normal
path: `models.TransformerLM(layers=("selected_attention", "experts") * depth,
indexer=IndexerConfig(16, 64, 2048))` — per-head-normed grouped-query
attention whose every query sees the 2,048 earlier keys a learned indexer
scores highest (the flash kernels take the selection as their mask's operand),
and softmax-routed sparse experts with renormalised weights — with
`models.next_token_loss + models.indexer_loss`, `hvd.jax.build_train_step` on
`data_parallel_mesh(devices)`, AdamW: the sparse-expert builder's step with
another attention and a second loss.

The configuration holds one chip's share of each layer (`expert_shard`: the
routed experts; a sliced `vocab_size`; attention, indexer and router whole)
and a bound on the rows of the sorted expert buffer (`row_bound`); a row the
buffer could not hold makes the step's loss NaN, as in
benchmark/builders/moe_lm.py.

The comparison with the reference (`system_terms`, `against_reference`,
`compare_rows`: module functions, so that a control can put another program
on either side) compiles two programs: the system's two loss terms and
gradients with what its layers chose — experts and selections — and the
reference's, each parameter's reference gradient reduced against the system's
where the backward pass makes it (`trinity_lm._met`), the indexers'
parameters a group of their own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import ops_count_keye
from benchmark.builders import collectives_expected, dtype_of
from benchmark.builders.hybrid_lm import _expert_layers
from benchmark.builders.moe_lm import BuiltMoE
from benchmark.builders.trinity_lm import FLASH_CALLS, _met
from benchmark.reference import compare, keye_lm as reference

AXIS = "hvd"
# What this builder builds, as the source's config.json states it; another
# value of any of these keys is another model.
AS_PUBLISHED = {
    "model_type": "KeyeVL2", "hidden_act": "silu", "norm_topk_prob": True,
    "attention_bias": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False}
SUFFIX = "_selected"      # what `ops/attention.py` names the kernels by
DSA_KERNELS = ("hvd_dsa_index", "hvd_dsa_index_bwd_dq",
               "hvd_dsa_index_bwd_dk", "hvd_dsa_probs")
COUNTERS = ("rows_per_local_expert", "rows_over_bound", "chosen_experts")
GROUPS = ("body", "indexer")         # `against_reference`'s gradient groups


def model_of(config: dict, topk=None):
    """(the model, its MoEConfig, its layer kinds) of a configuration;
    `topk`: another than the configuration's (a control's)."""
    from horovod_tpu.models import IndexerConfig, MoEConfig, TransformerLM

    wrong = {k: config.get(k) for k, v in AS_PUBLISHED.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(f"keye_lm builds Keye-VL-2.0's language model as "
                         f"published, not {wrong}")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("keye_lm builds an indexer of one key head")
    depth = config["num_hidden_layers"]
    kinds = ("selected_attention", "experts") * depth
    moe = MoEConfig(
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], tuple(config["expert_shard"]),
        config["row_bound"], renormalize=True)
    model = TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        dtype=dtype_of(config["compute_dtype"]),
        logits_dtype=dtype_of(config["logits_dtype"]), use_flash=True,
        norm_eps=config["rms_norm_eps"], moe=moe, layers=kinds,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], head_norm=True,
        rope_theta=float(config["rope_theta"]),
        indexer=IndexerConfig(sa["indexer_num_heads"], sa["indexer_head_dim"],
                              sa["topk"] if topk is None else topk))
    return model, moe, kinds


def reference_config(config: dict) -> dict:
    return dict(
        topk=config["sa_config"]["topk"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"], num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_shard=tuple(config["expert_shard"]))


def loss_terms_and_rows(model, params, batch):
    """((next-token loss, the indexers' loss), what the layers counted and
    chose): `seen` holds the expert layers' COUNTERS, the selecting layers'
    SELECTION_COUNTS as `(layers, 5)` under "selection_counts" and their
    selections `(layers, batch, seq, seq)` int8 under "selections" (empty
    where no layer selects: a sequence of at most `topk` positions)."""
    from horovod_tpu.models import indexer_loss, next_token_loss
    from horovod_tpu.models.transformer import SELECTION_COUNTS, _sown

    inputs, targets = batch
    logits, wrote = model.apply({"params": params}, inputs,
                                mutable=["intermediates"])
    wrote = wrote["intermediates"]
    seen = {name: _expert_layers(wrote, name) for name in COUNTERS}
    counts = [jnp.stack(_sown(wrote, "dsa_" + name))
              for name in SELECTION_COUNTS if _sown(wrote, "dsa_" + name)]
    seen["selection_counts"] = jnp.stack(counts, axis=1) if counts \
        else jnp.zeros((0, len(SELECTION_COUNTS)), jnp.int32)
    chosen = _sown(wrote, "dsa_selection")
    seen["selections"] = jnp.stack(chosen) if chosen \
        else jnp.zeros((0,) + inputs.shape + inputs.shape[-1:], jnp.int8)
    over = seen["rows_over_bound"].sum() > 0
    return (jnp.where(over, jnp.nan, next_token_loss(logits, targets)),
            indexer_loss(wrote)), seen


def system_terms(model, devices):
    """(params, batch) -> ((next-token loss, indexers' loss), gradients of
    their sum, `seen`): the step's own loss on a one-device mesh of the
    step's axis name, jitted."""
    from horovod_tpu.parallel import data_parallel_mesh

    def local(params, batch):
        def total(params):
            terms, seen = loss_terms_and_rows(model, params, batch)
            return terms[0] + terms[1], (terms, seen)

        (_, (terms, seen)), grads = jax.value_and_grad(
            total, has_aux=True)(params)
        return tuple(lax.pmean(t, AXIS) for t in terms), grads, {
            "chosen_experts": seen["chosen_experts"],
            "selections": seen["selections"],
            **{name: lax.psum(seen[name], AXIS)
               for name in ("rows_per_local_expert", "rows_over_bound",
                            "selection_counts")}}

    spec = (P(AXIS), P(AXIS))
    return jax.jit(jax.shard_map(
        local, mesh=data_parallel_mesh(devices[:1], axis_name=AXIS),
        in_specs=(P(), spec),
        out_specs=(P(), P(), {
            "chosen_experts": P(None, AXIS), "selections": P(None, AXIS),
            "rows_per_local_expert": P(), "rows_over_bound": P(),
            "selection_counts": P()})))


def _group(path) -> int:
    return int(any("index_" in str(getattr(key, "key", key))
                   for key in path))


def against_reference(reference_config, params, batch, grads_s, chose,
                      selections, **more):
    """The reference's two loss terms; per group of GROUPS the three norms
    (||g_s||, ||g_r||, ||g_s - g_r||); the share of the system's (row,
    choice) pairs whose expert the reference did not choose; the share of
    the pairs the system selected past `topk` that the reference did not.
    `more`: the reference's other keywords (a control's `operand_dtype`)."""
    def total(sums):
        met = jax.tree_util.tree_map_with_path(
            lambda path, p, g: _met(p, g, sums[_group(path)]), params,
            grads_s)
        xent, kl, differ, want, _ = reference.loss_terms(
            met, batch, selections if selections.shape[0] else None,
            **reference_config, **more)
        return xent + kl, (xent, kl, differ, want)

    (_, (xent, kl, differ, want)), sums = jax.value_and_grad(
        total, has_aux=True)(jnp.zeros((len(GROUPS), 3)))
    same = (chose[..., :, None] == want[..., None, :]).any(axis=-1)
    return xent, kl, jnp.sqrt(sums), 1.0 - same.mean(), differ


def compare_rows(system, against, seen, bound_rows):
    """The comparison's rows from `system_terms`' terms and `seen` and
    `against_reference`'s result."""
    loss_s, kl_s = (float(t) for t in system)
    loss_r, kl_r, _, routing, differ = (
        float(t) if t.ndim == 0 else None for t in against)
    norms = [[float(x) for x in row] for row in against[2]]
    all_s, all_r = (sum(row[i] ** 2 for row in norms) ** 0.5 for i in (0, 1))
    rows = [
        {"name": "loss_rel_error", "limit": reference.LOSS_RTOL,
         "value": abs(loss_s - loss_r) / abs(loss_r),
         "system": loss_s, "reference": loss_r},
        # The indexers' own loss, the layers' KL terms summed; 0 on both
        # sides where nothing selects.
        {"name": "indexer_kl_rel_error", "limit": reference.KL_RTOL,
         "value": abs(kl_s - kl_r) / max(abs(kl_r), 1e-30),
         "system": kl_s, "reference": kl_r},
        {"name": "grad_norm_rel_error", "limit": reference.GRAD_NORM_RTOL,
         "value": abs(all_s / all_r - 1.0), "system": all_s,
         "reference": all_r}]
    for group, limit, (norm_s, norm_r, norm_diff) in zip(
            GROUPS, (reference.GRAD_RTOL, reference.INDEXER_GRAD_RTOL),
            norms):
        # ||g_s - g_r|| / ||g_r|| over the group's parameters; the indexers'
        # gradient comes from the KL terms alone, every other from the
        # cross-entropy alone.
        rows.append({"name": f"{group}_grad_rel_l2_error", "limit": limit,
                     "value": norm_diff / max(norm_r, 1e-30),
                     "system": norm_s, "reference": norm_r})
    return rows + [
        # Rows routed here that the bounded buffer could not hold, in the
        # compared batch; in every other batch of the pool one makes a
        # step's loss NaN, and the window counts that step as failed.
        {"name": "rows_over_bound", "limit": 0.0,
         "value": float(seen["rows_over_bound"].sum()),
         "largest_layer_rows": int(
             seen["rows_per_local_expert"].sum(-1).max()),
         "bound_rows": bound_rows},
        # The pairs of the compared sequence whose expert the float32
        # reference did not choose for that token: a near-tie that bfloat16
        # flips.
        {"name": "routing_mismatch_share",
         "limit": reference.ROUTING_MISMATCH_MAX, "value": routing},
        # The (query, key) pairs the system's layers selected, in the rows
        # past `topk`, that the float32 reference did not select: a score
        # within rounding of the row's threshold.
        {"name": "selection_mismatch_share",
         "limit": reference.SELECTION_MISMATCH_MAX, "value": differ,
         "selected_over_causal": [
             float(layer[0]) / float(layer[1])
             for layer in seen["selection_counts"]],
         "threshold_ties": [int(layer[2])
                            for layer in seen["selection_counts"]]}]


def build(config: dict, traffic: dict, devices, seed: int) -> BuiltMoE:
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.ops.attention import _bwd_plan
    from horovod_tpu.parallel import data_parallel_mesh

    model, moe, kinds = model_of(config)
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    heads, head_dim = config["num_attention_heads"], config["head_dim"]
    depth, sa = config["num_hidden_layers"], config["sa_config"]
    shard = tuple(config["expert_shard"])
    seq, per_chip = traffic["sequence_length"], traffic["batch_per_chip"]
    dtype = dtype_of(config["compute_dtype"])
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    replicated = NamedSharding(mesh, P())
    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"keye_lm builds AdamW, not {config['optimizer']}")
    tx = optax.adamw(config["optimizer"]["learning_rate"])

    def loss_and_rows(params, batch):
        terms, seen = loss_terms_and_rows(model, params, batch)
        del seen["selections"]            # the comparison's, not a counter
        return terms[0] + terms[1], seen

    def loss_fn(params, batch):
        return loss_and_rows(params, batch)[0]

    step = build_train_step(loss_fn, tx, mesh, axis_name=AXIS,
                            batch_spec=(P(AXIS), P(AXIS)))
    seeded = config["initialisation"]

    def init_state():
        def init(key):
            params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
            # Embedding rows at `embedding_std` an element (flax draws them
            # at 1 / sqrt(hidden)): `assumed.initialisation` has why.
            table = params["embed"]["embedding"] * (
                seeded["embedding_std"] * hidden ** 0.5)
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

    def kernel_calls_off_plan(state, pool):
        """The step's own lowered text: every Pallas call this builder's
        layers make, by its name — the selected flash kernels a layer each
        (or, lowered once and called a layer each, once), the score
        product's three and the target pass, and no causal flash call."""
        text = step.lower(state[0], state[1], pool[0]).as_text()
        found = {name: text.count(f'kernel_name = "{name}"')
                 for name in (*planned, *DSA_KERNELS)}
        missing = [name for name in DSA_KERNELS if not found[name]] + [
            name for name, layers in planned.items()
            if bool(found[name]) != bool(layers)]
        return {"name": "kernel_calls_off_plan", "limit": 0.0,
                "value": float(len(missing)), "found": found,
                "missing_or_unplanned": missing}

    def reference_checks(state, pool):
        params = compare.first_device_copy(state[0])
        inputs, targets = compare.first_device_copy(pool[0])
        n = traffic["reference_check"]["grad_batch"]
        batch = (inputs[:n], targets[:n])
        terms, grads_s, seen = system_terms(model, devices)(params, batch)
        against = compare.reference_jit(
            lambda *a: against_reference(reference_config(config), *a))(
            params, batch, grads_s, seen["chosen_experts"],
            seen["selections"])
        del grads_s
        rows = compare_rows(terms, against, seen, bound_rows)
        if devices[0].platform == "tpu":     # interpreted elsewhere: no call
            rows.append(kernel_calls_off_plan(state, pool))
        return rows

    selects = seq > sa["topk"]
    mode = _bwd_plan(seq, head_dim, 1024, 1024, per_chip * heads)[0]
    planned = {name + suffix: layers for suffix, layers in (
        (SUFFIX, depth if selects else 0), ("", 0 if selects else depth))
        for name in FLASH_CALLS[mode]}
    no_more, at_least_one = collectives_expected(devices)
    tokens_per_chip = per_chip * seq
    bound_rows = moe.buffer_rows(tokens_per_chip)
    itemsize = jnp.dtype(dtype).itemsize
    shape = {
        "hidden": hidden, "vocab": vocab, "layers": depth,
        "attention": {"heads": heads,
                      "kv_heads": config["num_key_value_heads"],
                      "head_dim": head_dim},
        "indexer": {"heads": sa["indexer_num_heads"],
                    "head_dim": sa["indexer_head_dim"], "topk": sa["topk"]},
        "experts": {"num_experts": moe.num_experts,
                    "expert_width": moe.expert_width}}
    ops = ops_count_keye.keye_lm_train_ops_per_token(
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
            "flash_selected": ops_count_keye.flash_kernel(
                seq, heads, head_dim, depth, itemsize),
            "dsa_index": ops_count_keye.index_kernel(
                seq, sa["indexer_num_heads"], sa["indexer_head_dim"], depth,
                itemsize),
            "moe_experts": {"hidden": hidden,
                            "expert_width": moe.expert_width,
                            "local_experts": moe.num_experts // shard[1],
                            "itemsize": itemsize}},
        # No collective on one chip, kernels in the program; which kernels
        # is `kernel_calls_off_plan`'s row, by name, and no count of loops.
        program_exactly=no_more,
        program_at_least_one=["tpu_custom_call"] + at_least_one,
        plain_loss_fn=loss_fn, optimizer=tx, has_aux=False,
        reference_checks=reference_checks,
        notes={"flash_backward": mode, "buffer_rows": bound_rows,
               "layers": list(kinds), "expert_shard": list(shard),
               "topk": sa["topk"], "selects": selects},
        expert_rows=expert_rows)
