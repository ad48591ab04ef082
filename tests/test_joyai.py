"""What JoyAI-LLM-Flash adds to models.TransformerLM — latent attention with a
query latent and no output gate, and a multi-token-prediction module that
reads the embedding table and the head a second time — against the plain
float32 reference the benchmark keeps (benchmark/reference/joyai_lm.py): a
plain softmax over whole rows, a loop over the shard's experts, the module
written out.  CPU, float32, seeded weights, the configuration's rehearsal
sizes.

Tolerances: both sides are float32 and differ in the order of their sums
(grouped rows against masked whole batches, blocks of query rows), so they
agree to float32 rounding accumulated over a few layers: 2e-5 of the largest
value, 1e-4 for gradients through every layer.  bfloat16 anywhere would read
1e-3 to 1e-2 and fail every case.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import joyai_lm as builder
from benchmark.reference import joyai_lm as reference
from horovod_tpu.models import (LatentAttention, LatentConfig, MoEConfig,
                                TransformerLM, mtp_next_token_loss,
                                record_mtp_losses)
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import SparseExperts
from tests.test_hybrid import (RTOL, both_ways, close, mixer_case,
                               share_outputs, trees_close, with_highest)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH, WEIGHT = 128, 2, 0.3
HIDDEN, HEADS = 64, 8                 # tests.test_hybrid.mixer_case's width
LATENT = LatentConfig(kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
                      rope_theta=3.2e7, q_rank=24, gate=False)
EXPERTS, PER_TOKEN, WIDTH, SCALE = 256, 8, 48, 2.5


def rehearsal_config(**more):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyaiflash.json")) as f:
        config = json.load(f)
    return dict(config, **config["rehearsal"], **more)


@functools.cache
def case(recompute=False, seed=0):
    """(the model at the rehearsal sizes, its kinds, its expert entries,
    `{"params", "buffers"}` seeded with a selection bias that binds, (inputs,
    targets))."""
    config = rehearsal_config(recompute_layers=recompute)
    model, kinds, expert_layers = builder.model_of(config)

    def make(key):
        keys = jax.random.split(key, 3)
        tokens = jax.random.randint(keys[0], (BATCH, SEQ + 1), 0,
                                    config["vocab_size"])
        state = builder.seeded_state(model, config, expert_layers,
                                     (BATCH, SEQ), keys[1])
        bias = jax.tree.map(
            lambda b: b + 0.2 * jax.random.normal(keys[2], b.shape),
            state["buffers"])
        return dict(state, buffers=bias), (tokens[:, :-1], tokens[:, 1:])

    state, batch = jax.jit(make)(jax.random.PRNGKey(seed))
    return model, config, kinds, expert_layers, state, batch


def system_side(model, state, batch, targets=False):
    """(((L, (L_main, L_mtp)), gradients of the parameters) of `model` from
    one program, by the logits and `mtp_next_token_loss` or under
    `targets=`."""
    def loss(params):
        variables = dict(state, params=params)
        if targets:
            terms = model.apply(variables, batch[0], targets=batch[1])
            return terms[0] + WEIGHT * terms[1], terms
        return mtp_next_token_loss(model.apply(variables, batch[0]),
                                   batch[0], WEIGHT, with_terms=True)

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(state["params"])


@functools.cache
def reference_side(seed=0):
    _, config, kinds, expert_layers, state, batch = case(seed=seed)
    ref = builder.reference_config_of(config, kinds)
    bias = builder.selection_bias(state, expert_layers)
    (loss, (terms, _)), grads = with_highest(jax.value_and_grad(
        lambda p: reference.loss_and_parts(p, batch, selection_bias=bias,
                                           **ref), has_aux=True))(
        state["params"])
    return (loss, terms), grads


# --- the whole model -------------------------------------------------------

def test_both_losses_and_every_gradient_are_the_references():
    """L_main, L_mtp, their weighted sum and the gradient of EVERY parameter
    — the module's, and the table's and the head's, which are the sum of two
    uses' — at the rehearsal sizes, under a selection bias that binds."""
    model, _, _, _, state, batch = case()
    (loss, terms), grads = system_side(model, state, batch)
    (want_loss, want_terms), want_grads = reference_side()
    np.testing.assert_allclose(terms, want_terms, rtol=RTOL)
    np.testing.assert_allclose(loss, want_loss, rtol=RTOL)
    np.testing.assert_allclose(loss, terms[0] + WEIGHT * terms[1], rtol=1e-6)
    assert set(grads) == set(want_grads) and {
        "mtp_0_embed_norm", "mtp_0_state_norm", "mtp_0_proj",
        "mtp_0_layer_0", "mtp_0_layer_1", "mtp_0_final_norm"} <= set(grads)
    trees_close(grads, want_grads, 1e-4)
    # A dropped lambda, or a module left out of the loss, is far outside.
    assert abs(float(terms[0]) / float(want_loss) - 1) > 0.1


@pytest.mark.parametrize("path", ["recompute", "targets",
                                  "recompute_targets"])
def test_recompute_and_targets_give_the_same_losses_and_gradients(path):
    """`recompute=True` reaches the module's block as it reaches the
    pattern's, and `targets=` returns the two means through
    `fused_next_token_loss` (one position left out of L_main, two of L_mtp,
    the targets' last column unread): the losses and gradients of the plain
    path."""
    model, _, _, _, state, batch = case("recompute" in path)
    through = "targets" in path
    if through:      # the last target is not read: any id may stand there
        batch = (batch[0], batch[1].at[:, -1].set(0))
    (loss, terms), grads = system_side(model, state, batch, targets=through)
    plain, _, _, _, _, plain_batch = case()
    (want_loss, want_terms), want_grads = system_side(plain, state,
                                                      plain_batch)
    np.testing.assert_allclose(jnp.stack(terms), jnp.stack(want_terms),
                               rtol=RTOL)
    np.testing.assert_allclose(loss, want_loss, rtol=RTOL)
    trees_close(grads, want_grads, 1e-4)


def test_table_and_head_take_the_sum_of_both_uses_gradients(monkeypatch):
    """ONE `embed` and ONE `lm_head_kernel` in the tree.  With the first and
    the second lookup reading two copies of the table, and the two heads two
    copies of the kernel, each copy's gradient is one use's: the model's
    gradient is their sum, and neither use's is nothing."""
    model, _, _, _, state, batch = case()
    params = state["params"]
    flat = [path for path, _ in jax.tree_util.tree_flatten_with_path(
        params)[0]]
    assert sum("lm_head_kernel" in str(path) for path in flat) == 1
    assert sum("'embed'" in str(path) for path in flat) == 1
    (_, _), grads = system_side(model, state, batch)

    lookup, head = transformer.embedding_lookup, transformer._head_logits

    def apart(tables, kernels):
        used = {"table": 0, "head": 0}

        def one_lookup(table, tokens):
            used["table"] += 1
            return lookup(tables[used["table"] - 1], tokens)

        def one_head(x, w, *rest):
            used["head"] += 1
            return head(x, kernels[used["head"] - 1], *rest)

        monkeypatch.setattr(transformer, "embedding_lookup", one_lookup)
        monkeypatch.setattr(transformer, "_head_logits", one_head)
        loss = mtp_next_token_loss(model.apply(state, batch[0]), batch[0],
                                   WEIGHT)
        assert used == {"table": 2, "head": 2}
        return loss

    table, kernel = params["embed"]["embedding"], params["lm_head_kernel"]
    by_table, by_kernel = jax.grad(apart, (0, 1))((table, table),
                                                  (kernel, kernel))
    monkeypatch.undo()
    for parts, whole in ((by_table, grads["embed"]["embedding"]),
                         (by_kernel, grads["lm_head_kernel"])):
        assert all(float(jnp.abs(part).max()) > 0 for part in parts)
        close(parts[0] + parts[1], whole)
        assert float(jnp.abs(parts[1]).max()) > 1e-3 * float(
            jnp.abs(whole).max())


def test_the_counter_reads_the_two_losses_of_a_targets_pass():
    model, _, _, _, state, batch = case()
    terms, wrote = jax.jit(lambda s, b: model.apply(
        s, b[0], targets=b[1], mutable=["intermediates"]))(state, batch)
    seen = record_mtp_losses(wrote["intermediates"])
    assert seen == {"main": pytest.approx(float(terms[0])),
                    "modules": [pytest.approx(float(terms[1]))]}
    # Seeded weights: both near the logarithm of the vocabulary.
    assert 0.8 < seen["modules"][0] / seen["main"] < 1.2
    # A pass without `targets=`, or a model without modules, sows none.
    _, wrote = model.apply(state, batch[0], mutable=["intermediates"])
    assert record_mtp_losses(wrote["intermediates"]) == {"main": None,
                                                         "modules": []}


def test_two_modules_chain_and_leave_out_one_more_position_each():
    """`mtp=(2, kinds)`: three sets of logits, module 2 behind module 1, the
    means over seq-1, seq-2 and seq-3 positions, by either path."""
    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, d_ff=48, dtype=jnp.float32,
        use_flash=False, layers=("attention", "gated_mlp"),
        mtp=(2, ("attention", "gated_mlp")))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 17), 0, 64)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    params = jax.jit(model.init)(jax.random.PRNGKey(1), inputs)
    assert {"mtp_0_proj", "mtp_1_proj", "mtp_1_layer_1"} <= set(
        params["params"])
    logits = model.apply(params, inputs)
    assert [x.shape for x in logits] == [(2, 16, 64)] * 3
    loss, terms = mtp_next_token_loss(logits, inputs, 0.3, with_terms=True)
    for k, (one, got) in enumerate(zip(logits, terms)):
        logp = jax.nn.log_softmax(one[:, :16 - 1 - k])
        want = -jnp.take_along_axis(
            logp, inputs[:, 1 + k:, None], axis=-1).mean()
        np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(loss, terms[0] + 0.15 * (terms[1] + terms[2]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        jnp.stack(model.apply(params, inputs, targets=targets)),
        jnp.stack(terms), rtol=1e-5)


@pytest.mark.parametrize("how", ["blocks", "loops", "decode_ctx", "noised"])
def test_the_module_is_refused_where_it_cannot_run(how):
    fields = dict(vocab_size=64, d_model=32, n_heads=4, dtype=jnp.float32,
                  use_flash=False, layers=("attention", "gated_mlp"),
                  mtp=(1, ("attention", "gated_mlp")))
    call = {}
    if how == "blocks":
        fields.update(layers=None, n_layers=1)
    elif how == "loops":
        fields["loops"] = 2
    elif how == "noised":
        fields["block_diffusion"] = 4
        call["noised"] = jnp.zeros((1, 8), jnp.int32)
    else:
        call["decode_ctx"] = object()
    with pytest.raises(ValueError, match="mtp= .* per-layer pattern"):
        TransformerLM(**fields).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32), **call)


# --- latent attention ------------------------------------------------------

LING_NAMES = {"q_kernel": (HIDDEN, HEADS, 12), "kv_a_kernel": (HIDDEN, 20),
              "kv_norm_scale": (16,), "kv_b_kernel": (16, HEADS, 16),
              "gate_kernel": (HIDDEN, HEADS), "o_kernel": (HEADS, 8, HIDDEN)}


def test_latent_attention_without_the_new_fields_is_lings_tree():
    """Unset, the query latent and the absent gate change no name and no
    shape of Ling's layer; set, the query's kernel gives way to the latent's
    three and the gate's goes."""
    ling = LatentConfig(16, 8, 4, 8, 6e6)
    assert (ling.q_rank, ling.gate) == (None, True)
    u = jnp.zeros((1, 16, HIDDEN))

    def tree(config):
        return jax.tree.map(lambda x: x.shape, jax.eval_shape(
            LatentAttention(HEADS, config, jnp.float32, use_flash=False).init,
            jax.random.PRNGKey(0), u)["params"])

    assert tree(ling) == LING_NAMES
    want = {name: shape for name, shape in LING_NAMES.items()
            if name not in ("q_kernel", "gate_kernel")}
    want.update(q_a_kernel=(HIDDEN, 24), q_norm_scale=(24,),
                q_b_kernel=(24, HEADS, 12))
    assert tree(LATENT) == want


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("head_shard", [(0, 1), (1, 2)])
def test_latent_attention_with_a_query_latent_is_the_reference(head_shard,
                                                               use_flash):
    layer = LatentAttention(HEADS, LATENT, jnp.float32, use_flash=use_flash,
                            head_shard=head_shard)
    u, params, mix = mixer_case(layer, head_shard[0] + use_flash)
    assert params["q_a_kernel"].shape == (HIDDEN, 24)            # whole
    assert params["q_b_kernel"].shape == (24, HEADS // head_shard[1], 12)
    both_ways(lambda p, u: layer.apply({"params": p}, u),
              lambda p, u: reference.latent_attention(
                  u, p, nope_dim=LATENT.nope_dim,
                  rope_theta=LATENT.rope_theta, norm_eps=1e-6),
              u, params, mix)


# --- the shares add up to the uncut layer ----------------------------------

@pytest.mark.parametrize("n", [2, 8])
def test_query_latent_tensor_shares_add_up_with_both_latents_counted_once(n):
    """Every share holds the whole `W_qa`, `W_kva` and the two latents'
    norms; the heads' slices of `W_qb`, `W_kvb` and `W_o` partition, and the
    n outputs sum to the uncut layer's."""
    whole = LatentAttention(HEADS, LATENT, jnp.float32, use_flash=False)
    u, params, _ = mixer_case(whole, n)
    local = HEADS // n

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, q_b_kernel=params["q_b_kernel"][:, held],
                    kv_b_kernel=params["kv_b_kernel"][:, held],
                    o_kernel=params["o_kernel"][held])

    parts = share_outputs(
        n, lambda i: LatentAttention(HEADS, LATENT, jnp.float32,
                                     use_flash=False, head_shard=(i, n)),
        share, params, u)
    close(sum(parts), with_highest(reference.latent_attention)(
        u, params, nope_dim=LATENT.nope_dim, rope_theta=LATENT.rope_theta,
        norm_eps=1e-6))


def test_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """JoyAI's expert layer at its counts — 256 sigmoid-routed experts, 8 a
    token, one group, one shared expert — as the deployment's 16 shares of 16:
    each share's output holds the shared expert, so their sum holds it 16
    times and the routed part once; with it counted once the sum is the
    uncut reference's layer."""
    n = 16

    def moe(shard=(0, 1)):
        return MoEConfig(EXPERTS, PER_TOKEN, WIDTH, shard, None, "sigmoid",
                         True, SCALE, shared_width=WIDTH)

    whole = SparseExperts(moe(), jnp.float32)
    u, params, _ = mixer_case(whole, n)
    local = EXPERTS // n

    def share(params, i):
        held = slice(i * local, (i + 1) * local)
        return dict(params, **{name: params[name][held] for name in (
            "gate_kernel", "up_kernel", "down_kernel")})

    parts = share_outputs(n, lambda i: SparseExperts(moe((i, n)),
                                                     jnp.float32),
                          share, params, u)
    flat = u.reshape(-1, HIDDEN)
    shared = reference.gated_mlp(flat, *(params[name]["kernel"] for name in (
        "shared_gate", "shared_up", "shared_down"))).reshape(u.shape)
    want = with_highest(reference.sparse_experts)(
        flat, params, num_experts=EXPERTS, expert_shard=(0, 1),
        experts_per_token=PER_TOKEN, weight_scale=SCALE)[0]
    close(sum(part - shared for part in parts) + shared,
          want.reshape(u.shape))
