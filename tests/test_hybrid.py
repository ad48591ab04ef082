"""The per-layer pattern of models.TransformerLM (Nemotron-3's layers: Mamba-2,
grouped-query attention without rotary, latent sparse experts) against the
plain float32 reference the benchmark keeps (benchmark/reference/hybrid_lm.py):
the recurrence one step a token, a loop over the shard's experts, a plain
softmax.  CPU, float32, seeded weights, small sizes.

Tolerances: both sides are float32 and differ in the order of their sums
(products over chunks against a step a token, grouped rows against masked
whole batches), so they agree to float32 rounding accumulated over a few
layers: 2e-5 of the largest value (the chunked scan, whose decays span many
orders of magnitude, 1e-4).  bfloat16 anywhere would read 1e-3 to 1e-2 and
fail every case.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.reference import hybrid_lm as reference
from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models import (Mamba2Config, Mamba2Mixer, MoEConfig,
                                TransformerLM, next_token_loss)
from horovod_tpu.models.transformer import Attention, SparseExperts
from horovod_tpu.ops.ssm import chunked_scan

RTOL = 2e-5
VOCAB, HIDDEN, SEQ = 256, 64, 128
HEADS, KV_HEADS = 8, 2                        # attention: head 8
SSM = Mamba2Config(heads=8, head_dim=8, groups=4, state=16, conv=4, chunk=32)
EXPERTS, PER_TOKEN, WIDTH, LATENT, SHARED, SCALE = 16, 4, 48, 32, 96, 2.5
LAYERS = ("ssm", "experts", "ssm", "attention", "experts")


def moe(shard=(0, 1), row_bound=None, experts=EXPERTS):
    return MoEConfig(experts, PER_TOKEN, WIDTH, shard, row_bound, "sigmoid",
                     True, SCALE, "relu2", LATENT, SHARED)


def lm(expert_shard=(0, 1), head_shard=(0, 1), use_flash=False, vocab=VOCAB,
       chunk=SSM.chunk):
    return TransformerLM(
        vocab_size=vocab, d_model=HIDDEN, n_heads=HEADS, dtype=jnp.float32,
        use_flash=use_flash, norm_eps=1e-5, moe=moe(expert_shard),
        layers=LAYERS, ssm=SSM._replace(chunk=chunk), n_kv_heads=KV_HEADS,
        rope=False, head_shard=head_shard)


def reference_config(expert_shard=(0, 1), **more):
    return dict(layers=LAYERS, ssm_head_dim=SSM.head_dim, ssm_state=SSM.state,
                norm_eps=1e-5, num_experts=EXPERTS,
                experts_per_token=PER_TOKEN, expert_shard=expert_shard,
                weight_scale=SCALE, **more)


def spread(params, seed=1):
    """Norm scales, D and biases away from their seeded one and zero, so
    that one left out or misplaced shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([
        leaf + 0.3 * jax.random.normal(key, leaf.shape) if leaf.ndim == 1
        else leaf for leaf, key in zip(leaves, keys)])


def close(got, want, rtol=RTOL):
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=rtol * scale, rtol=0)


def trees_close(got, want, rtol=RTOL):
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(got)[0]:
        close(leaf, flat_want[path], rtol)


def with_highest(fn):
    """`fn` jitted, every float32 product in full precision."""
    def call(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: fn(*a, **kwargs))(*args)
    return call


# --- the scan ------------------------------------------------------------

def scan_inputs(seed, groups=4, seq=SEQ):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    heads, head_dim, state = 8, 8, 16
    x = jax.random.normal(keys[0], (2, seq, heads, head_dim))
    # Steps from 1e-3 to 1: decays of a chunk from nearly one to e^-500.
    dt = jnp.exp(jax.random.uniform(keys[1], (2, seq, heads), minval=-7.0,
                                    maxval=0.0))
    A = -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=16.0)
    B = jax.random.normal(keys[3], (2, seq, groups, state))
    C = jax.random.normal(keys[4], (2, seq, groups, state))
    D = jax.random.normal(keys[5], (heads,))
    mix = jax.random.normal(keys[6], x.shape)
    return (x, dt, A, B, C, D), mix


@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_chunked_scan_is_the_recurrence(chunk, groups):
    args, _ = scan_inputs(chunk + groups, groups)
    got, decay_min = jax.jit(lambda *a: chunked_scan(*a, chunk))(*args)
    close(got, jax.jit(reference.recurrence)(*args), 1e-4)
    x, dt, A = args[:3]
    summed = (dt * A).reshape(2, SEQ // chunk, chunk, -1).sum(axis=2)
    close(decay_min, summed.min())


@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_scan_gradients_are_the_recurrences(chunk):
    args, mix = scan_inputs(7 + chunk)

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    got = jax.jit(jax.grad(total(lambda *a: chunked_scan(*a, chunk)[0]),
                           argnums=range(6)))(*args)
    want = jax.jit(jax.grad(total(reference.recurrence),
                            argnums=range(6)))(*args)
    for g, w in zip(got, want):
        close(g, w, 1e-4)


def test_chunked_scan_refuses_a_ragged_length():
    args, _ = scan_inputs(0, seq=96)
    with pytest.raises(ValueError, match="multiple"):
        chunked_scan(*args, 64)


# --- each mixer against the reference's ---------------------------------

def mixer_case(module, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(keys[0], (2, SEQ, HIDDEN))
    params = spread(module.init(keys[1], u)["params"], seed)
    mix = jax.random.normal(keys[2], u.shape)
    return u, params, mix


def both_ways(system, plain, u, params, mix, rtol=RTOL):
    """Values and gradients (input and parameters) of `system(params, u)`
    against `plain(params, u)`."""
    close(jax.jit(system)(params, u), with_highest(plain)(params, u), rtol)

    def total(fn):
        return lambda p, u: (fn(p, u) * mix).sum()

    got = jax.jit(jax.grad(total(system), (0, 1)))(params, u)
    want = with_highest(jax.grad(total(plain), (0, 1)))(params, u)
    trees_close(got, want, rtol)


@pytest.mark.parametrize("head_shard", [(0, 1), (1, 2), (3, 4)])
@pytest.mark.parametrize("chunk", [16, 64])
def test_mamba2_mixer_is_the_reference(chunk, head_shard):
    mixer = Mamba2Mixer(*SSM._replace(chunk=chunk), head_shard=head_shard,
                        dtype=jnp.float32, norm_eps=1e-5)
    u, params, mix = mixer_case(mixer, chunk)
    both_ways(lambda p, u: mixer.apply({"params": p}, u),
              lambda p, u: reference.mamba2(
                  u, p, head_dim=SSM.head_dim, state=SSM.state,
                  norm_eps=1e-5), u, params, mix, 1e-4)


def test_mamba2_mixer_writes_its_chunks_decay():
    mixer = Mamba2Mixer(*SSM, dtype=jnp.float32)
    u, params, _ = mixer_case(mixer)
    _, wrote = mixer.apply({"params": params}, u, mutable=["intermediates"])
    (decay,) = wrote["intermediates"]["ssm_chunk_log_decay_min"]
    assert decay.shape == () and -1e4 < float(decay) < 0


def plain_softmax_attention(p, u):
    """Query head j against key/value head j // (heads / kv heads), a plain
    softmax over the keys up to the query's, no position embedding."""
    q = jnp.einsum("bsd,dhe->bhse", u, p["q_kernel"])
    k, v = jnp.einsum("bsd,djhe->jbhse", u, p["kv_kernel"])
    group = q.shape[1] // k.shape[1]
    outs = []
    for j in range(q.shape[1]):
        scores = q[:, j] @ k[:, j // group].swapaxes(-1, -2) \
            * q.shape[-1] ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), scores,
                           -jnp.inf)
        outs.append(jax.nn.softmax(scores, axis=-1) @ v[:, j // group])
    return jnp.einsum("hbse,hed->bsd", jnp.stack(outs), p["o_kernel"])


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("kv_heads,head_shard", [(2, (0, 1)), (2, (1, 2)),
                                                 (2, (3, 4)), (8, (1, 4)),
                                                 (1, (0, 1))])
def test_grouped_query_attention_without_rotary(kv_heads, head_shard,
                                                use_flash):
    layer = Attention(HEADS, jnp.float32, use_flash=use_flash,
                      n_kv_heads=kv_heads, rope=False, head_shard=head_shard)
    u, params, mix = mixer_case(layer, kv_heads)
    local = HEADS // head_shard[1]
    assert params["q_kernel"].shape == (HIDDEN, local, HIDDEN // HEADS)
    assert params["kv_kernel"].shape[2] == max(1, kv_heads // head_shard[1])
    both_ways(lambda p, u: layer.apply({"params": p}, u),
              plain_softmax_attention, u, params, mix)
    close(with_highest(reference.grouped_query_attention)(u, params),
          with_highest(plain_softmax_attention)(params, u))


def test_rotary_is_on_by_default_and_off_when_asked():
    u = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, HIDDEN))
    on, off = (Attention(HEADS, jnp.float32, use_flash=False, n_kv_heads=2,
                         rope=flag) for flag in (True, False))
    params = on.init(jax.random.PRNGKey(1), u)["params"]
    assert float(jnp.abs(on.apply({"params": params}, u)
                         - off.apply({"params": params}, u)).max()) > 1e-3


def test_attention_refuses_a_share_that_does_not_divide():
    u = jnp.zeros((1, SEQ, HIDDEN))
    with pytest.raises(ValueError, match="head_shard"):
        Attention(HEADS, n_kv_heads=2, head_shard=(0, 3)).init(
            jax.random.PRNGKey(0), u)
    with pytest.raises(ValueError, match="head_shard"):
        Mamba2Mixer(*SSM, head_shard=(0, 8)).init(jax.random.PRNGKey(0), u)


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("shard", [(0, 1), (0, 4), (3, 4)])
def test_latent_experts_are_the_dense_loop(shard, bias):
    layer = SparseExperts(moe(shard), jnp.float32)
    u, params, mix = mixer_case(layer, shard[0] + bias)
    buffers = {"selection_bias": 0.2 * jax.random.normal(
        jax.random.PRNGKey(5), (EXPERTS,))} if bias else {}
    config = dict(num_experts=EXPERTS, experts_per_token=PER_TOKEN,
                  expert_shard=shard, weight_scale=SCALE,
                  selection_bias=buffers.get("selection_bias"))

    def system(p, u):
        return layer.apply({"params": p, "buffers": buffers}, u)

    def plain(p, u):
        return reference.latent_experts(u.reshape(-1, HIDDEN), p,
                                        **config)[0].reshape(u.shape)

    both_ways(system, plain, u, params, mix)
    _, wrote = layer.apply({"params": params, "buffers": buffers}, u,
                           mutable=["intermediates"])
    chose = wrote["intermediates"]["chosen_experts"][0]
    want = reference.latent_experts(u.reshape(-1, HIDDEN), params,
                                    **config)[1]
    np.testing.assert_array_equal(jnp.sort(chose, -1), jnp.sort(want, -1))
    if bias:     # the bias moves choices and never the weights
        unbiased = layer.apply({"params": params}, u,
                               mutable=["intermediates"])[1]
        assert (jnp.sort(unbiased["intermediates"]["chosen_experts"][0], -1)
                != jnp.sort(chose, -1)).any()


def test_latent_experts_count_rows_over_a_bound():
    layer = SparseExperts(moe(row_bound=0.25), jnp.float32)
    u, params, _ = mixer_case(layer)
    _, wrote = layer.apply({"params": params}, u, mutable=["intermediates"])
    routed = int(wrote["intermediates"]["rows_per_local_expert"][0].sum())
    bound = moe(row_bound=0.25).buffer_rows(2 * SEQ)
    assert routed > bound
    assert int(wrote["intermediates"]["rows_over_bound"][0]) == routed - bound


@pytest.mark.parametrize("field,value", [("scoring", "tanh"),
                                         ("expert_act", "gelu")])
def test_sparse_experts_refuse_an_unknown_choice(field, value):
    layer = SparseExperts(moe()._replace(**{field: value}), jnp.float32)
    with pytest.raises(ValueError, match="unknown"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, SEQ, HIDDEN)))


# --- the whole model ----------------------------------------------------

def seeded(model, seed=0, batch=2, vocab=VOCAB):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    tokens = jax.random.randint(keys[0], (batch, SEQ + 1), 0, vocab)
    params = spread(model.init(keys[1], tokens[:, :-1])["params"], seed)
    return params, (tokens[:, :-1], tokens[:, 1:])


def system_loss(model, params, batch):
    return next_token_loss(model.apply({"params": params}, batch[0]),
                           batch[1])


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("expert_shard,head_shard",
                         [((0, 1), (0, 1)), ((1, 4), (1, 2))])
def test_hybrid_lm_loss_and_gradients_are_the_references(expert_shard,
                                                         head_shard, chunk):
    model = lm(expert_shard, head_shard, chunk=chunk)
    params, batch = seeded(model, seed=chunk)
    config = reference_config(expert_shard)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p: system_loss(model, p, batch)))(params)
    want, want_grads = with_highest(jax.value_and_grad(
        lambda p: reference.loss(p, batch, **config)))(params)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    trees_close(got_grads, want_grads, 1e-4)
    _, wrote = model.apply({"params": params}, batch[0],
                           mutable=["intermediates"])
    chose = jnp.stack([wrote["intermediates"][f"layer_{i}"]["mixer"][
        "chosen_experts"][0] for i, kind in enumerate(LAYERS)
        if kind == "experts"])
    want = with_highest(reference.chosen_experts)(params, batch[0], **config)
    np.testing.assert_array_equal(jnp.sort(chose, -1), jnp.sort(want, -1))


def test_reference_refuses_float8_operands():
    """The reference against itself with every matmul operand rounded to
    float8_e4m3fn: the error the benchmark's limits must refuse is far over
    what float32 reorderings give above."""
    model = lm()
    params, batch = seeded(model)
    losses = [with_highest(jax.value_and_grad(lambda p: reference.loss(
        p, batch, operand_dtype=dtype, **reference_config())))(params)
        for dtype in (None, jnp.float8_e4m3fn)]
    norm = optax.global_norm
    wrong = norm(jax.tree.map(jnp.subtract, losses[1][1], losses[0][1]))
    assert float(wrong / norm(losses[0][1])) > 0.05


def test_pattern_has_one_norm_and_one_mixer_a_layer():
    shapes = jax.eval_shape(lambda: lm((0, 4), (0, 2)).init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32))["params"])
    assert set(shapes) == {"embed", "final_norm", "lm_head_kernel"} | {
        f"layer_{i}" for i in range(len(LAYERS))}
    mixers = {"ssm": {"A_log", "D", "conv_bias", "conv_kernel", "dt_bias",
                      "in_proj_kernel", "norm_scale", "out_proj_kernel"},
              "attention": {"q_kernel", "kv_kernel", "o_kernel"},
              "experts": {"router_kernel", "up_kernel", "down_kernel",
                          "latent_down", "latent_up", "shared_up",
                          "shared_down"}}
    for i, kind in enumerate(LAYERS):
        assert set(shapes[f"layer_{i}"]) == {"norm", "mixer"}
        assert set(shapes[f"layer_{i}"]["mixer"]) == mixers[kind]
    # The share: 4 of 8 heads in 2 of 4 groups, 4 of 16 experts.
    inner, bc = 4 * SSM.head_dim, 2 * SSM.state
    assert shapes["layer_0"]["mixer"]["in_proj_kernel"].shape == (
        HIDDEN, 2 * inner + 2 * bc + 4)
    assert shapes["layer_1"]["mixer"]["up_kernel"].shape == (4, LATENT, WIDTH)
    assert shapes["layer_1"]["mixer"]["router_kernel"].shape == (HIDDEN,
                                                                 EXPERTS)


@pytest.mark.parametrize("how", ["decode_ctx", "seq_axis", "kind"])
def test_pattern_refuses_what_it_cannot_run(how):
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    if how == "kind":
        with pytest.raises(ValueError, match="layer kind"):
            TransformerLM(vocab_size=VOCAB, d_model=HIDDEN, n_heads=HEADS,
                          layers=("mlp",)).init(jax.random.PRNGKey(0), tokens)
        return
    model = TransformerLM(
        vocab_size=VOCAB, d_model=HIDDEN, n_heads=HEADS, layers=LAYERS,
        ssm=SSM, moe=moe(), seq_axis="sp" if how == "seq_axis" else None)
    with pytest.raises(ValueError, match="per-layer pattern"):
        model.init(jax.random.PRNGKey(0), tokens,
                   decode_ctx=object() if how == "decode_ctx" else None)


def test_trains_through_build_train_step_and_replicas_stay_equal():
    """Two CPU devices, data parallel: the dense LM's step with the pattern.
    The replicated weights stay equal and the loss of a repeated batch
    falls.  The flash kernel (interpreted here), as in the benchmark."""
    model = lm((0, 4), (0, 2), use_flash=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))
    params, batch = seeded(model, seed=3)
    tx = optax.adamw(1e-2)
    step = build_train_step(lambda p, b: system_loss(model, p, b), tx, mesh,
                            axis_name="hvd", batch_spec=(P("hvd"), P("hvd")))
    state = (params, tx.init(params))
    losses = []
    for _ in range(4):
        *state, loss = step(*state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    for leaf in jax.tree.leaves(state[0]):
        first, second = (np.asarray(s.data) for s in leaf.addressable_shards)
        np.testing.assert_array_equal(first, second)


# --- the shares add up to the uncut layer ---------------------------------

def columns(kernel, blocks, shard, n):
    """`kernel`'s last axis is consecutive blocks of the given widths; the
    `shard`-th of `n` equal parts of each, concatenated."""
    parts, start = [], 0
    for width in blocks:
        part = width // n
        parts.append(kernel[..., start + shard * part:
                            start + (shard + 1) * part])
        start += width
    return jnp.concatenate(parts, axis=-1)


def mamba2_share(p, shard, n, ssm):
    inner, bc = ssm.heads * ssm.head_dim, ssm.groups * ssm.state
    conv = [inner, bc, bc]

    def heads(v):
        return columns(v, [ssm.heads], shard, n)

    return {"in_proj_kernel": columns(p["in_proj_kernel"],
                                      [inner] + conv + [ssm.heads], shard, n),
            "conv_kernel": columns(p["conv_kernel"], conv, shard, n),
            "conv_bias": columns(p["conv_bias"], conv, shard, n),
            "dt_bias": heads(p["dt_bias"]), "A_log": heads(p["A_log"]),
            "D": heads(p["D"]),
            "norm_scale": columns(p["norm_scale"], [inner], shard, n),
            "out_proj_kernel": columns(p["out_proj_kernel"].T, [inner],
                                       shard, n).T}


@pytest.mark.parametrize("n,groups", [(2, 4), (4, 4), (8, 8)])
def test_mamba2_tensor_shares_add_up_to_the_uncut_layer(n, groups):
    ssm = SSM._replace(groups=groups)
    whole = Mamba2Mixer(*ssm, dtype=jnp.float32, norm_eps=1e-5)
    u, params, _ = mixer_case(whole, n)
    parts = [jax.jit(Mamba2Mixer(*ssm, head_shard=(i, n), dtype=jnp.float32,
                                 norm_eps=1e-5).apply)(
        {"params": mamba2_share(params, i, n, ssm)}, u) for i in range(n)]
    close(sum(parts), with_highest(reference.mamba2)(
        u, params, head_dim=ssm.head_dim, state=ssm.state, norm_eps=1e-5),
        1e-4)


@pytest.mark.parametrize("n", [2, 8])
def test_attention_tensor_shares_add_up_to_the_uncut_layer(n):
    whole = Attention(HEADS, jnp.float32, use_flash=False,
                      n_kv_heads=KV_HEADS, rope=False)
    u, params, _ = mixer_case(whole, n)
    local, group = HEADS // n, HEADS // KV_HEADS
    parts = []
    for i in range(n):
        kv = slice(i * local // group, max(i * local // group + 1,
                                           (i + 1) * local // group))
        share = {"q_kernel": params["q_kernel"][:, i * local:(i + 1) * local],
                 "kv_kernel": params["kv_kernel"][:, :, kv],
                 "o_kernel": params["o_kernel"][i * local:(i + 1) * local]}
        parts.append(jax.jit(Attention(
            HEADS, jnp.float32, use_flash=False, n_kv_heads=KV_HEADS,
            rope=False, head_shard=(i, n)).apply)({"params": share}, u))
    close(sum(parts), with_highest(reference.grouped_query_attention)(
        u, params))


@pytest.mark.parametrize("n,experts", [(4, EXPERTS), (16, EXPERTS),
                                       (64, 128)])
def test_expert_shares_add_up_with_what_every_chip_computes_counted_once(
        n, experts):
    """The n shares' outputs each hold the shared expert, and (the projection
    up being linear) their sum holds it n times and the routed part once.
    64 shares of 2 experts: the deployment's count."""
    whole = SparseExperts(moe(experts=experts), jnp.float32)
    u, params, _ = mixer_case(whole, n)
    local = experts // n
    parts = []
    for i in range(n):
        held = slice(i * local, (i + 1) * local)
        share = dict(params, up_kernel=params["up_kernel"][held],
                     down_kernel=params["down_kernel"][held])
        parts.append(jax.jit(SparseExperts(moe((i, n), experts=experts),
                                           jnp.float32).apply)(
            {"params": share}, u))
    flat = u.reshape(-1, HIDDEN)
    shared = reference.relu2(flat @ params["shared_up"]["kernel"]) \
        @ params["shared_down"]["kernel"]
    want = with_highest(reference.latent_experts)(
        flat, params, num_experts=experts, experts_per_token=PER_TOKEN,
        expert_shard=(0, 1), weight_scale=SCALE)[0]
    shared = shared.reshape(u.shape)
    # Each share less the shared expert, summed, and the shared expert once
    # (the same sum in the order that does not cancel n large terms).
    close(sum(part - shared for part in parts) + shared,
          want.reshape(u.shape))


@pytest.mark.parametrize("n", [2, 8])
def test_vocabulary_slices_concatenate_to_the_uncut_head(n):
    """A sliced vocabulary is a smaller vocabulary: the i-th slice's model —
    its rows of the embedding, its columns of the head — gives, for ids of
    the slice, the uncut model's logits of those columns."""
    model = lm()
    params, _ = seeded(model)
    rows = VOCAB // n
    logits = []
    whole, sliced = jax.jit(model.apply), jax.jit(lm(vocab=rows).apply)
    for i in range(n):
        ids = jax.random.randint(jax.random.PRNGKey(9), (1, SEQ), 0, rows)
        held = slice(i * rows, (i + 1) * rows)
        share = dict(params,
                     embed={"embedding": params["embed"]["embedding"][held]},
                     lm_head_kernel=params["lm_head_kernel"][:, held])
        got = sliced({"params": share}, ids)
        want = whole({"params": params}, ids + i * rows)
        close(got, want[..., held])
        logits.append(want[..., held].shape[-1])
    assert sum(logits) == VOCAB
