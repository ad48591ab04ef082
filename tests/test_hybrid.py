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

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.reference import hybrid_lm as reference
from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models import (Mamba2Config, Mamba2Mixer, MoEConfig,
                                TransformerLM, next_token_loss)
from horovod_tpu.models.ssm import (L2_EPS, causal_depthwise_conv,
                                    mixer_opening)
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


@jax.jit
def relative_error(got, want):
    """The norm of two trees' difference over the norm of the second."""
    return optax.global_norm(jax.tree.map(jnp.subtract, got, want)) \
        / optax.global_norm(want)


def with_highest(fn):
    """`fn` jitted, every float32 product in full precision.  Calls without
    keywords share one jitted function: kept, it compiles once a shape."""
    jitted = jax.jit(fn)

    def call(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            if kwargs:
                return jax.jit(lambda *a: fn(*a, **kwargs))(*args)
            return jitted(*args)
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
    got, whole = jax.jit(lambda *a: chunked_scan(*a, chunk))(*args)
    close(got, jax.jit(reference.recurrence)(*args), 1e-4)
    x, dt, A = args[:3]
    summed = (dt * A).reshape(2, SEQ // chunk, chunk, -1).sum(axis=2)
    close(whole.reshape(summed.shape), summed, 1e-5)


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
    """(an input, `module`'s seeded parameters spread, a cotangent), made in
    one program: op by op every primitive of `init` compiles alone."""
    def make(key):
        keys = jax.random.split(key, 3)
        u = jax.random.normal(keys[0], (2, SEQ, HIDDEN))
        params = spread(module.init(keys[1], u)["params"], seed)
        return u, params, jax.random.normal(keys[2], u.shape)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def sown(module, variables, u):
    """What `module` sows into `intermediates` on `u`, from one program."""
    return jax.jit(lambda v, u: module.apply(
        v, u, mutable=["intermediates"])[1]["intermediates"])(variables, u)


def both_ways(system, plain, u, params, mix, rtol=RTOL):
    """Values and gradients (input and parameters) of `system(params, u)`
    against `plain(params, u)`: one program a side."""
    def total(fn):
        def summed(p, u):
            out = fn(p, u)
            return (out * mix).sum(), out
        return jax.value_and_grad(summed, (0, 1), has_aux=True)

    (_, got), got_grads = jax.jit(total(system))(params, u)
    (_, want), want_grads = with_highest(total(plain))(params, u)
    close(got, want, rtol)
    trees_close(got_grads, want_grads, rtol)


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
    (decay,) = sown(mixer, {"params": params}, u)["ssm_chunk_log_decay_min"]
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
    chose = sown(layer, {"params": params, "buffers": buffers}, u)[
        "chosen_experts"][0]
    want = with_highest(reference.latent_experts)(
        u.reshape(-1, HIDDEN), params, **config)[1]
    np.testing.assert_array_equal(jnp.sort(chose, -1), jnp.sort(want, -1))
    if bias:     # the bias moves choices and never the weights
        unbiased = sown(layer, {"params": params}, u)["chosen_experts"][0]
        assert (jnp.sort(unbiased, -1) != jnp.sort(chose, -1)).any()


def test_latent_experts_count_rows_over_a_bound():
    layer = SparseExperts(moe(row_bound=0.25), jnp.float32)
    u, params, _ = mixer_case(layer)
    wrote = sown(layer, {"params": params}, u)
    routed = int(wrote["rows_per_local_expert"][0].sum())
    bound = moe(row_bound=0.25).buffer_rows(2 * SEQ)
    assert routed > bound
    assert int(wrote["rows_over_bound"][0]) == routed - bound


@pytest.mark.parametrize("field,value", [("scoring", "tanh"),
                                         ("expert_act", "gelu")])
def test_sparse_experts_refuse_an_unknown_choice(field, value):
    layer = SparseExperts(moe()._replace(**{field: value}), jnp.float32)
    with pytest.raises(ValueError, match="unknown"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, SEQ, HIDDEN)))


# --- the whole model ----------------------------------------------------

def seeded(model, seed=0, batch=2, vocab=VOCAB):
    """(`model`'s seeded parameters spread, (inputs, targets)), made in one
    program."""
    def make(key):
        keys = jax.random.split(key, 2)
        tokens = jax.random.randint(keys[0], (batch, SEQ + 1), 0, vocab)
        params = spread(model.init(keys[1], tokens[:, :-1])["params"], seed)
        return params, (tokens[:, :-1], tokens[:, 1:])

    return jax.jit(make)(jax.random.PRNGKey(seed))


def vocabulary_slices_concatenate(lm, n, vocab=VOCAB):
    """A sliced vocabulary is a smaller vocabulary: the i-th of `n` slices'
    model — its rows of the embedding, its columns of the head — gives, for
    ids of the slice, the uncut `lm()`'s logits of those columns."""
    model = lm()
    params, _ = seeded(model)
    rows = vocab // n
    whole, sliced = jax.jit(model.apply), jax.jit(lm(vocab=rows).apply)
    width = 0
    for i in range(n):
        ids = jax.random.randint(jax.random.PRNGKey(9), (1, SEQ), 0, rows)
        held = slice(i * rows, (i + 1) * rows)
        share = dict(params,
                     embed={"embedding": params["embed"]["embedding"][held]},
                     lm_head_kernel=params["lm_head_kernel"][:, held])
        got = sliced({"params": share}, ids)
        want = whole({"params": params}, ids + i * rows)
        close(got, want[..., held])
        width += got.shape[-1]
    assert width == vocab


def trains_and_replicas_stay_equal(model, params, batch, loss=None):
    """Two CPU devices, data parallel, `model`'s step through
    `build_train_step`: the loss of a repeated batch falls and the replicated
    weights stay equal.  Returns the four losses."""
    loss = loss or system_loss
    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))
    tx = optax.adamw(1e-2)
    step = build_train_step(lambda p, b: loss(model, p, b), tx, mesh,
                            axis_name="hvd",
                            batch_spec=(P("hvd"),) * len(batch))
    state = (params, tx.init(params))
    losses = []
    for _ in range(4):
        *state, value = step(*state, batch)
        losses.append(float(value))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    for leaf in jax.tree.leaves(state[0]):
        first, second = (np.asarray(s.data) for s in leaf.addressable_shards)
        np.testing.assert_array_equal(first, second)
    return losses


def system_loss(model, params, batch):
    return next_token_loss(model.apply({"params": params}, batch[0]),
                           batch[1])


def system_side(model, params, batch, loss=next_token_loss):
    """((the loss, the experts every expert layer chose), every gradient) of
    `model` from one program: `loss(logits, *batch[1:])` of
    `model.apply(params, batch[0])`."""
    def loss_and_chosen(params):
        logits, wrote = model.apply({"params": params}, batch[0],
                                    mutable=["intermediates"])
        chose = jnp.stack([wrote["intermediates"][f"layer_{i}"]["mixer"][
            "chosen_experts"][0] for i, kind in enumerate(model.layers)
            if kind == "experts"])
        return loss(logits, *batch[1:]), chose

    return jax.jit(jax.value_and_grad(loss_and_chosen, has_aux=True))(params)


def reference_sides(reference_config, loss_and_chosen):
    """`side(expert_shard, **more)(params, batch)`: a reference's ((loss,
    chosen experts), gradients) under `reference_config(expert_shard,
    **more)`, one program, every product in full precision.  One jitted
    function a configuration is kept, so cases that differ in the system
    alone compile the reference once."""
    @functools.cache
    def side(expert_shard=(0, 1), **more):
        config = reference_config(expert_shard, **more)
        return with_highest(jax.value_and_grad(
            lambda p, batch: loss_and_chosen(p, batch, **config),
            has_aux=True))
    return side


reference_side = reference_sides(
    reference_config, lambda p, batch, **config: (
        reference.loss(p, batch, **config),
        reference.chosen_experts(p, batch[0], **config)))


def sides_agree(system, plain, grads_rtol=1e-4):
    (got, chose), got_grads = system
    (want, want_chose), want_grads = plain
    np.testing.assert_allclose(got, want, rtol=RTOL)
    trees_close(got_grads, want_grads, grads_rtol)
    np.testing.assert_array_equal(jnp.sort(chose, -1),
                                  jnp.sort(want_chose, -1))


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


def share_outputs(n, layer_of, share_of, params, u):
    """The `n` shares' outputs, `layer_of(i)` on `share_of(params, i)`, from
    one program (a program a share is n compiles of the same few layers)."""
    return jax.jit(lambda params, u: [
        layer_of(i).apply({"params": share_of(params, i)}, u)
        for i in range(n)])(params, u)


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


# ---------------------------------------------------------------------------
# The recurrent mixers' opening stage (`models.ssm.mixer_opening`) against
# the composition the mixers ran before it: convolution, SiLU, split,
# reshape, the unit norms, one rounding.
# ---------------------------------------------------------------------------

# Each caller's form: Mamba-2's (a bias, no norm; heads narrower than a
# register's lanes), Gated DeltaNet's at Qwen3-Next's heads (16 key and 32
# value heads of 128), the channel gate's (4 and 4).
OPENINGS = {
    "mamba2": (((8, 8, None), (4, 16, None), (4, 16, None)), True),
    "gdn": (((16, 128, 128 ** -0.5), (16, 128, 1.0), (32, 128, None)),
            False),
    "kda": (((4, 16, 16 ** -0.5), (4, 16, 1.0), (4, 16, None)), False),
}


def composed_opening(x, taps, bias, parts, dtype):
    """`DeltaMixer`'s and `Mamba2Mixer`'s stage as plain autodiff saw it."""
    active = nn.silu(causal_depthwise_conv(x, taps, bias))
    edges = np.cumsum([heads * width for heads, width, _ in parts])[:-1]
    outputs = []
    for t, (heads, width, unit) in zip(
            jnp.split(active, edges.tolist(), axis=-1), parts):
        t = t.reshape(t.shape[:2] + (heads, width))
        if unit is not None:
            t = t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                              + L2_EPS) * unit
        outputs.append(t.astype(dtype))
    return tuple(outputs)


def opening_case(form, seq, dtype, batch=2):
    parts, has_bias = OPENINGS[form]
    channels = sum(heads * width for heads, width, _ in parts)
    keys = jax.random.split(jax.random.PRNGKey(seq), 3 + len(parts))
    x = jax.random.normal(keys[0], (batch, seq, channels)).astype(dtype)
    taps = 0.5 * jax.random.normal(keys[1], (4, channels))
    bias = 0.1 * jax.random.normal(keys[2], (channels,)) if has_bias else None
    cotangents = tuple(
        jax.random.normal(key, (batch, seq, heads, width)).astype(dtype)
        for key, (heads, width, _) in zip(keys[3:], parts))
    return parts, (x, taps, bias), cotangents


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seq", [3, 4, 13])   # under, at, off the taps' 4
@pytest.mark.parametrize("form", list(OPENINGS))
def test_mixer_opening_is_the_composition_to_the_bit(form, seq, dtype):
    """One primitive at a time, so that both sides are the arithmetic they
    are written as: compiled whole, XLA's CPU backend contracts products and
    sums differently in differently fused programs."""
    parts, operands, _ = opening_case(form, seq, dtype)
    with jax.disable_jit():
        ours = mixer_opening(*operands, parts)
        theirs = composed_opening(*operands, parts, dtype)
    assert len(ours) == len(parts)
    for one, two, (heads, width, _) in zip(ours, theirs, parts):
        assert one.dtype == dtype and one.shape == (2, seq, heads, width)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(two))


def opening_gradients(opening, operands, cotangents):
    """(dx, d_taps[, d_bias]) of sum(outputs * cotangents), float32 sums."""
    def scalar(*operands):
        return sum(jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))
                   for o, g in zip(opening(*operands), cotangents))
    given = tuple(i for i, t in enumerate(operands) if t is not None)
    return jax.jit(jax.grad(scalar, argnums=given))(*operands)


@pytest.mark.parametrize("seq", [3, 4, 13])
@pytest.mark.parametrize("form", list(OPENINGS))
def test_mixer_opening_gradients_are_float32s(form, seq):
    """Against `jax.grad` of the composition run in float32: float32 operands
    to float32 rounding; bfloat16 operands' `dx` (rounded once) no further
    from it than the composition's own bfloat16 gradient (four roundings and
    a bfloat16 sum), the parameters' to float32 rounding still."""
    for dtype in (jnp.float32, jnp.bfloat16):
        parts, operands, cotangents = opening_case(form, seq, dtype)
        wide = (operands[0].astype(jnp.float32),) + operands[1:]
        exact = opening_gradients(
            lambda *o: composed_opening(*o, parts, jnp.float32), wide,
            cotangents)
        ours = opening_gradients(lambda *o: mixer_opening(*o, parts),
                                 operands, cotangents)
        theirs = opening_gradients(
            lambda *o: composed_opening(*o, parts, dtype), operands,
            cotangents)

        def off(got, want):
            return float(jnp.linalg.norm((got.astype(jnp.float32)
                                          - want).ravel()))
        assert ours[0].dtype == dtype and ours[1].dtype == jnp.float32
        for got, want in zip(ours[1:], exact[1:]):      # d_taps, d_bias
            assert off(got, want) <= 1e-5 * float(jnp.linalg.norm(want))
        if dtype == jnp.float32:
            assert off(ours[0], exact[0]) \
                <= 1e-5 * float(jnp.linalg.norm(exact[0]))
        else:
            assert off(ours[0], exact[0]) <= off(theirs[0], exact[0])


@pytest.mark.parametrize("form", list(OPENINGS))
def test_mixer_opening_keeps_no_float32_activation(form):
    """What the backward is handed: the input as stored, the parameters, and
    a float32 a (token, head) of the normed parts, where plain autodiff kept
    nine float32 arrays of the activation's size."""
    from jax._src.ad_checkpoint import saved_residuals

    parts, operands, _ = opening_case(form, 64, jnp.bfloat16)
    given = [t for t in operands if t is not None]

    def kept(opening):
        def run(*given):
            x, taps, bias = (*given, None)[:3]
            return opening(x, taps, bias)
        return [aval for aval, _ in saved_residuals(run, *given)]

    batch, seq, channels = operands[0].shape
    ours = kept(lambda *o: mixer_opening(*o, parts))
    # Of a token's: the input in its own dtype, and a number a head.
    assert sorted((a.shape, a.dtype) for a in ours if a.ndim > 2) == sorted(
        [((batch, seq, channels), jnp.bfloat16)]
        + [((batch, seq, heads, 1), jnp.float32)
           for heads, _, unit in parts if unit is not None])
    theirs = kept(lambda *o: composed_opening(*o, parts, jnp.bfloat16))
    assert len([a for a in theirs if a.dtype == jnp.float32
                and a.size >= batch * seq * channels // 4]) >= 4
