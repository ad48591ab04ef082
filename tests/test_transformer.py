"""TransformerLM tests: causality, sequence-parallel equivalence, and a
dp x sp 2-D-mesh training step."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import TransformerLM, next_token_loss

VOCAB = 64


def _model(seq_axis=None):
    # use_flash=False on the single-shard path: interpret-mode Pallas is
    # needlessly slow on the CPU test platform; blockwise is identical math.
    return TransformerLM(vocab_size=VOCAB, d_model=32, n_layers=2,
                         n_heads=4, dtype=jnp.float32, seq_axis=seq_axis,
                         use_flash=False)


def _tokens(batch=2, seq=32, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                              VOCAB)


def test_forward_shape_and_finite():
    model = _model()
    tokens = _tokens()
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)["params"]
    logits = jax.jit(model.apply)({"params": params}, tokens)
    assert logits.shape == (2, 32, VOCAB)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.slow  # ~18s compile-bound parity sweep; the fused loss
# stays tier-1 in test_dp_sp_train_step and
# test_fused_loss_rejects_sequence_parallelism
def test_fused_loss_matches_full_logits():
    """model.apply(..., targets=) — the chunked fused head+loss — matches
    next_token_loss on full logits in value and gradient, including when
    the token count does not divide the chunk count (silent n_chunks=1
    degrade)."""
    model = _model()
    for seq in (32, 31):  # 2*31 tokens are not divisible by 8 chunks
        tokens = _tokens(seq=seq + 1)
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        params = model.init(jax.random.PRNGKey(0), inp)["params"]

        def full(p):
            return next_token_loss(model.apply({"params": p}, inp), tgt)

        def fused(p):
            return model.apply({"params": p}, inp, targets=tgt)

        np.testing.assert_allclose(fused(params), full(params), rtol=1e-6)
        g_full = jax.grad(full)(params)
        g_fused = jax.grad(fused)(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_fused),
                        jax.tree_util.tree_leaves(g_full)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


def test_fused_loss_rejects_sequence_parallelism():
    import pytest

    model = _model(seq_axis="sp")
    tokens = _tokens()
    with pytest.raises(ValueError, match="sequence parallelism"):
        # init traces __call__, which must raise before touching the mesh
        model.init(jax.random.PRNGKey(0), tokens[:, :-1],
                   targets=tokens[:, 1:])


def test_causality():
    """Changing a future token must not change earlier logits."""
    model = _model()
    tokens = _tokens(seq=16)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)["params"]
    apply = jax.jit(model.apply)
    base = apply({"params": params}, tokens)
    mutated = tokens.at[:, 10].set((tokens[:, 10] + 1) % VOCAB)
    out = apply({"params": params}, mutated)
    np.testing.assert_allclose(base[:, :10], out[:, :10], atol=1e-6)
    assert not np.allclose(base[:, 10:], out[:, 10:])


def test_sequence_parallel_matches_single_device():
    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    tokens = _tokens(batch=2, seq=4 * 16, seed=3)

    single = _model(seq_axis=None)
    params = single.init(jax.random.PRNGKey(1), tokens)["params"]
    want = single.apply({"params": params}, tokens)

    sharded = _model(seq_axis="sp")

    def fwd(params, tokens):
        return sharded.apply({"params": params}, tokens)

    got = jax.jit(shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp")))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_dp_sp_train_step():
    """One 2-D-mesh (dp x sp) training step: batch sharded over dp,
    sequence over sp, gradients averaged over both axes."""
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.parallel import replicate

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("dp", "sp"))
    model = _model(seq_axis="sp")

    tokens = _tokens(batch=4, seq=4 * 8, seed=5)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    # Pad the shifted sequence back to a multiple of the sp axis.
    pad = (-inputs.shape[1]) % 4
    inputs = jnp.pad(inputs, ((0, 0), (0, pad)))
    targets = jnp.pad(targets, ((0, 0), (0, pad)))
    mask = jnp.pad(jnp.ones((4, tokens.shape[1] - 1)), ((0, 0), (0, pad)))

    # init outside shard_map: the unsharded twin has the identical pytree
    # (seq_axis only changes the attention communication pattern).
    params = _model(seq_axis=None).init(
        jax.random.PRNGKey(1), inputs[:, :8])["params"]

    def loss_fn(params, batch):
        inp, tgt, msk = batch
        logits = model.apply({"params": params}, inp)
        return next_token_loss(logits, tgt, msk, axis_name=("dp", "sp"))

    tx = optax.adamw(1e-3)
    spec = P("dp", "sp")
    step = build_train_step(loss_fn, tx, mesh, axis_name=("dp", "sp"),
                            batch_spec=(spec, spec, spec))
    params = replicate(mesh, params)
    opt_state = replicate(mesh, tx.init(params))
    batch = tuple(
        jax.device_put(x, NamedSharding(mesh, spec))
        for x in (inputs, targets, mask))
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses  # tiny model memorizes the batch


def test_sequence_parallel_fused_ring_matches():
    """TransformerLM(ring_impl='fused') — the fused ring-flash kernel —
    produces the same logits as the single-device model (the plumbing
    test for the flagship kernel inside the full model)."""
    model_sp = TransformerLM(vocab_size=VOCAB, d_model=32, n_layers=2,
                             n_heads=4, dtype=jnp.float32, seq_axis="sp",
                             use_flash=False, ring_impl="fused")
    model_1 = _model()
    tokens = _tokens(batch=2, seq=64)
    params = model_1.init(jax.random.PRNGKey(3), tokens)["params"]
    want = model_1.apply({"params": params}, tokens)

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    spec = P(None, "sp")

    def fwd(tokens):
        return model_sp.apply({"params": params}, tokens)

    got = jax.jit(shard_map(fwd, mesh=mesh, in_specs=spec,
                            out_specs=spec, check_vma=False))(tokens)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.slow  # ~26s compile-bound gradient check; forward parity
# (test_sequence_parallel_fused_ring_matches) stays tier-1
def test_sequence_parallel_fused_ring_gradients():
    """Training gradients through TransformerLM(ring_impl='fused') match
    the single-device model's — exercises the fused kernel's composed
    custom_vjp inside the full model (not just the op-level test)."""
    model_sp = TransformerLM(vocab_size=VOCAB, d_model=32, n_layers=1,
                             n_heads=4, dtype=jnp.float32, seq_axis="sp",
                             use_flash=False, ring_impl="fused")
    model_1 = TransformerLM(vocab_size=VOCAB, d_model=32, n_layers=1,
                            n_heads=4, dtype=jnp.float32, use_flash=False)
    tokens = _tokens(batch=2, seq=64, seed=11)
    targets = _tokens(batch=2, seq=64, seed=12)
    params = model_1.init(jax.random.PRNGKey(4), tokens)["params"]
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    spec = P(None, "sp")

    def sp_loss(params, tokens, targets):
        def shard(tokens, targets):
            logits = model_sp.apply({"params": params}, tokens)
            return next_token_loss(logits, targets)[None]
        losses = shard_map(shard, mesh=mesh, in_specs=(spec, spec),
                           out_specs=P("sp"), check_vma=False)(
            tokens, targets)
        return losses.mean()

    def ref_loss(params, tokens, targets):
        return next_token_loss(model_1.apply({"params": params}, tokens),
                               targets)

    g_sp = jax.grad(sp_loss)(params, tokens, targets)
    g_ref = jax.grad(ref_loss)(params, tokens, targets)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4),
        g_sp, g_ref)


def test_qkv_project_custom_vjp_matches_autodiff():
    """_qkv_project's hand-written VJP (no activation-sized cotangent
    stack) must match plain autodiff through the sliced einsum, value
    and gradient."""
    from horovod_tpu.models.transformer import _qkv_project

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 3, 4, 8), jnp.float32)

    def ref(x, w):
        return jnp.einsum("bsd,djhe->jbhse", x, w)

    q, k, v = _qkv_project(x, w)
    np.testing.assert_allclose(jnp.stack([q, k, v]), ref(x, w),
                               atol=1e-5, rtol=1e-5)

    weights = jnp.asarray(rng.randn(3, 2, 4, 16, 8), jnp.float32)

    def loss_custom(x, w):
        q, k, v = _qkv_project(x, w)
        return (jnp.stack([q, k, v]) * weights).sum()

    def loss_ref(x, w):
        return (ref(x, w) * weights).sum()

    g_c = jax.grad(loss_custom, argnums=(0, 1))(x, w)
    g_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    for a, b in zip(g_c, g_r):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# --- the output gate: `_output_gated` against the expression it replaced -----


def _plain_gated(out, x, w):
    """``Attention(gate=True)``'s gate as it stood until PR 48: the float32
    gate under plain autodiff."""
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dhe->bhse", x, w,
                                     preferred_element_type=jnp.float32))
    return (out * gate).astype(out.dtype)


def _gate_operands(dtype, heads, head_dim, seq=48, d=64, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(2, heads, seq, head_dim), dtype),
            jnp.asarray(rng.randn(2, seq, d), dtype),
            jnp.asarray(rng.randn(d, heads, head_dim) * d ** -0.5, dtype))


@pytest.mark.parametrize("head_dim", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=str)
def test_output_gate_forward_is_the_plain_expression_to_the_bit(dtype,
                                                                head_dim):
    from horovod_tpu.models.transformer import _output_gated

    operands = _gate_operands(dtype, 4, head_dim)
    got = jax.jit(_output_gated)(*operands)
    # The value a backward pass is built on is the same one.
    kept, _ = jax.jit(lambda *a: jax.vjp(_output_gated, *a))(*operands)
    want = jax.jit(_plain_gated)(*operands)
    assert got.dtype == want.dtype == dtype
    for value in (got, kept):
        np.testing.assert_array_equal(np.asarray(value, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("heads,head_dim", [(4, 128), (2, 256)])
def test_output_gate_backward_matches_autodiff(heads, head_dim):
    """out's, x's and w_g's gradients against `jax.grad` of the plain float32
    expression: in float32 within `_qkv_project`'s tolerances; in bfloat16
    (the gate kept rounded, `d_out` and `dz` rounded once) as near the
    float32 gradients as plain autodiff on the same bfloat16 operands is."""
    from horovod_tpu.models.transformer import _output_gated

    out, x, w = _gate_operands(jnp.float32, heads, head_dim, seed=1)
    mix = jnp.asarray(np.random.RandomState(2).randn(*out.shape), jnp.float32)

    def grads(fn, *operands):
        return jax.jit(jax.grad(lambda *a: (
            fn(*a).astype(jnp.float32) * mix).sum(), argnums=(0, 1, 2)))(
                *operands)

    want = grads(_plain_gated, out, x, w)
    for a, b in zip(grads(_output_gated, out, x, w), want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def off(got):
        return max(float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                         / jnp.linalg.norm(b)) for a, b in zip(got, want))

    low = [t.astype(jnp.bfloat16) for t in (out, x, w)]
    ours, autodiffs = off(grads(_output_gated, *low)), off(
        grads(_plain_gated, *low))
    assert ours < 1.25 * autodiffs < 0.02, (ours, autodiffs)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=str)
@pytest.mark.parametrize("kv_heads", [None, 2], ids=["ungrouped", "grouped"])
@pytest.mark.parametrize("head_dim", [128, 256])
def test_gated_attention_is_the_layer_with_the_plain_gate(
        monkeypatch, head_dim, kv_heads, dtype):
    """`Attention(gate=True)` whole: its forward to the bit, and in float32
    its input's and every parameter's gradient, against the same layer with
    the gate under plain autodiff."""
    import horovod_tpu.models.transformer as transformer

    layer = transformer.Attention(4, dtype, use_flash=False, head_dim=head_dim,
                                  n_kv_heads=kv_heads, head_norm=True,
                                  gate=True)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 32, 64), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(4), x)["params"]
    assert params["gate_kernel"].shape == (64, 4, head_dim)

    def value_and_grads():
        return jax.value_and_grad(
            lambda p, x: layer.apply({"params": p}, x).astype(
                jnp.float32).sum(), argnums=(0, 1))(params, x)

    def forward():
        return np.asarray(layer.apply({"params": params}, x), np.float32)

    got, got_loss_and_grads = forward(), value_and_grads()
    monkeypatch.setattr(transformer, "_output_gated", _plain_gated)
    np.testing.assert_array_equal(got, forward())
    if dtype == jnp.float32:
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4,
                                                    rtol=1e-4),
            got_loss_and_grads, value_and_grads())


def test_output_gate_keeps_and_hands_on_the_layers_dtype_alone():
    """What crosses `_output_gated`'s edges in bfloat16: the residuals (out,
    x, w_g and the ROUNDED gate, nothing float32), the two arrays the backward
    writes before its products (one barrier, both bfloat16) and the products'
    operands and results.  What the chip's compiler makes of it is in
    `tests/test_chip_steps.py` (`test_gated_attention_writes_no_float32_...`):
    the CPU backend computes bfloat16 element-wise passes in float32 and keeps
    no barrier, so its text shows nothing of this."""
    from horovod_tpu.models.transformer import (_output_gated_bwd,
                                                _output_gated_fwd)

    operands = _gate_operands(jnp.bfloat16, 4, 128)
    gated, kept = jax.eval_shape(_output_gated_fwd, *operands)
    assert [leaf.dtype for leaf in kept] == [jnp.bfloat16] * 4
    assert [leaf.shape for leaf in kept] == [
        operands[0].shape, operands[1].shape, operands[2].shape,
        operands[0].shape]
    jaxpr = jax.make_jaxpr(_output_gated_bwd)(kept, gated).jaxpr
    barriers = [eqn for eqn in jaxpr.eqns
                if eqn.primitive.name == "optimization_barrier"]
    assert len(barriers) == 1
    written = barriers[0].outvars
    assert [(v.aval.shape, v.aval.dtype) for v in written] \
        == [(operands[0].shape, jnp.bfloat16)] * 2
    products = [eqn for eqn in jaxpr.eqns
                if eqn.primitive.name == "dot_general"]
    assert len(products) == 2
    for eqn in products:
        assert written[1] in eqn.invars          # dz, as the barrier wrote it
        assert {v.aval.dtype for v in eqn.invars + eqn.outvars} \
            == {jnp.dtype(jnp.bfloat16)}
    assert jaxpr.outvars[0] is written[0]        # d_out
    assert [v.aval.dtype for v in jaxpr.outvars] == [jnp.bfloat16] * 3


# --- rope: the pairs' rotation with the head's width kept in the last axis --
# against the formula it had until PR 42, kept here as the plain reference.


def _plain_rope(x, positions, base=10000.0, seq_dim=-2):
    """Adjacent pairs through a ``(half, 2)`` view, float32 inside, rounded
    once: `models.transformer.rope` as it was until PR 42."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    shape = [1] * x.ndim
    if positions.ndim == 2:
        shape[0] = positions.shape[0]
    shape[seq_dim] = x.shape[seq_dim]
    shape[-1] = half
    cos = jnp.cos(angles).reshape(shape)[..., None]
    sin = jnp.sin(angles).reshape(shape)[..., None]
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., :1], pairs[..., 1:]
    rotated = jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                              axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


def _rope_case(dtype, head_dim, seq_dim, rank, seed=0):
    batch, heads, seq = 2, 3, 24
    shape = (batch, heads, seq, head_dim) if seq_dim == -2 \
        else (batch, seq, heads, head_dim)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(keys[0], shape, dtype)
    # what a bfloat16 cotangent holds exactly
    mix = jax.random.normal(keys[1], shape, jnp.bfloat16).astype(jnp.float32)
    positions = 5 + jnp.arange(seq) if rank == 1 \
        else jnp.stack([3 + jnp.arange(seq), 4000 + jnp.arange(seq)])
    return x, mix, positions


ROPE_CASES = [(64, -2, 1, 1e4), (128, -2, 1, 1e6), (6, -2, 2, 1e4),
              (64, 1, 2, 1e6), (128, 1, 1, 1e4), (10, 1, 1, 1e6)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("head_dim,seq_dim,rank,base", ROPE_CASES, ids=str)
def test_rope_is_the_plain_formula(dtype, head_dim, seq_dim, rank, base):
    """Operation by operation (no fusion, so no contraction the CPU might
    make of one form and not the other) the two forms are the same float32
    sums: bfloat16 results agree to the bit."""
    from horovod_tpu.models.transformer import rope

    x, _, positions = _rope_case(dtype, head_dim, seq_dim, rank)
    got = rope(x, positions, base, seq_dim)
    want = _plain_rope(x, positions, base, seq_dim)
    assert got.dtype == want.dtype
    if dtype == jnp.bfloat16:
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("head_dim,seq_dim,rank,base", ROPE_CASES[:4],
                         ids=str)
def test_rope_gradient_is_autodiff_of_the_plain_formula(dtype, head_dim,
                                                        seq_dim, rank, base):
    """The written-out backward — the rotation by the negative angles,
    rounded once — against autodiff of the plain formula: float32 to 1e-6;
    bfloat16 to the three roundings autodiff makes of a pair's cotangent
    (each product's, then their sum's) where the written-out one makes one."""
    from horovod_tpu.models.transformer import rope

    x, mix, positions = _rope_case(dtype, head_dim, seq_dim, rank, seed=1)

    def through(fn):
        return jax.grad(lambda x: jnp.sum(
            fn(x, positions, base, seq_dim).astype(jnp.float32) * mix))(x)

    got, want = through(rope), through(_plain_rope)
    assert got.dtype == want.dtype == dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    else:
        exact = jax.grad(lambda x: jnp.sum(_plain_rope(
            x, positions, base, seq_dim) * mix))(x.astype(jnp.float32))
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(exact.astype(dtype), np.float32))
        np.testing.assert_allclose(got, want, rtol=2**-7, atol=2**-5)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_rope_keeps_the_head_width_in_the_last_axis(head_dim):
    """Forward and backward lower with no pair axis: no concatenate, no pad,
    no tensor whose last axis is 2."""
    from horovod_tpu.models.transformer import rope

    x = jax.ShapeDtypeStruct((2, 4, 256, head_dim), jnp.bfloat16)
    text = jax.jit(jax.grad(lambda x: jnp.sum(rope(
        x, jnp.arange(256), 1e4).astype(jnp.float32) ** 2))).lower(
            x).as_text()
    assert "dot_general" in text
    assert "concatenate" not in text and "stablehlo.pad" not in text
    assert not re.search(r"x2x(bf16|f32)>", text)
    assert re.search(rf"tensor<2x4x256x{head_dim}xbf16>", text)


def test_rope_refuses_an_odd_width():
    from horovod_tpu.models.transformer import rope

    with pytest.raises(ValueError, match="must be even"):
        rope(jnp.ones((1, 1, 4, 5)), jnp.arange(4))


def _plain_grouped_attention(params, x, heads, kv_heads, theta, eps):
    """Grouped-query attention with per-head norms as `Attention` ran it
    until PR 42: a key head normed, REPEATED, and then turned with the rest."""
    def normed(t, scale):
        mean_sq = jnp.mean(jnp.square(t), axis=-1, keepdims=True)
        return t * jax.lax.rsqrt(mean_sq + eps) * scale

    seq = x.shape[1]
    q = jnp.einsum("bsd,dhe->bhse", x, params["q_kernel"])
    k, v = jnp.einsum("bsd,djhe->jbhse", x, params["kv_kernel"])
    q = normed(q, params["q_head_norm_scale"])
    k = normed(k, params["k_head_norm_scale"])
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    positions = jnp.arange(seq)
    q, k = (_plain_rope(t, positions, theta) for t in (q, k))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    logits = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), logits,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, axis=-1), v)
    return jnp.einsum("bhse,hed->bsd", out, params["o_kernel"])


def test_a_key_head_turned_before_its_repeat_is_the_same_layer():
    """`Attention(n_kv_heads=4, head_norm=True)` turns 4 key heads for 16
    query heads and sums a head's 4 cotangents before the inverse rotation:
    output and every gradient are those of rotating after the repeat."""
    from horovod_tpu.models.transformer import Attention

    heads, kv_heads, theta, eps = 16, 4, 1e6, 1e-6
    layer = Attention(n_heads=heads, dtype=jnp.float32, use_flash=False,
                      n_kv_heads=kv_heads, head_dim=8, head_norm=True,
                      rope_theta=theta, norm_eps=eps)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (2, 32, 48), jnp.float32)
    mix = jax.random.normal(keys[1], (2, 32, 48), jnp.float32)
    params = jax.jit(lambda key: jax.tree.map(   # scales off one, so they
        lambda p: p * (1.0 + 0.1 * jnp.arange(p.size).reshape(p.shape)
                       / p.size), layer.init(key, x)["params"]))(keys[2])

    def both(fn):
        """(the output, the gradients of its mix), one program."""
        def summed(params, x):
            out = fn(params, x)
            return jnp.sum(out * mix), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            summed, (0, 1), has_aux=True))(params, x)
        return out, grads

    def ours(params, x):
        return layer.apply({"params": params}, x)

    def plain(params, x):
        return _plain_grouped_attention(params, x, heads, kv_heads, theta,
                                        eps)

    (out, got), (plain_out, want) = both(ours), both(plain)
    np.testing.assert_allclose(out, plain_out, rtol=1e-5, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-4), got, want)


def test_attention_names_its_rotations_and_only_where_it_turns():
    """`hvd_attn_rotate` holds q's and k's rotation, forward and backward, a
    grouped layer's k in front of its repeat (4 heads wide, not 16); a layer
    that does not rotate lowers without the scope and without a pair swap."""
    from horovod_tpu.models.transformer import Attention

    def lowered(**kwargs):
        layer = Attention(n_heads=16, dtype=jnp.bfloat16, use_flash=False,
                          **kwargs)
        x = jnp.zeros((1, 64, 128), jnp.bfloat16)
        params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                                   x)["params"])
        grad = jax.grad(lambda p, x: jnp.sum(layer.apply(
            {"params": p}, x).astype(jnp.float32)), (0, 1))
        return jax.jit(grad).lower(params, x).as_text(debug_info=True)

    def swaps(text, heads):
        """The pair swaps (products with the 8 x 8 permutation) of a tensor
        `heads` heads wide."""
        return len(re.findall(
            rf"dot_general .*tensor<1x{heads}x64x8xbf16>, tensor<8x8xbf16>",
            text))

    fused = lowered()
    grouped = lowered(n_kv_heads=4, head_norm=True)
    plain = lowered(n_kv_heads=4, rope=False)
    for text in (fused, grouped):
        assert "hvd_attn_attend/hvd_attn_rotate" in text
        assert "transpose(" in text
    in_projections = r'hvd_attn_qkv/[^"]*hvd_attn_rotate'
    assert re.search(in_projections, grouped)
    assert not re.search(in_projections, fused)
    # q and k, forward and backward
    assert (swaps(fused, 16), swaps(fused, 4)) == (4, 0)
    assert (swaps(grouped, 16), swaps(grouped, 4)) == (2, 2)
    assert "hvd_attn_rotate" not in plain and "...d,de->...e" not in plain


def test_head_norm_writes_its_input_s_cotangent_out():
    """The per-head norm's backward ends in a barrier, forward it has none:
    the cotangent of the projection's output is written out once for the
    two products that read it (PERF.md section 6, PR 42, has what fusing it
    into them cost on the chip)."""
    from horovod_tpu.models.transformer import Attention

    layer = Attention(n_heads=4, dtype=jnp.bfloat16, use_flash=False,
                      n_kv_heads=2, head_dim=8, head_norm=True)
    x = jnp.zeros((1, 16, 32), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                               x)["params"])

    def out(p, x):
        return jnp.sum(layer.apply({"params": p}, x).astype(jnp.float32))

    assert "optimization_barrier" not in jax.jit(out).lower(
        params, x).as_text()
    backward = jax.jit(jax.grad(out)).lower(params, x).as_text()
    assert backward.count("optimization_barrier") == 2       # q's and k's


# --- next_token_loss: a cross-entropy with its own backward pass ----------
# against optax's, which plain autodiff differentiates.

XENT_SHAPE, XENT_VOCAB = (4, 64), 512


def _xent_case(dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = (3.0 * jax.random.normal(keys[0], (*XENT_SHAPE, XENT_VOCAB))
              ).astype(dtype)
    targets = jax.random.randint(keys[1], XENT_SHAPE, 0, XENT_VOCAB)
    mask = jax.random.uniform(keys[2], XENT_SHAPE) > 0.3
    return logits, targets, mask


def _optax_loss(logits, targets, mask=None):
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets)
    if mask is None:
        return loss.mean()
    mask = mask.astype(loss.dtype)
    return (loss * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _assert_within_ulps(got, want, units=1):
    """Element by element, ``units`` in the last place of ``want``'s dtype,
    which is ``got``'s too."""
    assert got.dtype == want.dtype and got.shape == want.shape
    eps = float(jnp.finfo(want.dtype).eps)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    ulp = np.ldexp(eps, np.frexp(np.abs(want))[1] - 1)
    worst = np.max(np.abs(got - want) / ulp)
    assert worst <= units, f"{worst} units in the last place"


@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_next_token_loss_matches_optax(dtype, masked):
    """Loss and gradient of next_token_loss equal optax's cross-entropy
    under plain autodiff, and the logits' cotangent comes back in the
    logits' own dtype."""
    logits, targets, mask = _xent_case(dtype)
    mask = mask if masked else None
    loss, grad = jax.value_and_grad(next_token_loss)(logits, targets, mask)
    want_loss, want_grad = jax.value_and_grad(_optax_loss)(logits, targets,
                                                           mask)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert grad.dtype == logits.dtype
    _assert_within_ulps(grad, want_grad)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_next_token_loss_masked_under_shard_map(dtype):
    """The masked loss with ``axis_name`` inside shard_map (dp x sp, the
    padding unevenly spread over the shards): the pmean of the shards'
    losses and its gradient equal the global weighted mean's."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "sp"))
    logits, targets, mask = _xent_case(dtype, seed=1)
    mask = mask.at[:, 48:].set(False)  # the last sp shard is all padding
    spec = P("dp", "sp")

    def sharded(logits, targets, mask):
        loss = next_token_loss(logits, targets, mask,
                               axis_name=("dp", "sp"))
        return jax.lax.pmean(loss, ("dp", "sp"))

    fn = jax.jit(jax.value_and_grad(shard_map(
        sharded, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P())))
    loss, grad = fn(logits, targets, mask)
    want_loss, want_grad = jax.value_and_grad(_optax_loss)(logits, targets,
                                                           mask)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    # 1 / (count / 8) / 8 is not 1 / count to the last bit: two units.
    _assert_within_ulps(grad, want_grad, units=2)


def test_next_token_loss_replicated_logits_under_shard_map():
    """Logits replicated over a mapped axis against targets that vary over
    it: the hand-written backward pass sums the cotangent over that axis,
    as plain autodiff does."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    logits, targets, _ = _xent_case(jnp.float32, seed=2)
    targets = jnp.stack([(targets + i) % XENT_VOCAB for i in range(4)])

    def sharded(logits, targets):
        return jax.lax.pmean(next_token_loss(logits, targets[0]), "dp")

    grad = jax.jit(jax.grad(shard_map(
        sharded, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P())))(
            logits, targets)
    want = jax.grad(lambda l: jnp.mean(jnp.stack(
        [_optax_loss(l, t) for t in targets])))(logits)
    np.testing.assert_allclose(grad, want, rtol=1e-5, atol=1e-9)


# --- embedding_lookup: the table's gradient a slab of columns at a time ----
# against autodiff of jnp.take, which is nn.Embed's lookup.

# Whole rows (512 and 1,024, `ops.moe.WHOLE_ROW_WIDTH`), a narrower last slab
# (1,280 and 2,304), Ling's 2,560 and Nemotron's 4,096.
EMBED_WIDTHS = [512, 1024, 1280, 2304, 2560, 4096]
EMBED_VOCAB, EMBED_OFTEN, EMBED_NEVER = 24, 3, 5


def _plain_take(table, tokens):
    return jnp.take(table, tokens, axis=0)


def _embed_case(width, dtype):
    """A table, tokens among which one id fills half the batch and one is
    never drawn, and the rows' cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(width), 3)
    table = jax.random.normal(keys[0], (EMBED_VOCAB, width), dtype)
    tokens = jax.random.randint(keys[1], (2, 96), 0, EMBED_VOCAB)
    tokens = jnp.where(tokens == EMBED_NEVER, EMBED_NEVER + 1, tokens)
    tokens = tokens.at[:, :48].set(EMBED_OFTEN)
    mix = jax.random.normal(keys[2], (2, 96, width), dtype)
    return table, tokens, mix


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("width", EMBED_WIDTHS)
def test_embedding_lookup_and_its_gradient_are_takes(width, dtype):
    """The rows and the table's gradient are what `jnp.take` and autodiff
    of it give, bit for bit in both dtypes (each column is the same sum of
    the same terms in the same order, whole rows or a slab at a time): under
    a scale as `embed_scale` applies it, with a token drawn 96 times and one
    never drawn, whose row's gradient is zero."""
    from horovod_tpu.models.transformer import embedding_lookup

    table, tokens, mix = _embed_case(width, dtype)

    def loss(take, table, tokens, mix):
        rows = (take(table, tokens) * 48.0).astype(dtype)
        return (rows.astype(jnp.float32) * mix.astype(jnp.float32)).sum()

    np.testing.assert_array_equal(
        jax.jit(embedding_lookup)(table, tokens), _plain_take(table, tokens))
    got = jax.jit(jax.grad(loss, 1), static_argnums=0)(
        embedding_lookup, table, tokens, mix)
    want = jax.jit(jax.grad(loss, 1), static_argnums=0)(
        _plain_take, table, tokens, mix)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(got, want)
    assert not np.asarray(got[EMBED_NEVER], np.float32).any()
    assert np.asarray(got[EMBED_OFTEN], np.float32).any()


@pytest.mark.parametrize("width,scatters", [
    (512, 1), (1024, 1), (1025, 3), (2048, 4), (2560, 5), (4096, 8)])
def test_embedding_gradient_takes_slabs_past_the_whole_row_width(width,
                                                                 scatters):
    """The rule is `ops.moe`'s, from the table's static shape alone: up to
    `WHOLE_ROW_WIDTH` elements a row plain autodiff of the take (no
    `custom_vjp`, one scatter-add of whole rows: pythia-410m's 1,024), past
    it one scatter-add a slab of `ROW_SLAB_WIDTH` columns (OLMoE's and
    Trinity's 2,048 four, Ling's 2,560 five, Nemotron's 4,096 eight) and
    their join behind a barrier, which keeps it out of the optimizer's
    fusion."""
    from horovod_tpu.models.transformer import embedding_lookup
    from horovod_tpu.ops.moe import ROW_SLAB_WIDTH, WHOLE_ROW_WIDTH

    table = jax.ShapeDtypeStruct((EMBED_VOCAB, width), jnp.bfloat16)
    tokens = jax.ShapeDtypeStruct((2, 96), jnp.int32)
    whole = width <= WHOLE_ROW_WIDTH
    jaxpr = str(jax.make_jaxpr(embedding_lookup)(table, tokens))
    assert ("custom_vjp" not in jaxpr) == whole
    text = jax.jit(jax.grad(
        lambda table, tokens: embedding_lookup(table, tokens).astype(
            jnp.float32).sum())).lower(table, tokens).as_text()
    assert scatters == (1 if whole else -(-width // ROW_SLAB_WIDTH))
    assert text.count('"stablehlo.scatter"') == scatters
    assert text.count("stablehlo.optimization_barrier") == (not whole)


@pytest.mark.parametrize("embed_scale", [None, 35.8], ids=["plain", "scaled"])
def test_wide_model_s_table_gradient_is_autodiffs(embed_scale, monkeypatch):
    """A one-layer TransformerLM 1,280 wide: every gradient leaf, the
    table's among them, is what the model gives with the lookup left to
    autodiff, with `embed_scale` and without."""
    from horovod_tpu.models import transformer

    model = TransformerLM(vocab_size=VOCAB, d_model=1280, n_layers=1,
                          n_heads=2, d_ff=64, dtype=jnp.float32,
                          use_flash=False, embed_scale=embed_scale)
    tokens = _tokens(seed=3)
    params = jax.jit(model.init)(jax.random.PRNGKey(2), tokens)["params"]

    def loss(params):
        return next_token_loss(model.apply({"params": params}, tokens),
                               tokens)

    got = jax.grad(loss)(params)
    monkeypatch.setattr(transformer, "scatters_whole_rows", lambda _: True)
    want = jax.grad(loss)(params)
    assert np.asarray(got["embed"]["embedding"]).any()
    jax.tree.map(np.testing.assert_array_equal, got, want)


def test_wide_table_trains_data_parallel_and_replicas_stay_equal():
    """Two CPU devices through `build_train_step`, a one-layer LM with a
    table 1,280 wide and 16 ids: the written backward owes the sum over the
    data-parallel axis that autodiff inserts for a replicated table.  The
    first step's table gradient (SGD: the table's change over the rate) is
    the one-device gradient of the whole batch, the replicas' weights stay
    equal and the loss of a repeated batch falls."""
    from horovod_tpu.jax.train import build_train_step

    vocab = 16
    model = TransformerLM(vocab_size=vocab, d_model=1280, n_layers=1,
                          n_heads=10, d_ff=64, dtype=jnp.float32,
                          use_flash=True)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 129), 0, vocab)
    batch = (tokens[:, :-1], tokens[:, 1:])
    params = jax.jit(model.init)(jax.random.PRNGKey(5), batch[0])["params"]

    def loss_fn(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    whole = jax.jit(jax.grad(loss_fn))(params, batch)["embed"]["embedding"]
    table = np.asarray(params["embed"]["embedding"])    # the step donates
    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))
    rate = 0.01
    tx = optax.sgd(rate)
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd",
                            batch_spec=(P("hvd"), P("hvd")))
    state = (params, tx.init(params))
    losses = []
    for _ in range(3):
        *state, loss = step(*state, batch)
        losses.append(float(loss))
        if len(losses) == 1:
            moved = table - np.asarray(state[0]["embed"]["embedding"])
            np.testing.assert_allclose(moved / rate, whole, rtol=1e-4,
                                       atol=2e-6)
            assert np.abs(whole).max() > 1e-2
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    for leaf in jax.tree.leaves(state[0]):
        first, second = (np.asarray(s.data) for s in leaf.addressable_shards)
        np.testing.assert_array_equal(first, second)
