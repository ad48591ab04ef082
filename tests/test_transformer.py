"""TransformerLM tests: causality, sequence-parallel equivalence, and a
dp x sp 2-D-mesh training step."""

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
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
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
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    base = model.apply({"params": params}, tokens)
    mutated = tokens.at[:, 10].set((tokens[:, 10] + 1) % VOCAB)
    out = model.apply({"params": params}, mutated)
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
