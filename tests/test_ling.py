"""The pattern kinds Ling-3.0-flash adds to models.TransformerLM (Kimi-delta
linear attention, latent attention with two head widths, a dense gated MLP,
group-limited sparse experts) against the plain float32 reference the
benchmark keeps (benchmark/reference/ling_lm.py): the delta rule one step a
token, a plain softmax over whole rows, a loop over the shard's experts.  CPU,
float32, seeded weights, small sizes.

Tolerances: both sides are float32 and differ in the order of their sums
(products over chunks and a solve against a step a token, grouped rows against
masked whole batches), so they agree to float32 rounding accumulated over a
few layers: 2e-5 of the largest value (the chunked delta rule, whose decays
span e^-5 a step and whose solve multiplies 64 x 64 matrices, 1e-4).  bfloat16
anywhere would read 1e-3 to 1e-2 and fail every case.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ling_lm as reference
from horovod_tpu.models import (DeltaConfig, DeltaMixer, LatentAttention,
                                LatentConfig, MoEConfig, TransformerLM)
from horovod_tpu.models.transformer import GatedMLP, SparseExperts
from horovod_tpu.ops import flash_attention, mha_reference
from horovod_tpu.ops import delta_rule
from horovod_tpu.ops.delta_rule import chunked_delta_rule
from tests.test_hybrid import (both_ways, close, columns, mixer_case,
                               reference_sides, sown, with_highest)

RTOL = 2e-5
VOCAB, HIDDEN, SEQ, HEADS, D_FF = 256, 64, 128, 8, 96
DELTA = DeltaConfig(heads=HEADS, head_dim=8, conv=4, chunk=32)
LATENT = LatentConfig(kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
                      rope_theta=6e6)
EXPERTS, PER_TOKEN, WIDTH, SHARED, SCALE, GROUPS, KEPT = 16, 4, 48, 40, 2.5, \
    4, 2
# A published layer is a mixer and then an MLP or the experts: the leading
# dense layer, two delta layers and a latent-attention layer.
LAYERS = ("delta", "gated_mlp", "delta", "experts", "delta", "experts",
          "latent_attention", "experts")


def moe(shard=(0, 1), row_bound=None, experts=EXPERTS, groups=GROUPS,
        kept=KEPT):
    return MoEConfig(experts, PER_TOKEN, WIDTH, shard, row_bound, "sigmoid",
                     True, SCALE, "gated_silu", None, SHARED, groups, kept)


def lm(expert_shard=(0, 1), head_shard=(0, 1), use_flash=False, vocab=VOCAB,
       chunk=DELTA.chunk):
    return TransformerLM(
        vocab_size=vocab, d_model=HIDDEN, n_heads=HEADS, d_ff=D_FF,
        dtype=jnp.float32, use_flash=use_flash, norm_eps=1e-6,
        moe=moe(expert_shard), layers=LAYERS,
        delta=DELTA._replace(chunk=chunk), latent=LATENT,
        head_shard=head_shard)


def routing(**more):
    return dict(experts_per_token=PER_TOKEN, weight_scale=SCALE,
                n_group=GROUPS, topk_group=KEPT, **more)


def reference_config(expert_shard=(0, 1), **more):
    return dict(layers=LAYERS, head_dim=DELTA.head_dim,
                lower_bound=DELTA.lower_bound, nope_dim=LATENT.nope_dim,
                rope_theta=LATENT.rope_theta, norm_eps=1e-6,
                num_experts=EXPERTS, expert_shard=expert_shard,
                **routing(**more))


reference_side = reference_sides(reference_config, reference.loss_and_chosen)


# --- the delta rule --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("seed", "seq", "d_k", "d_v"))
def delta_inputs(seed, seq=SEQ, d_k=16, d_v=8):
    """Unit keys, queries at d_k^-1/2, log-decays from 0 down to the gate's
    bound of -5 a step — every seventh token AT the bound in every channel,
    so that a chunk's product reaches e^-45 and a sub-block's ratios e^75."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (2, seq, 3)

    def unit(key):
        t = jax.random.normal(key, shape + (d_k,))
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q, k = unit(keys[0]) * d_k ** -0.5, unit(keys[1])
    v = jax.random.normal(keys[2], shape + (d_v,))
    log_alpha = -5.0 * jax.random.uniform(keys[3], shape + (d_k,)) ** 3
    log_alpha = log_alpha.at[:, ::7].set(-5.0)
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], shape))
    mix = jax.random.normal(keys[5], shape + (d_v,))
    return (q, k, v, log_alpha, beta), mix


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_delta_rule_is_the_recurrence(chunk):
    args, _ = delta_inputs(chunk)
    got, decay_min = jax.jit(lambda *a: chunked_delta_rule(*a, chunk))(*args)
    close(got, jax.jit(reference.delta_recurrence)(*args), 1e-4)
    summed = args[3].reshape(2, SEQ // chunk, chunk, 3, -1).sum(axis=2)
    close(decay_min, summed.min())
    assert float(decay_min) < -5.0 * chunk / 7


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_delta_rule_gradients_are_the_recurrences(chunk):
    args, mix = delta_inputs(7 + chunk)

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    got = jax.jit(jax.grad(total(lambda *a: chunked_delta_rule(*a, chunk)[0]),
                           argnums=range(5)))(*args)
    want = jax.jit(jax.grad(total(reference.delta_recurrence),
                            argnums=range(5)))(*args)
    for g, w in zip(got, want):
        assert bool(jnp.isfinite(g).all())
        close(g, w, 1e-4)


@pytest.mark.parametrize("seq,chunk", [(96, 64), (96, 24)])
def test_chunked_delta_rule_refuses_a_ragged_length(seq, chunk):
    args, _ = delta_inputs(0, seq=seq)
    with pytest.raises(ValueError, match="multiple"):
        chunked_delta_rule(*args, chunk)


# --- the delta rule at the Ling cell's widths: the recurrence that carries
# only its state, and the solve whose rows are stacked once ------------------

WIDE_HEAD, WIDE_CHUNK = 128, 64


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def wide_inputs(seed, chunks, regime):
    """Head width 128, chunks of 64 with sub-blocks of 16.  `bound`:
    `delta_inputs`' own decays, every seventh token AT the gate's bound of -5
    in every channel (with EVERY token there the log-decays' gradient, a
    thousandth of the others', is 7e-3 of its largest value off the
    recurrence's, before PR 35 as after); `near_zero`: decays of 1 - 1e-3 a
    step, so the state crosses every chunk whole; `alike`: keys a hundredth
    apart, so that `K K^T` is all ones and the powers of `A` reach 1e8 (the
    case that breaks a Neumann-series inverse)."""
    (q, k, v, log_alpha, beta), mix = delta_inputs(
        seed, seq=chunks * WIDE_CHUNK, d_k=WIDE_HEAD, d_v=WIDE_HEAD)
    if regime == "near_zero":
        log_alpha = log_alpha * 2e-4
    elif regime == "alike":
        k = k[:, :1] + 0.01 * k
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (q[:1, :, :2], k[:1, :, :2], v[:1, :, :2], log_alpha[:1, :, :2],
            beta[:1, :, :2]), mix[:1, :, :2]


@pytest.mark.parametrize("regime", ["bound", "near_zero", "alike"])
@pytest.mark.parametrize("chunks", [2, 5])
def test_wide_delta_rule_and_its_gradients_are_the_recurrences(chunks,
                                                              regime):
    args, mix = wide_inputs(chunks, chunks, regime)

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    got, decay_min = jax.jit(
        lambda *a: chunked_delta_rule(*a, WIDE_CHUNK))(*args)
    close(got, jax.jit(reference.delta_recurrence)(*args), 1e-4)
    close(decay_min, args[3].reshape(1, chunks, WIDE_CHUNK, 2, -1).sum(
        axis=2).min())
    grads = jax.jit(jax.grad(total(
        lambda *a: chunked_delta_rule(*a, WIDE_CHUNK)[0]),
        argnums=range(5)))(*args)
    wanted = jax.jit(jax.grad(total(reference.delta_recurrence),
                              argnums=range(5)))(*args)
    for g, w in zip(grads, wanted):
        assert bool(jnp.isfinite(g).all())
        close(g, w, 1e-4)


def plain_carry(w, u0, q_in, qk, k_end, carried):
    """The recurrence between chunks as it is written down — one scan of the
    chunk's three products with `O` inside the step, differentiated by
    autodiff: what `delta_rule._carry` must equal, value and gradients."""
    def chunk_step(state, inputs):
        w, u0, q_in, qk, k_end, carried = inputs
        u = u0 - jnp.einsum("bhtc,bhcv->bhtv", w, state)
        o = jnp.einsum("bhtc,bhcv->bhtv", q_in, state) \
            + jnp.einsum("bhts,bhsv->bhtv", qk, u)
        return carried[..., None] * state + jnp.einsum(
            "bhtc,bhtv->bhcv", k_end, u), o

    start = jnp.zeros((w.shape[0], w.shape[2], w.shape[-1], u0.shape[-1]))
    by_step = [jnp.moveaxis(t, 1, 0)
               for t in (w, u0, q_in, qk, k_end, carried)]
    return jnp.moveaxis(jax.lax.scan(chunk_step, start, tuple(by_step))[1],
                        0, 1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def carry_operands(seed, chunks, carried_to):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    lead = (2, chunks, 2, WIDE_CHUNK)

    def normal(key, *shape, scale=1.0):
        return scale * jax.random.normal(key, shape)

    width = WIDE_HEAD ** -0.5
    w, q_in, k_end = (normal(key, *lead, WIDE_HEAD, scale=width)
                      for key in keys[:3])
    u0 = normal(keys[3], *lead, WIDE_HEAD)
    qk = jnp.tril(normal(keys[4], *lead, WIDE_CHUNK, scale=0.1))
    carried = jnp.exp(carried_to * jax.random.uniform(
        keys[5], (2, chunks, 2, WIDE_HEAD)))
    mix = normal(keys[6], *lead, WIDE_HEAD)
    return (w, u0, q_in, qk, k_end, carried), mix


@pytest.mark.parametrize("carried_to", [-320.0, -1e-3])
@pytest.mark.parametrize("chunks", [2, 5])
def test_carry_is_the_scan_with_o_inside_it(chunks, carried_to):
    """Value and all six cotangents, where nothing crosses a chunk (a decay of
    e^-320 is a float32 zero) and where everything does."""
    operands, mix = carry_operands(chunks, chunks, carried_to)

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    got, wanted = (jax.jit(jax.value_and_grad(total(fn), argnums=range(6)))(
        *operands) for fn in (delta_rule._carry, plain_carry))
    close(got[0], wanted[0], RTOL)
    for g, w in zip(got[1], wanted[1]):
        assert bool(jnp.isfinite(g).all())
        close(g, w, RTOL)


def scan_bodies(jaxpr):
    bodies = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            bodies.append(eqn.params["jaxpr"].jaxpr)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            bodies += scan_bodies(sub)
    return bodies


def test_each_pass_carries_the_state_alone_through_two_products_a_chunk():
    """One scan forward and one backward, each of two products a step: `O`,
    and every cotangent but the state's, are products over all chunks."""
    args, mix = wide_inputs(0, 2, "bound")
    program = jax.make_jaxpr(jax.grad(
        lambda *a: (chunked_delta_rule(*a, WIDE_CHUNK)[0] * mix).sum(),
        argnums=range(5)))(*args)
    bodies = scan_bodies(program.jaxpr)
    assert [sum(e.primitive.name == "dot_general" for e in body.eqns)
            for body in bodies] == [2, 2]


@pytest.mark.parametrize("size", [8, 16, 64])
def test_unit_lower_inverse_of_keys_that_resemble_one_another(size):
    """`(I + A)^-1` with `A = tril(beta K K^T, -1)` of keys a hundredth apart
    (every entry near beta): against float64, and its cotangent
    `-T^T g T^T`."""
    keys = jax.random.split(jax.random.PRNGKey(size), 3)
    k = jax.random.normal(keys[0], (3, 1, WIDE_HEAD)) \
        + 0.01 * jax.random.normal(keys[1], (3, size, WIDE_HEAD))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = jnp.tril(0.9 * jnp.einsum("bic,bjc->bij", k, k), -1)
    wanted = np.linalg.inv(np.eye(size) + np.asarray(a, np.float64))
    close(jax.jit(delta_rule._unit_lower_inverse)(a), wanted, RTOL)
    g = jax.random.normal(keys[2], a.shape)
    got = jax.jit(jax.grad(
        lambda a: (delta_rule._unit_lower_inverse(a) * g).sum()))(a)
    transposed = wanted.swapaxes(-1, -2)
    close(got, -transposed @ np.asarray(g, np.float64) @ transposed, RTOL)


# --- flash attention with two widths ----------------------------------------

@pytest.mark.parametrize("seq,block", [(256, 128), (384, None), (200, None)])
def test_flash_attention_takes_a_value_width_of_its_own(seq, block):
    """Query and key 48 wide, value 32: the kernels (interpreted here; 200
    tokens take the blockwise scan) against plain attention, values and the
    three gradients, each of its operand's shape."""
    keys = jax.random.split(jax.random.PRNGKey(seq), 4)
    q, k = (jax.random.normal(key, (1, 2, seq, 48)) for key in keys[:2])
    v, mix = (jax.random.normal(key, (1, 2, seq, 32)) for key in keys[2:])

    def total(fn):
        return lambda *a: (fn(*a, causal=True) * mix).sum()

    def kernel(*a, causal):
        return flash_attention(*a, causal=causal, block_q=block,
                               block_k=block)

    got = jax.value_and_grad(total(kernel), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(total(mha_reference), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape
        close(g, w)


# --- each mixer against the reference's ------------------------------------

@pytest.mark.parametrize("head_shard", [(0, 1), (1, 2), (3, 4)])
@pytest.mark.parametrize("chunk", [16, 64])
def test_delta_mixer_is_the_reference(chunk, head_shard):
    mixer = DeltaMixer(*DELTA._replace(chunk=chunk), head_shard=head_shard,
                       dtype=jnp.float32)
    u, params, mix = mixer_case(mixer, chunk + head_shard[0])
    assert params["in_proj_kernel"].shape == (
        HIDDEN, (5 * DELTA.head_dim + 1) * HEADS // head_shard[1])
    both_ways(lambda p, u: mixer.apply({"params": p}, u),
              lambda p, u: reference.kda(u, p, head_dim=DELTA.head_dim,
                                         lower_bound=DELTA.lower_bound,
                                         norm_eps=1e-6), u, params, mix, 1e-4)


def test_delta_mixer_writes_its_chunks_decay_inside_the_gates_bound():
    mixer = DeltaMixer(*DELTA, dtype=jnp.float32)
    u, params, _ = mixer_case(mixer)
    decay = sown(mixer, {"params": params}, u)["kda_chunk_log_decay_min"][0]
    assert decay.shape == ()
    assert DELTA.lower_bound * DELTA.chunk < float(decay) < 0


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("head_shard", [(0, 1), (1, 2), (3, 4)])
def test_latent_attention_is_the_reference(head_shard, use_flash):
    layer = LatentAttention(HEADS, LATENT, jnp.float32, use_flash=use_flash,
                            head_shard=head_shard)
    u, params, mix = mixer_case(layer, head_shard[0] + use_flash)
    local = HEADS // head_shard[1]
    assert params["q_kernel"].shape == (HIDDEN, local, 12)
    assert params["kv_a_kernel"].shape == (HIDDEN, 16 + 4)      # whole
    assert params["kv_b_kernel"].shape == (16, local, 8 + 8)
    both_ways(lambda p, u: layer.apply({"params": p}, u),
              lambda p, u: reference.latent_attention(
                  u, p, nope_dim=LATENT.nope_dim,
                  rope_theta=LATENT.rope_theta, norm_eps=1e-6),
              u, params, mix)


def test_gated_mlp_is_the_reference():
    layer = GatedMLP(D_FF, jnp.float32)
    u, params, mix = mixer_case(layer)
    both_ways(lambda p, u: layer.apply({"params": p}, u),
              lambda p, u: reference.gated_mlp(
                  u, *(p[n]["kernel"] for n in ("gate", "up", "down"))),
              u, params, mix)


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("shard", [(0, 1), (0, 4), (3, 4)])
def test_group_limited_experts_are_the_dense_loop(shard, bias):
    layer = SparseExperts(moe(shard), jnp.float32)
    u, params, mix = mixer_case(layer, shard[0] + bias)
    selection_bias = 0.2 * jax.random.normal(
        jax.random.PRNGKey(5), (EXPERTS,)) if bias else None
    config = dict(num_experts=EXPERTS, expert_shard=shard,
                  **routing(selection_bias=selection_bias))
    buffers = {"buffers": {"selection_bias": selection_bias}} if bias else {}

    def system(p, u):
        return layer.apply({"params": p, **buffers}, u)

    def plain(p, u):
        return reference.sparse_experts(u.reshape(-1, HIDDEN), p,
                                        **config)[0].reshape(u.shape)

    both_ways(system, plain, u, params, mix)
    wrote = sown(layer, {"params": params, **buffers}, u)
    flat = u.reshape(-1, HIDDEN)
    _, want, want_groups = with_highest(reference.router)(
        flat, params["router_kernel"], **routing(
            selection_bias=selection_bias))
    chose, groups = wrote["chosen_experts"][0], wrote["groups_chosen"][0]
    np.testing.assert_array_equal(jnp.sort(chose, -1), jnp.sort(want, -1))
    np.testing.assert_array_equal(jnp.sort(groups, -1),
                                  jnp.sort(want_groups, -1))
    # No token holds an expert outside its KEPT groups, and the choice is
    # not the plain top-k's (the groups bind at these sizes).
    per_group = EXPERTS // GROUPS
    assert groups.shape == (flat.shape[0], KEPT)
    assert bool((chose[..., None] // per_group
                 == groups[:, None, :]).any(-1).all())
    scores = jax.nn.sigmoid(flat @ params["router_kernel"])
    if bias:
        scores = scores + selection_bias
    plain_top = jax.lax.top_k(scores, PER_TOKEN)[1]
    assert not bool((jnp.sort(plain_top, -1) == jnp.sort(chose, -1)).all())


def test_one_group_is_the_plain_choice():
    """`n_group` 1 is the program before the groups: the same jaxpr as a
    configuration that never names them."""
    named = MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (0, 4), None, "sigmoid",
                      True, SCALE, "relu2", 32, SHARED, 1, 1)
    unnamed = MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (0, 4), None, "sigmoid",
                        True, SCALE, "relu2", 32, SHARED)
    u = jnp.zeros((2, SEQ, HIDDEN))
    texts = []
    for cfg in (named, unnamed):
        layer = SparseExperts(cfg, jnp.float32)
        params = jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), u)["params"])
        texts.append(jax.jit(jax.grad(
            lambda p, u: layer.apply({"params": p}, u).sum())).lower(
                params, u).as_text())
    assert texts[0] == texts[1] and "groups" not in texts[0]


@pytest.mark.parametrize("field,value", [("n_group", 3), ("topk_group", 5),
                                         ("scoring", "softmax")])
def test_sparse_experts_refuse_groups_they_cannot_form(field, value):
    cfg = moe()._replace(**{field: value})
    with pytest.raises(ValueError, match="n_group"):
        SparseExperts(cfg, jnp.float32).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, HIDDEN)))


# --- the shares add up to the uncut layer ---------------------------------

def delta_share(p, shard, n):
    inner = HEADS * DELTA.head_dim

    def heads(v, width=HEADS):
        return columns(v, [width], shard, n)

    return {"in_proj_kernel": columns(p["in_proj_kernel"],
                                      [inner] * 5 + [HEADS], shard, n),
            "conv_kernel": columns(p["conv_kernel"], [inner] * 3, shard, n),
            "dt_bias": heads(p["dt_bias"], inner),
            "A_log": heads(p["A_log"]),
            "norm_scale": p["norm_scale"],               # one for every head
            "out_proj_kernel": heads(p["out_proj_kernel"].T, inner).T}
