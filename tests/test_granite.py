"""What Granite-4.0-H's layers ask of the library, each alone: the chunked scan
at ONE group under many heads against the token-by-token recurrence the
benchmark keeps (benchmark/reference/granite_lm.py), and the four fields a muP
model with a tied head adds to `models.TransformerLM` — `tie_head`,
`residual_scale`, `logits_divisor`, `attn_scale` — each against its
hand-written form, and each at its default leaving an existing model's traced
program and parameter tree as they were.  The whole model is
tests/test_granite_model.py's.  CPU, float32, seeded weights.

Tolerances: float32 rounding through a handful of products, 1e-5 of the
largest value where one product separates the two sides and 1e-4 for the
chunked scan (sums of 64 to 256 terms in another order than the recurrence's).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_lm as reference
from horovod_tpu.models import (Mamba2Config, TransformerLM, next_token_loss,
                                record_ssm_carry)
from horovod_tpu.models.ssm import CARRY_LIVE
from horovod_tpu.models.transformer import Attention, looped_exit_loss
from horovod_tpu.ops.ssm import STAGES, chunked_scan
from tests.test_hybrid import close, seeded, trees_close, with_highest

VOCAB, HIDDEN, SEQ = 256, 64, 128
SSM = Mamba2Config(heads=16, head_dim=8, groups=1, state=16, conv=4, chunk=64)
LAYERS = ("ssm", "gated_mlp", "attention", "gated_mlp")
MUP = dict(embed_scale=12.0, tie_head=True, residual_scale=0.22,
           logits_divisor=8.0, attn_scale=1.0 / 64)


def lm(**fields):
    return TransformerLM(**{**dict(
        vocab_size=VOCAB, d_model=HIDDEN, n_heads=4, d_ff=96,
        dtype=jnp.float32, use_flash=False, norm_eps=1e-5, layers=LAYERS,
        ssm=SSM, n_kv_heads=2, head_dim=16, rope=False), **fields})


# --- the scan at one group under many heads ---------------------------------

def scan_inputs(seed, heads=SSM.heads, seq=SEQ):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (2, seq, heads, SSM.head_dim))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, seq, heads)) - 2.0)
    A = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    B = jax.random.normal(keys[3], (2, seq, 1, SSM.state))
    C = jax.random.normal(keys[4], (2, seq, 1, SSM.state))
    D = jax.random.normal(keys[5], (heads,))
    return (x, dt, A, B, C, D), jax.random.normal(keys[6], x.shape)


@pytest.mark.parametrize("chunk", [64, 32])     # the chunk's like, and half
def test_one_groups_scan_is_the_token_by_token_recurrence(chunk):
    """Sixteen heads that all read ONE B and C: values, every gradient, and
    the chunks' summed log-decays."""
    args, mix = scan_inputs(chunk)
    got, whole = jax.jit(lambda *a: chunked_scan(*a, chunk))(*args)
    close(got, with_highest(reference.recurrence)(*args), 1e-4)
    assert whole.shape == (2, SEQ // chunk, 1, SSM.heads)
    x, dt, A = args[:3]
    close(whole[:, :, 0], (dt * A).reshape(2, SEQ // chunk, chunk, -1).sum(2),
          1e-5)

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    got = jax.jit(jax.grad(total(lambda *a: chunked_scan(*a, chunk)[0]),
                           argnums=range(6)))(*args)
    want = with_highest(jax.grad(total(reference.recurrence),
                                 argnums=range(6)))(*args)
    for g, w in zip(got, want):
        close(g, w, 1e-4)


def test_the_scans_decays_and_states_are_float32_under_bfloat16_operands():
    """What the configuration states float32 and no tolerance of the chip's
    comparison can hold (benchmark/reference/granite_lm.py): with bfloat16 x,
    B and C every cumulative sum and every exponential is float32, every
    product accumulates in float32, and the states between chunks (`ends`,
    `entering`) are float32 arrays that only the last product rounds."""
    args, _ = scan_inputs(0)
    x, dt, A, B, C, D = args
    narrow = (x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
              C.astype(jnp.bfloat16), D)
    jaxpr = jax.make_jaxpr(lambda *a: chunked_scan(*a, 32))(*narrow)

    def equations(inner):               # `jnp.cumsum`, `tril` are jits
        for equation in inner.eqns:
            yield equation
            if "jaxpr" in equation.params:
                yield from equations(equation.params["jaxpr"].jaxpr)

    kinds = {}
    for equation in equations(jaxpr.jaxpr):
        kinds.setdefault(equation.primitive.name, []).append(
            equation.outvars[0].aval.dtype)
    for name in ("exp", "cumsum", "dot_general"):
        assert kinds[name] and set(kinds[name]) == {jnp.dtype("float32")}, name
    # The carry's own product reads float32 states in full precision.
    (carry,) = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"
                and all(v.aval.dtype == jnp.float32 for v in e.invars)]
    assert "HIGHEST" in str(carry.params["precision"]).upper()
    y, whole = jax.jit(lambda *a: chunked_scan(*a, 32))(*narrow)
    assert y.dtype == whole.dtype == jnp.float32


def test_the_scans_four_stages_partition_its_operations():
    """Every equation `chunked_scan` traces lies under exactly one of the four
    stage scopes (a caller's own scope is in front of them)."""
    args, _ = scan_inputs(0)
    jaxpr = jax.make_jaxpr(lambda *a: chunked_scan(*a, 32))(*args)
    seen = set()
    for equation in jaxpr.eqns:
        stack = str(equation.source_info.name_stack)
        stages = [s for s in STAGES if f"hvd_ssm_scan_{s}" in stack]
        if equation.primitive.name in ("reshape", "convert_element_type") \
                and not stages:
            continue        # the operands' views and casts, before any stage
        assert len(stages) == 1, (equation.primitive.name, stack)
        seen.add(stages[0])
    assert seen == set(STAGES)


def test_the_mixer_counts_the_chunks_that_carry_state_on():
    model = lm()
    params, (tokens, _) = seeded(model, vocab=VOCAB)
    _, wrote = model.apply({"params": params}, tokens,
                           mutable=["intermediates"])
    seen = record_ssm_carry(wrote["intermediates"])
    chunks = 2 * (SEQ // SSM.chunk) * SSM.heads
    assert seen["chunks"] == [chunks]
    (carried,) = seen["chunks_carried"]
    assert 0 < carried <= chunks
    # By hand, from the layer's own dt and A.
    mixer = params["layer_0"]["mixer"]
    x = params["embed"]["embedding"][tokens]
    u = reference.rms_norm(x, params["layer_0"]["norm"]["scale"], 1e-5)
    dt = jax.nn.softplus((u @ mixer["in_proj_kernel"])[..., -SSM.heads:]
                         + mixer["dt_bias"])
    whole = (dt * -jnp.exp(mixer["A_log"])).reshape(
        2, SEQ // SSM.chunk, SSM.chunk, -1).sum(2)
    assert carried == int((jnp.exp(whole) > CARRY_LIVE).sum())
    assert record_ssm_carry({}) == {"chunks_carried": [], "chunks": []}
    # Mirrored into the registry where it is on, and rendered.
    from horovod_tpu.common import metrics

    metrics.registry.reset()
    metrics.registry.enabled = True
    try:
        assert record_ssm_carry(wrote["intermediates"]) == seen
        assert metrics.registry.snapshot()["ssm"] == seen
        text = metrics.prometheus_text(metrics.registry.snapshot())
        assert f'hvd_tpu_ssm_chunks{{layer="0",kind="carried"}} {carried}' \
            in text
        assert f'hvd_tpu_ssm_chunks{{layer="0",kind="all"}} {chunks}' in text
    finally:
        metrics.registry.enabled = False
        metrics.registry.reset()


# --- the tied head -----------------------------------------------------------

def untied_twin(params):
    """The tied model's parameters as the untied model holds them: the head a
    copy of the table, transposed."""
    return {**params, "lm_head_kernel": params["embed"]["embedding"].T}


@pytest.mark.parametrize("fused", [False, True])
def test_the_tied_tables_gradient_is_the_lookups_plus_the_heads(fused):
    tied, untied = lm(tie_head=True), lm()
    params, (inputs, targets) = seeded(tied, vocab=VOCAB)
    assert "lm_head_kernel" not in params
    assert set(untied_twin(params)) == set(seeded(untied, vocab=VOCAB)[0])

    def loss(model, p):
        if fused:
            return model.apply({"params": p}, inputs, targets=targets)
        return next_token_loss(model.apply({"params": p}, inputs), targets)

    got, got_grads = jax.jit(jax.value_and_grad(
        functools.partial(loss, tied)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        functools.partial(loss, untied)))(untied_twin(params))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    head = want_grads.pop("lm_head_kernel")
    lookup = want_grads["embed"]["embedding"]
    # Neither part is small beside the other: a sum that dropped one shows.
    assert 0.05 < float(jnp.linalg.norm(lookup) / jnp.linalg.norm(head)) < 20
    want_grads["embed"] = {"embedding": lookup + head.T}
    trees_close(got_grads, want_grads, 1e-5)


# --- the three multipliers, each against its hand-written form --------------

OUTPUTS = {"ssm": ("out_proj_kernel",), "attention": ("o_kernel",),
           "gated_mlp": ("down", "kernel")}


def outputs_scaled(params, factor):
    """`params` with every pattern entry's LAST product times `factor`: `x +
    factor * Mixer(N(x))` written into the weights."""
    out = jax.tree.map(lambda leaf: leaf, params)
    for i, kind in enumerate(LAYERS):
        node = out[f"layer_{i}"]["mixer"]
        for key in OUTPUTS[kind][:-1]:
            node = node[key]
        node[OUTPUTS[kind][-1]] = node[OUTPUTS[kind][-1]] * factor
    return out


def test_the_residual_multiplier_scales_what_joins_the_stream():
    scaled, plain = lm(residual_scale=0.22), lm()
    params, (inputs, _) = seeded(plain, vocab=VOCAB)
    got = jax.jit(scaled.apply)({"params": params}, inputs)
    want = jax.jit(plain.apply)({"params": outputs_scaled(params, 0.22)},
                                inputs)
    close(got, want, 1e-5)
    assert float(jnp.abs(got - jax.jit(plain.apply)(
        {"params": params}, inputs)).max()) > 0.1    # and it is no no-op


def test_the_logits_divisor_divides_on_both_loss_paths():
    divided, plain = lm(logits_divisor=8.0), lm()
    params, (inputs, targets) = seeded(plain, vocab=VOCAB)
    logits = jax.jit(plain.apply)({"params": params}, inputs)
    close(jax.jit(divided.apply)({"params": params}, inputs), logits / 8.0,
          1e-6)
    want = next_token_loss(logits / 8.0, targets)
    assert abs(float(want) - float(next_token_loss(logits, targets))) > 0.1
    np.testing.assert_allclose(
        jax.jit(lambda p: divided.apply({"params": p}, inputs,
                                        targets=targets))(params),
        want, rtol=1e-6)


def test_the_logits_divisor_reaches_a_looped_models_per_pass_head():
    fields = dict(loops=2, exit_gate=True)
    divided, plain = lm(logits_divisor=8.0, **fields), lm(**fields)
    params, (inputs, targets) = seeded(plain, vocab=VOCAB)
    logits, gate = jax.jit(plain.apply)({"params": params}, inputs)
    got_logits, got_gate = jax.jit(divided.apply)({"params": params}, inputs)
    close(got_logits, logits / 8.0, 1e-6)
    per_token, _ = jax.jit(lambda p: divided.apply(
        {"params": p}, inputs, targets=targets))(params)
    want = jnp.stack([-jnp.take_along_axis(
        jax.nn.log_softmax(one / 8.0), targets[..., None], -1)[..., 0]
        for one in logits])
    close(per_token, want, 1e-5)
    assert np.isfinite(float(looped_exit_loss(per_token, got_gate)))


def masked_softmax_attention(p, u, scale):
    """Grouped-query attention without a position embedding, the softmax of
    `scale * q k^T` under a causal mask, whole rows at once."""
    q = jnp.einsum("bsd,dhe->bhse", u, p["q_kernel"])
    k, v = jnp.einsum("bsd,djhe->jbhse", u, p["kv_kernel"])
    k, v = (jnp.repeat(t, q.shape[1] // k.shape[1], axis=1) for t in (k, v))
    scores = scale * jnp.einsum("bhse,bhte->bhst", q, k)
    seen = jnp.tril(jnp.ones(scores.shape[-2:], bool))
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhse,hed->bsd", jnp.einsum("bhst,bhte->bhse", weights,
                                                  v), p["o_kernel"])


@pytest.mark.parametrize("use_flash", [False, True])
def test_the_attention_scale_is_the_softmaxs_own(use_flash):
    layer = Attention(n_heads=4, dtype=jnp.float32, use_flash=use_flash,
                      n_kv_heads=2, rope=False, head_dim=16,
                      sm_scale=1.0 / 64)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, HIDDEN))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    mix = jax.random.normal(jax.random.PRNGKey(2), u.shape)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda p, u: (fn(p, u) * mix).sum(), argnums=(0, 1)))(params, u)

    got = both(lambda p, u: layer.apply({"params": p}, u))
    want = both(functools.partial(masked_softmax_attention, scale=1.0 / 64))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    trees_close(got[1], want[1], 1e-5)
    # 16 ** -0.5 is sixteen times 1 / 64: the default is another layer.
    default = both(lambda p, u: layer.clone(sm_scale=None).apply(
        {"params": p}, u))
    assert abs(float(default[0]) - float(want[0])) > 1e-2 * abs(float(want[0]))


def test_the_attention_scale_refuses_the_ring():
    layer = Attention(n_heads=4, dtype=jnp.float32, seq_axis="seq",
                      sm_scale=0.1)
    with pytest.raises(ValueError, match="sm_scale"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, HIDDEN)))


def test_a_model_of_blocks_refuses_the_patterns_multipliers():
    for field in ("residual_scale", "attn_scale"):
        model = TransformerLM(vocab_size=VOCAB, d_model=HIDDEN, n_layers=1,
                              n_heads=4, dtype=jnp.float32, **{field: 0.5})
        with pytest.raises(ValueError, match="pattern"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_a_model_of_blocks_ties_its_head_and_divides_its_logits():
    fields = dict(vocab_size=VOCAB, d_model=HIDDEN, n_layers=1, n_heads=4,
                  dtype=jnp.float32, use_flash=False)
    tied = TransformerLM(tie_head=True, logits_divisor=4.0, **fields)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, VOCAB)
    params = tied.init(jax.random.PRNGKey(1), tokens)["params"]
    assert set(params) == {"embed", "final_norm", "layer_0"}
    want = TransformerLM(**fields).apply(
        {"params": untied_twin(params)}, tokens) / 4.0
    close(tied.apply({"params": params}, tokens), want, 1e-6)


# --- at their defaults nothing is traced ------------------------------------

def traced(model, fused=False):
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               tokens)["params"])

    def loss(p, t):
        if fused:
            return model.apply({"params": p}, t, targets=t)
        return next_token_loss(model.apply({"params": p}, t), t)

    return str(jax.make_jaxpr(jax.value_and_grad(loss))(shapes, tokens)), \
        jax.tree.map(lambda leaf: (leaf.shape, leaf.dtype), shapes)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("field,identity", [
    ("residual_scale", 1.0), ("logits_divisor", 1.0), ("attn_scale", 0.25)])
def test_a_field_at_its_default_traces_nothing_of_itself(field, identity,
                                                         fused):
    """The unset model's program and tree are the model's with every new
    field spelt out as unset; a field at the value that changes no number
    still ADDS its operation (a multiply, a divide, a scale handed to the
    kernel), so the unset path is not `* 1.0`."""
    base, base_tree = traced(lm(), fused)
    spelt, spelt_tree = traced(lm(tie_head=False, residual_scale=None,
                                  logits_divisor=None, attn_scale=None),
                               fused)
    assert base == spelt and base_tree == spelt_tree
    assert "lm_head_kernel" in base_tree
    at_identity, tree = traced(lm(**{field: identity}), fused)
    assert tree == base_tree
    if field == "attn_scale":       # head 16: 0.25 is the default's number
        return
    op = " div " if field == "logits_divisor" else " mul "
    assert at_identity.count(op) > base.count(op)


def test_the_tied_tree_lacks_the_head_and_nothing_else():
    _, base_tree = traced(lm())
    _, tied_tree = traced(lm(tie_head=True))
    base_tree.pop("lm_head_kernel")
    assert tied_tree == base_tree


def test_every_field_together_is_each_in_turn():
    """The four fields and `embed_scale` at Granite's numbers against the
    plain reference's forward pass and loss (tests/test_granite_model.py has
    the gradients at the builder's sizes)."""
    model = lm(**MUP)
    params, batch = seeded(model, vocab=VOCAB)
    got = jax.jit(lambda p, b: next_token_loss(
        model.apply({"params": p}, b[0]), b[1]))(params, batch)
    want = with_highest(lambda p, b: reference.loss(
        p, b, layers=LAYERS, ssm_head_dim=SSM.head_dim, ssm_state=SSM.state,
        norm_eps=1e-5, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1.0 / 64, logits_scaling=8.0))(params, batch)
    np.testing.assert_allclose(got, want, rtol=2e-5)
