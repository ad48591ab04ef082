"""What Olmo-Hybrid adds to the layers of models.TransformerLM — a Gated
DeltaNet mixer whose keys and values have widths of their own and whose step
may pass 1, a layer whose one norm is on its mixer's OUTPUT, a q/k norm whose
statistic crosses the head shards — against the plain float32 reference the
benchmark keeps (benchmark/reference/olmohybrid_lm.py): the delta rule one
step a token, a plain softmax.  CPU, float32, seeded weights, small sizes; the
whole model is tests/test_olmohybrid_model.py's.

Tolerances: both sides are float32 and differ in the order of their sums
(products over chunks and a solve against a step a token), so they agree to
float32 rounding: 2e-5 of the largest value, and 1e-4 through the chunked
rule, whose solve multiplies 64 x 64 matrices — at steps up to 1.9 as at steps
under 1 (`test_rule_at_two_widths_and_steps_past_one_is_the_recurrence`).
bfloat16 anywhere would read 1e-3 to 1e-2 and fail every case.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.reference import olmohybrid_lm as reference
from horovod_tpu.models import (DeltaConfig, DeltaMixer, record_delta_steps)
from horovod_tpu.models.transformer import (LAYER_KINDS, NORM_PLACEMENTS,
                                            Attention, LayerOptions,
                                            MixerLayer)
from horovod_tpu.ops.delta_rule import chunked_delta_rule
from tests.test_hybrid import (both_ways, close, mixer_case, sown,
                               trees_close, with_highest)

HIDDEN, SEQ = 64, 128
HEADS, KEY_DIM, VALUE_DIM = 4, 12, 24
DELTA = DeltaConfig(heads=HEADS, head_dim=KEY_DIM, conv=4, chunk=32,
                    value_head_dim=VALUE_DIM, beta_scale=2.0)
ATTN_HEADS = 4                # attention: heads of 16


# --- the delta rule at two widths and steps past one ------------------------

@functools.partial(jax.jit, static_argnames=("seed", "seq", "heads", "d_k",
                                             "d_v", "scale"))
def rule_inputs(seed, seq, heads, d_k, d_v, scale=2.0):
    """Unit keys, queries at d_k^-1/2, a head's log-decay between 0 and -2 a
    step, and a step `scale sigmoid(b)` with `b` of deviation 3: at `scale` 2
    a sixth of the steps are past 1.9."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    of_heads = (2, seq, heads)

    def unit(key):
        t = jax.random.normal(key, of_heads + (d_k,))
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q, k = unit(keys[0]) * d_k ** -0.5, unit(keys[1])
    v = jax.random.normal(keys[2], of_heads + (d_v,))
    log_alpha = -2.0 * jax.random.uniform(keys[3], of_heads) ** 3
    beta = scale * jax.nn.sigmoid(3.0 * jax.random.normal(keys[4], of_heads))
    mix = jax.random.normal(keys[5], of_heads + (d_v,))
    return (q, k, v, log_alpha, beta), mix


def rule_and_gradients(fn, args, mix):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a) * mix).sum(), argnums=range(5)))(*args)


# (seq, chunk, heads, d_k, d_v): the rehearsal's widths over chunks that do
# and do not divide the length, and the published 96 and 192 — three
# quarters of a lane tile and one and a half — over two chunks.
RULE_CASES = [(128, 32, 4, 12, 24), (100, 64, 4, 12, 24),
              (72, 16, 2, 24, 12), (128, 64, 2, 96, 192)]


@pytest.mark.parametrize("seq,chunk,heads,d_k,d_v", RULE_CASES)
def test_rule_at_two_widths_and_steps_past_one_is_the_recurrence(
        seq, chunk, heads, d_k, d_v):
    """`chunked_delta_rule` with d_k != d_v (the Pallas interpreter runs the
    carry's kernels here) and beta up to 2 against the rule one step a token:
    `o` and the gradients of all five operands.  What `ops/delta_rule.py`'s
    docstring says of the step's range rests on this."""
    args, mix = rule_inputs(seq + d_k, seq, heads, d_k, d_v)
    assert float(args[4].max()) > 1.9 and float(args[4].min()) < 0.1
    got = rule_and_gradients(
        lambda *a: chunked_delta_rule(*a, chunk, scope="hvd_gdn_scan")[0],
        args, mix)
    want = rule_and_gradients(reference.delta_recurrence, args, mix)
    assert got[0].shape == () and got[1][2].shape == args[2].shape
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        close(g, w, 1e-4)


def test_steps_past_one_are_as_exact_as_steps_under_it():
    """The solve's error does not grow with the step: against the recurrence,
    `o` at beta up to 2 is as close as at beta under 1 on the same keys (I + A
    has entries twice as large; forward substitution in full precision does
    not care)."""
    errors = []
    for scale in (1.0, 2.0):
        args, _ = rule_inputs(5, 128, 4, 12, 24, scale)
        got = jax.jit(lambda *a: chunked_delta_rule(*a, 64)[0])(*args)
        want = jax.jit(reference.delta_recurrence)(*args)
        errors.append(float(jnp.abs(got - want).max()
                            / jnp.abs(want).max()))
    assert max(errors) < 2e-5 and errors[1] < 4 * errors[0] + 1e-6, errors


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [64, 16])
def test_solve_kernels_at_the_published_widths_are_the_float64_solve(
        chunk, dtype):
    """The solve's pair of kernels at keys of 96 and values of 192, a value
    head a key head, `beta` up to 2: `T`, `W`, `U0` and every cotangent
    against `np.linalg.inv` (`tests/test_qwen3next.py` has the case and the
    other widths)."""
    from tests.test_qwen3next import check_solve_kernels

    check_solve_kernels(96, 192, 1, chunk, dtype)


@pytest.mark.parametrize("d_k,d_v", [(12, 24), (96, 192)])
def test_head_form_at_two_widths_is_the_channel_form_on_a_decay_broadcast(
        d_k, d_v):
    """The head form (its solve and carry kernels) against the channel form's
    code path (`_solved`, `_unit_lower_inverse`, the loops) fed the same
    decay on every channel, at d_k != d_v and `beta` up to 2: output and the
    five gradients (ungrouped heads and whole chunks, which is what the
    channel form takes; its gate's bound holds the decays over -5.8)."""
    (q, k, v, log_alpha, beta), mix = rule_inputs(d_k, 128, 2, d_k, d_v)
    log_alpha = jnp.maximum(log_alpha, -5.0)

    def wide(q, k, v, log_alpha, beta):
        return chunked_delta_rule(
            q, k, v, jnp.broadcast_to(log_alpha[..., None], q.shape), beta,
            64)[0]

    got = rule_and_gradients(
        lambda *a: chunked_delta_rule(*a, 64)[0],
        (q, k, v, log_alpha, beta), mix)
    want = rule_and_gradients(wide, (q, k, v, log_alpha, beta), mix)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, w in zip(got[1], want[1]):
        close(g, w, 1e-4)


# --- the mixer --------------------------------------------------------------

def steep(params, scale=3.0):
    """`params` with the columns of `W_in` that make `b` multiplied, so that
    the step `2 sigmoid(b)` reaches 1.9 on a seeded input."""
    w = params["in_proj_kernel"]
    heads = params["A_log"].shape[0]
    return dict(params, in_proj_kernel=w.at[:, -2 * heads:-heads].multiply(
        scale))


def largest_step(params, u, scale=2.0):
    heads = params["A_log"].shape[0]
    b = u @ params["in_proj_kernel"][:, -2 * heads:-heads]
    return float(scale * jax.nn.sigmoid(b).max())


@pytest.mark.parametrize("head_shard", [(0, 1), (1, 2)])
@pytest.mark.parametrize("chunk", [16, 64])
def test_gated_delta_mixer_at_two_widths_is_the_reference(chunk, head_shard):
    mixer = DeltaMixer(*DELTA._replace(chunk=chunk), gate="head",
                       head_shard=head_shard, dtype=jnp.float32)
    u, params, mix = mixer_case(mixer, chunk + head_shard[0])
    params = steep(params)
    n = head_shard[1]
    keys, values = HEADS * KEY_DIM // n, HEADS * VALUE_DIM // n
    assert params["in_proj_kernel"].shape == (
        HIDDEN, 2 * keys + 2 * values + 2 * HEADS // n)
    assert params["conv_kernel"].shape == (4, 2 * keys + values)
    assert params["norm_scale"].shape == (VALUE_DIM,)
    assert params["out_proj_kernel"].shape == (values, HIDDEN)
    assert largest_step(params, u) > 1.9
    both_ways(lambda p, u: mixer.apply({"params": p}, u),
              lambda p, u: reference.gated_delta(
                  u, p, key_dim=KEY_DIM, beta_scale=2.0, norm_eps=1e-6),
              u, params, mix, 1e-4)


def test_gated_delta_mixer_at_the_published_widths_is_the_reference():
    """Two heads of 96 and 192, as published, over a short sequence."""
    mixer = DeltaMixer(2, 96, 4, 32, value_head_dim=192, beta_scale=2.0,
                       gate="head", dtype=jnp.float32)

    def make(key):
        keys = jax.random.split(key, 3)
        u = jax.random.normal(keys[0], (1, 64, HIDDEN))
        return u, mixer.init(keys[1], u)["params"], jax.random.normal(
            keys[2], u.shape)

    u, params, mix = jax.jit(make)(jax.random.PRNGKey(1))
    params = steep(params)
    assert params["in_proj_kernel"].shape == (HIDDEN, 2 * 192 + 2 * 384 + 4)
    assert largest_step(params, u) > 1.9
    both_ways(lambda p, u: mixer.apply({"params": p}, u),
              lambda p, u: reference.gated_delta(
                  u, p, key_dim=96, beta_scale=2.0, norm_eps=1e-6),
              u, params, mix, 1e-4)


def test_a_scaled_step_counts_its_steps_past_one():
    """`gdn_beta_over_one` and `gdn_beta_steps` beside the chunks' decay,
    about half on seeded weights, and `record_delta_steps` mirrors them into
    the registry; the plain step sows neither (Qwen3-Next's program gains no
    operation: tests/test_qwen3next.py pins what it sows)."""
    from horovod_tpu.common import metrics

    mixer = DeltaMixer(*DELTA, gate="head", dtype=jnp.float32)
    u, params, _ = mixer_case(mixer)
    wrote = sown(mixer, {"params": params}, u)
    assert set(wrote) == {"gdn_chunk_log_decay_min", "gdn_beta_over_one",
                          "gdn_beta_steps"}
    steps, over = int(wrote["gdn_beta_steps"][0]), int(
        wrote["gdn_beta_over_one"][0])
    assert steps == 2 * SEQ * HEADS and 0.4 * steps < over < 0.6 * steps
    metrics.registry.reset()
    metrics.registry.enabled = True
    try:
        recorded = record_delta_steps({"layer_0": {"mixer": wrote}})
        assert recorded == {"beta_over_one": [over], "beta_steps": [steps]}
        assert metrics.registry.snapshot()["delta"] == recorded
        text = metrics.prometheus_text(metrics.registry.snapshot())
        assert f'hvd_tpu_delta_steps{{layer="0",kind="over_one"}} {over}' \
            in text
    finally:
        metrics.registry.enabled = False
        metrics.registry.reset()
    plain = DeltaMixer(*DELTA._replace(beta_scale=1.0), gate="head",
                       dtype=jnp.float32)
    assert set(sown(plain, {"params": params}, u)) == {
        "gdn_chunk_log_decay_min"}
    assert record_delta_steps({}) == {"beta_over_one": [], "beta_steps": []}


@pytest.mark.parametrize("more", [dict(value_head_dim=VALUE_DIM),
                                  dict(beta_scale=2.0)])
def test_the_channel_gate_refuses_the_head_gates_options(more):
    mixer = DeltaMixer(HEADS, KEY_DIM, **more)
    with pytest.raises(ValueError, match="gate='head'"):
        mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, SEQ, HIDDEN)))


def test_the_two_fields_unset_are_the_mixer_of_before():
    """`value_head_dim=None, beta_scale=1.0` build the parameters and the
    program a mixer that never names them does, under either gate."""
    u = jnp.zeros((1, SEQ, HIDDEN))
    for gate in ("channel", "head"):
        named = DeltaMixer(*DeltaConfig(HEADS, KEY_DIM, 4, 32,
                                        value_head_dim=None, beta_scale=1.0),
                           gate=gate, dtype=jnp.float32)
        unnamed = DeltaMixer(HEADS, KEY_DIM, 4, 32, gate=gate,
                             dtype=jnp.float32)
        shapes = jax.eval_shape(lambda: named.init(jax.random.PRNGKey(0), u))
        texts = [jax.jit(m.apply).lower(shapes, u).as_text()
                 for m in (named, unnamed)]
        assert texts[0] == texts[1] and "beta_over_one" not in texts[0]
    same_width = DeltaMixer(HEADS, KEY_DIM, 4, 32, value_head_dim=KEY_DIM,
                            gate="head", dtype=jnp.float32)
    assert jax.eval_shape(
        lambda: same_width.init(jax.random.PRNGKey(0), u)) == shapes


# --- where a layer's norms stand --------------------------------------------

def placed(post_norm, kind="gated_mlp"):
    return MixerLayer(kind, LayerOptions(
        n_heads=ATTN_HEADS, d_ff=96, dtype=jnp.float32, post_norm=post_norm))


@pytest.mark.parametrize("post_norm,tree", [
    (False, {"norm", "mixer"}), (True, {"norm", "mixer", "post_norm"}),
    ("only", {"mixer", "post_norm"})])
def test_a_layers_norms_stand_where_post_norm_puts_them(post_norm, tree):
    """The three placements against the composition written out: the
    pre-norm, both norms (Trinity's and Ouro's layer, whose trees keep
    their names) and the output's norm alone on a mixer that reads the bare
    residual stream (Olmo's)."""
    layer = placed(post_norm)
    x, params, mix = mixer_case(layer, 2)
    assert set(params) == tree
    mlp = LAYER_KINDS["gated_mlp"].mixer(d_ff=96, dtype=jnp.float32)

    def norm(name, t):
        return nn.RMSNorm(epsilon=1e-6, dtype=jnp.float32).apply(
            {"params": params[name]}, t) if name in params else t

    want = x + norm("post_norm", mlp.apply(
        {"params": params["mixer"]}, norm("norm", x)))
    close(jax.jit(layer.apply)({"params": params}, x), want)
    assert NORM_PLACEMENTS[post_norm] == ("norm" in tree,
                                          "post_norm" in tree)


def test_an_output_normed_layer_is_the_reference():
    layer = placed("only", "attention")
    layer = layer.clone(options=layer.options._replace(
        qk_norm=True, rope=False, n_kv_heads=ATTN_HEADS, use_flash=False))
    x, params, mix = mixer_case(layer, 4)
    both_ways(lambda p, x: layer.apply({"params": p}, x),
              lambda p, x: reference.layer(
                  x, p, "attention", norm_eps=1e-6, key_dim=KEY_DIM,
                  beta_scale=2.0), x, params, mix)


def test_a_layer_refuses_a_placement_it_does_not_know():
    with pytest.raises(ValueError, match="post_norm"):
        placed("post").init(jax.random.PRNGKey(0), jnp.zeros((1, 8, HIDDEN)))


# --- the q/k norm under a head share ----------------------------------------

def attention(head_shard=(0, 1), axis=None):
    # Side by side the flash kernels (the interpreter runs them here): the
    # blockwise scan's carry does not vary over a mesh axis as its inputs do.
    return Attention(ATTN_HEADS, jnp.float32, use_flash=axis is not None,
                     qk_norm=True, n_kv_heads=ATTN_HEADS, rope=False,
                     head_shard=head_shard, head_shard_axis=axis)


def attention_share(p, shard, n):
    """Heads `[shard H/n, (shard + 1) H/n)` of a grouped layer's tree."""
    held = slice(shard * ATTN_HEADS // n, (shard + 1) * ATTN_HEADS // n)
    return {"q_kernel": p["q_kernel"][:, held],
            "kv_kernel": p["kv_kernel"][:, :, held],
            "q_norm_scale": p["q_norm_scale"][held],
            "k_norm_scale": p["k_norm_scale"][held],
            "o_kernel": p["o_kernel"][held]}


def test_qk_norm_under_an_axis_is_the_whole_projections():
    """Two head shards side by side under `head_shard_axis`: the q/k
    statistic is summed over the axis, so the two outputs add up to the
    uncut layer's, values and gradients (one float a token crosses heads);
    without the axis each shard norms over the heads it holds, and is the
    reference given the same share — the layer without its exchange."""
    whole = attention()
    u, params, mix = mixer_case(whole, 6)
    shares = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                          *(attention_share(params, i, 2) for i in range(2)))
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))

    def side_by_side(shares, u):
        def local(share, u):
            out = attention((0, 2), "tensor").apply(
                {"params": jax.tree.map(lambda t: t[0], share)}, u)
            return jax.lax.psum(out, "tensor")
        return jax.shard_map(local, mesh=mesh, in_specs=(P("tensor"), P()),
                             out_specs=P())(shares, u)

    def total(fn):
        return jax.jit(jax.value_and_grad(
            lambda p, u: (fn(p, u) * mix).sum(), (0, 1)))

    got, got_grads = total(side_by_side)(shares, u)
    want, want_grads = total(
        lambda p, u: whole.apply({"params": p}, u))(params, u)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    close(got_grads[1], want_grads[1])
    for i in range(2):
        trees_close(jax.tree.map(lambda t: t[i], got_grads[0]),
                    attention_share(want_grads[0], i, 2))
    # Without the axis: the held heads' statistic, the reference's on the
    # share — and NOT the whole layer's part.
    alone = attention((0, 2))
    share = attention_share(params, 0, 2)
    both_ways(lambda p, u: alone.apply({"params": p}, u),
              lambda p, u: reference.attention_layer(u, p, norm_eps=1e-6),
              u, share, mix)
    summed = with_highest(lambda p, u: sum(reference.attention_layer(
        u, attention_share(p, i, 2), norm_eps=1e-6) for i in range(2)))(
            params, u)
    whole_out = jax.jit(whole.apply)({"params": params}, u)
    assert float(jnp.abs(summed - whole_out).max()) \
        > 1e-3 * float(jnp.abs(whole_out).max()), \
        "the local statistics happen to be the whole projection's"


def test_the_rules_state_and_solve_are_float32_under_bfloat16_operands():
    """What no limit of the cell can see — a reference whose state is rounded
    to bfloat16 a token moves the mixers' gradient by 4e-3 where the system's
    bfloat16 operands move it by 2e-2 (PERF.md section 6, PR 60) — is held by
    types: with bfloat16 q, k, v the kernels' state scratch and the state
    they keep for the backward are float32 at (d_k, d_v), and every product
    of the solve is a float32 product in full precision: XLA's at `highest`
    outside the kernels, sums of bfloat16 terms inside them."""
    from tests.test_ops import _pallas_eqns

    args, _ = rule_inputs(0, 128, 2, 96, 192)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: chunked_delta_rule(*a, 64, scope="hvd_gdn_scan")[0].sum(),
        range(5)))(q, k, v, *args[3:])
    kernels = {eqn.params["name"]: eqn for eqn in _pallas_eqns(jaxpr.jaxpr)}
    forward = kernels["hvd_gdn_scan_carry_fwd"]
    states = [var.aval for var in forward.outvars
              if var.aval.shape[-2:] == (96, 192)]
    assert [aval.dtype for aval in states] == [jnp.float32]

    def products(jaxpr, inside=False):
        """Every product's equation, sub-programs included: outside the
        kernels, or inside them."""
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                if inside:
                    found += products(eqn.params["jaxpr"], True)
                continue
            if eqn.primitive.name == "dot_general":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += products(sub, inside)
        return found

    # Outside the kernels: the sums of log-decays and their transposes, exact.
    exact = [eqn.params["precision"] for eqn in products(jaxpr.jaxpr)
             if all(var.aval.dtype == jnp.float32 for var in eqn.invars)]
    assert exact and all("HIGHEST" in str(p) for p in exact), exact
    # Inside the solve's kernels: T, U0 and the cotangents leave float32, and
    # every product is of bfloat16 TERMS whose sum is the float32 operand
    # (`_exact_dot`: what `highest` keeps, by hand), summed in float32 — and
    # ALL of them: six single passes for two float32 operands, three where
    # one is bfloat16 as stored, one for two such.  A chunk of 64 is two
    # levels of sub-blocks, each `T - (T a) T` of float32 matrices.  Forward
    # besides: `(T . beta G) K` and `(T . beta) V`, k and v bfloat16.
    # Backward: `dW K^T` of two bfloat16 and `dU0 V^T` of one, the two
    # products of `-T^T g T^T`, `(T . beta G)^T dW` against bfloat16 and `(T .
    # beta)^T dU0` against float32.
    passes = {"hvd_gdn_scan_solve_fwd": 2 * (6 + 6) + 3 + 3,
              "hvd_gdn_scan_solve_bwd": 1 + 3 + (6 + 6) + 3 + 6}
    for name, wanted in passes.items():
        kernel = kernels[name]
        assert [var.aval.dtype for var in kernel.outvars].count(
            jnp.float32) >= 2
        inside = products(kernel.params["jaxpr"], True)
        assert len(inside) == wanted, (name, len(inside))
        for eqn in inside:
            assert all(var.aval.dtype == jnp.bfloat16 for var in eqn.invars)
            assert eqn.params["preferred_element_type"] == jnp.float32


@pytest.mark.parametrize("left,right,passes", [
    (jnp.float32, jnp.float32, 6), (jnp.float32, jnp.bfloat16, 3),
    (jnp.bfloat16, jnp.float32, 3), (jnp.bfloat16, jnp.bfloat16, 1)])
def test_the_kernels_product_is_the_float64_product_to_float32s_rounding(
        left, right, passes):
    """`ops.delta_rule._exact_dot`, the one product of the solve's kernels:
    against float64, within 2^-20 of the sum of the terms' sizes whatever
    the operands' spread — a float32 sum of 64 terms' own rounding: it reads
    3.4e-7 to 6.4e-7 where `jnp.matmul` of float32 at `highest` reads 3.7e-7
    to 5.7e-7 —; in the passes `highest` keeps and no other; and with a
    float32 operand's third term dropped (what `high` keeps) it reads 7e-6 to
    1.3e-5, which the limit refuses."""
    from horovod_tpu.ops import delta_rule

    keys = jax.random.split(jax.random.PRNGKey(passes), 4)
    a = (jax.random.normal(keys[0], (3, 64, 64))
         * jnp.exp(4.0 * jax.random.normal(keys[1], (3, 64, 64)))).astype(left)
    b = (jax.random.normal(keys[2], (3, 64, 96))
         * jnp.exp(4.0 * jax.random.normal(keys[3], (3, 64, 96)))).astype(
             right)

    def error(got, a_axis):
        a64, b64 = (np.asarray(t.astype(jnp.float32), np.float64)
                    for t in (a, b))
        if a_axis == 0:
            a64 = a64.swapaxes(1, 2)
        return float(np.max(np.abs(np.asarray(got, np.float64) - a64 @ b64)
                            / (np.abs(a64) @ np.abs(b64))))

    for a_axis in (1, 0):
        product = functools.partial(delta_rule._exact_dot, a_axis=a_axis)
        assert error(jax.jit(product)(a, b), a_axis) < 2.0 ** -20
        assert str(jax.make_jaxpr(product)(a, b)).count(
            "dot_general") == passes
    if passes > 1:
        terms = delta_rule._terms
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(delta_rule, "_terms", lambda x: terms(x)[:2])
            assert error(delta_rule._exact_dot(a, b), 1) > 2.0 ** -18
