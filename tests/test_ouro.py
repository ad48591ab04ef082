"""The looped language model (Ouro-2.6B's: `TransformerLM(layers=, loops=T,
exit_gate=True)` with `looped_exit_loss`) at small sizes on the CPU, float32,
seeded: against the plain reference of benchmark/reference/ouro_lm.py (loss,
every pass's cross-entropy, the exit distribution, every gradient), against
today's one-pass model where `loops` is unset or 1, a looped weight's gradient
against the sum over an unrolled copy's untied passes, the exit distribution's
own properties, `recompute=True`, the loss against a hand-written case, and
the gradient exchange of the data-parallel step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import ouro_lm as reference
from horovod_tpu.models import (TransformerLM, log_exit_distribution,
                                looped_exit_loss, next_token_loss,
                                record_exit_distribution)

PASSES, BETA, THETA, EPS = 3, 0.1, 1e6, 1e-6
KINDS = ("attention", "gated_mlp") * 2
SIZES = dict(vocab_size=96, d_model=32, n_heads=4, d_ff=48, dtype=jnp.float32,
             logits_dtype=jnp.float32, layers=KINDS, post_norm=True,
             rope_theta=THETA, norm_eps=EPS)
REFERENCE = dict(layers=KINDS, passes=PASSES, beta=BETA, rope_theta=THETA,
                 norm_eps=EPS)
# Float32 on both sides; the orders of summation differ (a rolled loop, the
# flash kernels' blocks, log-sigmoids against products of sigmoids).
RTOL = 2e-5


def looped(**changes):
    return TransformerLM(**{**SIZES, "loops": PASSES, "exit_gate": True,
                            **changes})


def system_terms(model, params, batch, beta=BETA):
    ce, z = model.apply({"params": params}, batch[0], targets=batch[1])
    p = jnp.exp(log_exit_distribution(z))
    return looped_exit_loss(ce, z, beta), (ce.mean(axis=(1, 2)),
                                           p.mean(axis=(1, 2)))


@pytest.fixture(scope="module")
def seeded():
    """(params of the looped model with every norm's scale and the gate's
    bias moved off their seeded one and zero, the batch)."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0,
                                SIZES["vocab_size"])
    batch = (tokens[:, :-1], tokens[:, 1:])
    params = looped().init(jax.random.PRNGKey(0), batch[0])["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(next(keys), p.shape)
        if p.ndim <= 1 else p, params)
    return params, batch


@pytest.fixture(scope="module")
def both_sides(seeded):
    """((loss, (pass ce, exit p)), gradients) of the system and of the
    reference."""
    params, batch = seeded
    system = jax.jit(jax.value_and_grad(
        functools.partial(system_terms, looped()), has_aux=True))(
            params, batch)

    def terms(params, batch):
        loss, ce, p = reference.loss_terms(params, batch, **REFERENCE)
        return loss, (ce, p)

    with jax.default_matmul_precision("highest"):
        plain = jax.jit(jax.value_and_grad(terms, has_aux=True))(params,
                                                                 batch)
    return system, plain


def test_the_parameter_tree_is_the_one_pass_models_plus_the_gate(seeded):
    params, batch = seeded
    one_pass = TransformerLM(**SIZES).init(jax.random.PRNGKey(0),
                                           batch[0])["params"]
    extra = set(params) - set(one_pass)
    assert extra == {"exit_gate_kernel", "exit_gate_bias"}
    assert params["exit_gate_kernel"].shape == (SIZES["d_model"],)
    assert params["exit_gate_bias"].shape == ()
    rest = {k: v for k, v in params.items() if k not in extra}
    assert jax.tree.map(jnp.shape, rest) == jax.tree.map(jnp.shape, one_pass)


@pytest.mark.parametrize("what", ["loss", "pass_ce", "exit_p"])
def test_forward_against_the_reference(both_sides, what):
    ((loss_s, (ce_s, p_s)), _), ((loss_r, (ce_r, p_r)), _) = both_sides
    got, want = {"loss": (loss_s, loss_r), "pass_ce": (ce_s, ce_r),
                 "exit_p": (p_s, p_r)}[what]
    assert np.shape(got) == (() if what == "loss" else (PASSES,))
    np.testing.assert_allclose(got, want, rtol=RTOL)
    if what == "exit_p":
        np.testing.assert_allclose(np.sum(got), 1.0, rtol=1e-6)
        assert np.min(got) > 0.02         # every pass carries weight here


@pytest.mark.parametrize("group", [
    "layer_0", "layer_1", "layer_2", "layer_3", "final_norm",
    "exit_gate_kernel", "exit_gate_bias", "lm_head_kernel", "embed"])
def test_gradients_against_the_reference(both_sides, group):
    (_, grads_s), (_, grads_r) = both_sides
    got, want = (jax.tree.leaves(g[group]) for g in (grads_s, grads_r))
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0          # nothing is compared idle
        # Against the parameter's largest gradient: float32 cancellation
        # leaves small entries less than RTOL of themselves.
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=10 * RTOL * float(jnp.abs(b).max()))


def test_the_reference_refuses_a_skipped_pass(both_sides, seeded):
    """`passes_run`: the wrong program the chip's comparison must refuse — a
    pass's cross-entropy moves by far more than PASS_CE_RTOL."""
    params, batch = seeded
    (_, (ce_r, _)), _ = both_sides[1]
    with jax.default_matmul_precision("highest"):
        _, ce_short, _ = reference.loss_terms(
            params, batch, **REFERENCE, passes_run=PASSES - 1)
    error = jnp.abs(ce_short - ce_r) / ce_r
    assert float(error[:-1].max()) < RTOL
    assert float(error[-1]) > 10 * reference.PASS_CE_RTOL


@pytest.mark.parametrize("loops", [None, 1])
def test_without_a_gate_one_pass_is_todays_model(seeded, loops):
    """`loops` unset, or 1 without a gate: the parent's model parameter for
    parameter, logits and loss alike (1 takes the rolled path once)."""
    _, batch = seeded
    today = TransformerLM(**SIZES)
    params = today.init(jax.random.PRNGKey(3), batch[0])["params"]
    model = TransformerLM(**SIZES, loops=loops)
    assert jax.tree.map(jnp.shape, model.init(
        jax.random.PRNGKey(3), batch[0])["params"]) == jax.tree.map(
            jnp.shape, params)
    want = today.apply({"params": params}, batch[0])
    got = model.apply({"params": params}, batch[0])
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=RTOL)
    np.testing.assert_allclose(next_token_loss(got, batch[1]),
                               next_token_loss(want, batch[1]), rtol=RTOL)


def test_loops_without_a_gate_gives_the_last_passes_logits(seeded):
    params, batch = seeded
    logits, z = looped().apply({"params": params}, batch[0])
    assert logits.shape == (PASSES, 2, 64, SIZES["vocab_size"])
    assert z.shape == (PASSES, 2, 64)
    plain = {k: v for k, v in params.items() if not k.startswith("exit_")}
    last = TransformerLM(**SIZES, loops=PASSES).apply({"params": plain},
                                                      batch[0])
    np.testing.assert_allclose(last, logits[-1], rtol=RTOL, atol=RTOL)


def test_a_looped_weights_gradient_is_the_sum_over_untied_passes(both_sides,
                                                                 seeded):
    """An unrolled copy with a set of layer weights a pass (the reference's
    `untied`): the system's gradient of a looped weight is the sum of the
    copy's per-pass gradients, and no single pass's."""
    params, batch = seeded
    (_, grads_s), _ = both_sides

    def loss(untied):
        return reference.loss(params, batch, **REFERENCE, untied=untied)

    with jax.default_matmul_precision("highest"):
        per_pass = jax.jit(jax.grad(loss))([params] * PASSES)
    for name in ("layer_0", "layer_3"):
        total = jax.tree.map(lambda *g: sum(g),
                             *(grads[name] for grads in per_pass))
        for a, b, first in zip(jax.tree.leaves(grads_s[name]),
                               jax.tree.leaves(total),
                               jax.tree.leaves(per_pass[0][name])):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(a, b, rtol=0, atol=10 * RTOL * scale)
            assert float(jnp.abs(a - first).max()) > 0.05 * scale


def test_the_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(4), (4, 5, 7))
    log_p = log_exit_distribution(z)
    p, lam = jnp.exp(log_p), jax.nn.sigmoid(z)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[3], (1 - lam[0]) * (1 - lam[1])
                               * (1 - lam[2]), rtol=1e-4, atol=1e-7)
    # The last gate's logit is unused, and a saturated gate gives no NaN.
    np.testing.assert_array_equal(
        log_exit_distribution(z.at[-1].set(100.0)), log_p)
    hard = log_exit_distribution(jnp.array([[80.0], [-80.0], [0.0]]))
    assert bool(jnp.isfinite(hard).all())
    assert float(jnp.exp(log_exit_distribution(z[:1]))[0, 0, 0]) == 1.0


def test_looped_exit_loss_against_a_hand_written_case():
    """Two passes, two positions: lambda_1 = 1/2 and 1/5, so p = (1/2, 1/2)
    and (1/5, 4/5)."""
    ce = jnp.array([[[2.0, 4.0]], [[1.0, 3.0]]])            # (T, 1, 2)
    z = jnp.log(jnp.array([[[1.0, 0.25]], [[7.0, -3.0]]]))  # logit(lambda)
    h = [-2 * 0.5 * np.log(0.5), -(0.2 * np.log(0.2) + 0.8 * np.log(0.8))]
    want = np.mean([0.5 * 2 + 0.5 * 1 - 0.3 * h[0],
                    0.2 * 4 + 0.8 * 3 - 0.3 * h[1]])
    np.testing.assert_allclose(looped_exit_loss(ce, z, 0.3), want, rtol=1e-6)
    # The gate's gradient: beta pushes towards the even split, the cheaper
    # second pass pulls the first gate shut.
    grad = jax.grad(lambda z: looped_exit_loss(ce, z, 0.0))(z)
    assert float(grad[0, 0, 0]) > 0 and float(grad[0, 0, 1]) > 0
    assert float(jnp.abs(grad[1]).max()) == 0.0


def test_recompute_changes_no_value(both_sides, seeded):
    params, batch = seeded
    (loss, (ce, p)), grads = both_sides[0]
    (loss_r, (ce_r, p_r)), grads_r = jax.jit(jax.value_and_grad(
        functools.partial(system_terms, looped(recompute=True)),
        has_aux=True))(params, batch)
    np.testing.assert_allclose(loss_r, loss, rtol=1e-6)
    np.testing.assert_allclose(ce_r, ce, rtol=1e-6)
    np.testing.assert_allclose(p_r, p, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads_r), jax.tree.leaves(grads)):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=RTOL * float(jnp.abs(b).max()))


def test_the_program_holds_the_layers_once_and_one_passes_logits(seeded):
    """The rolled loop: the gradient's program has one `while` forward and one
    backward whatever `loops` says, each product of a layer once a loop — not
    `loops` times — and no tensor of `loops` passes' logits."""
    params, batch = seeded

    def lowered(passes):
        model = looped(loops=passes, recompute=True, use_flash=False)
        return jax.jit(jax.grad(lambda p: system_terms(model, p, batch)[0])
                       ).lower(params).as_text()

    short, long = lowered(2), lowered(5)
    for text in (short, long):
        assert "stablehlo.while" in text
    assert short.count("stablehlo.dot_general") \
        == long.count("stablehlo.dot_general")
    vocab = SIZES["vocab_size"]
    assert f"tensor<5x2x64x{vocab}x" not in long
    assert f"tensor<2x64x{vocab}xf32>" in long


def test_the_gate_is_float32_in_a_bfloat16_model(seeded):
    """The comparison on the chip cannot tell a bfloat16 gate from this one
    (benchmark/reference/ouro_lm.py, GATE_GRAD_RTOL): the types hold it."""
    params, batch = seeded
    model = looped(dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16)
    ce, z = jax.eval_shape(
        lambda p: model.apply({"params": p}, batch[0], targets=batch[1]),
        params)
    assert ce.dtype == z.dtype == jnp.float32
    made = jax.eval_shape(model.init, jax.random.PRNGKey(0), batch[0])
    gate = {k: v.dtype for k, v in made["params"].items()
            if k.startswith("exit_")}
    assert gate == {"exit_gate_kernel": jnp.float32,
                    "exit_gate_bias": jnp.float32}


def test_what_the_layers_sow_is_stacked_a_pass_and_the_recorder_reads_it(
        seeded):
    params, batch = seeded
    model = looped(layers=("window_attention", "gated_mlp") * 2, window=16)
    _, wrote = model.apply({"params": params}, batch[0], targets=batch[1],
                           mutable=["intermediates"])
    sown = wrote["intermediates"]
    assert sown["layer_0"]["mixer"]["attn_blocks_visited"][0].shape \
        == (PASSES,)
    record = record_exit_distribution(sown)
    assert len(record["mean_p"]) == PASSES
    np.testing.assert_allclose(sum(record["mean_p"]), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        record["expected_passes"],
        sum((t + 1) * p for t, p in enumerate(record["mean_p"])), rtol=1e-6)
    assert 1.0 < record["expected_passes"] < PASSES
    assert 0.0 < record["entropy"] <= np.log(PASSES)


@pytest.mark.parametrize("bad", [
    dict(layers=None), dict(loops=0), dict(block_diffusion=4),
    dict(seq_axis="sp")])
def test_loops_refuses_what_it_does_not_compose_with(seeded, bad):
    _, batch = seeded
    with pytest.raises(ValueError, match="loops="):
        looped(**bad).init(jax.random.PRNGKey(0), batch[0])


def test_loops_without_a_gate_refuses_targets(seeded):
    """The fused head-and-loss is the one-pass model's: a looped model's
    per-token values come from its gate's path, and nothing else is kept."""
    params, batch = seeded
    plain = {k: v for k, v in params.items() if not k.startswith("exit_")}
    with pytest.raises(ValueError, match="exit_gate=True"):
        TransformerLM(**SIZES, loops=PASSES).apply(
            {"params": plain}, batch[0], targets=batch[1])


def test_the_data_parallel_step_exchanges_each_gradient_once():
    """Under a 2-device `data_parallel_mesh()` the step of the looped model
    holds as many all-reduces as the one-pass model's, with two more for the
    gate's weights at most — not `loops` times as many: the gradient that
    `build_train_step` sees is one a parameter, the passes' sum."""
    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.parallel import data_parallel_mesh

    # Sizes at which attention takes the flash kernels (interpreted here):
    # the blockwise fallback's scan does not run under shard_map's checks.
    sizes = {**SIZES, "d_model": 64, "d_ff": 96}
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 129), 0,
                                SIZES["vocab_size"])
    batch = (tokens[:, :-1], tokens[:, 1:])
    mesh = data_parallel_mesh(jax.devices()[:2], axis_name="hvd")
    tx = optax.adamw(1e-3)

    def reduces(model, loss):
        params = model.init(jax.random.PRNGKey(0), batch[0])["params"]
        step = build_train_step(loss, tx, mesh, axis_name="hvd",
                                batch_spec=(P("hvd"), P("hvd")))
        text = step.lower(params, tx.init(params), batch).as_text()
        return text.count("stablehlo.all_reduce"), step, params

    looped_model = TransformerLM(**sizes, loops=PASSES, exit_gate=True,
                                 recompute=True)
    count, step, params = reduces(
        looped_model, lambda p, b: system_terms(looped_model, p, b)[0])
    one_pass = TransformerLM(**sizes)
    base, _, _ = reduces(
        one_pass,
        lambda p, b: next_token_loss(one_pass.apply({"params": p}, b[0]),
                                     b[1]))
    assert 0 < base <= count <= base + 2
    # And the step trains on the two devices: the loss falls.
    state = (params, tx.init(params))
    losses = []
    for _ in range(3):
        *state, loss = step(state[0], state[1], batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
