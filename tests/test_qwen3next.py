"""What Qwen3-Next adds to models.TransformerLM (Gated DeltaNet mixers on a
delta rule with a decay a head over grouped heads, a partial rotation in
attention, a gate on the shared expert's output) against the plain float32
reference the benchmark keeps (benchmark/reference/qwen3next_lm.py): the delta
rule one step a token with q and k repeated for their value heads, a plain
softmax over whole rows, a loop over the shard's experts.  CPU, float32,
seeded weights, small sizes.

Tolerances: both sides are float32 and differ in the order of their sums
(products over chunks and a solve against a step a token, grouped rows against
masked whole batches), so they agree to float32 rounding accumulated over a
few layers: 2e-5 of the largest value (the chunked delta rule, whose solve
multiplies 64 x 64 matrices, and what holds it, 1e-4).  bfloat16 anywhere
would read 1e-3 to 1e-2 and fail every case;
`test_reference_refuses_float8_operands` shows the next precision down is far
outside them.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.reference import qwen3next_lm as reference
from benchmark.reference.ling_lm import delta_recurrence
from horovod_tpu.models import (DeltaConfig, DeltaMixer,
                                MoEConfig, TransformerLM)
from horovod_tpu.models.transformer import Attention, SparseExperts, rope
from horovod_tpu.ops import delta_rule
from horovod_tpu.ops.delta_rule import chunked_delta_rule, lowered_plan
from tests.test_hybrid import (both_ways, close, columns, mixer_case,
                               reference_sides, seeded, sown, with_highest)

RTOL = 2e-5
VOCAB, HIDDEN, SEQ = 256, 64, 128
KEY_HEADS, VALUE_HEADS, LINEAR_DIM = 2, 4, 16
HEADS, KV_HEADS, HEAD_DIM, ROTARY = 4, 2, 32, 8
THETA = 1e7
DELTA = DeltaConfig(heads=KEY_HEADS, head_dim=LINEAR_DIM, conv=4, chunk=32,
                    value_heads=VALUE_HEADS)
EXPERTS, PER_TOKEN, WIDTH, SHARED = 16, 4, 48, 40
# One period: three Gated DeltaNet layers and a gated attention layer, each
# followed by the experts.
LAYERS = ("gated_delta", "experts") * 3 + ("attention", "experts")


def moe(shard=(0, 1), row_bound=None, experts=EXPERTS, gate=True):
    return MoEConfig(experts, PER_TOKEN, WIDTH, shard, row_bound,
                     renormalize=True, shared_width=SHARED,
                     shared_output_gate=gate)


def lm(expert_shard=(0, 1), use_flash=False, chunk=DELTA.chunk):
    return TransformerLM(
        vocab_size=VOCAB, d_model=HIDDEN, n_heads=HEADS, dtype=jnp.float32,
        use_flash=use_flash, norm_eps=1e-6, moe=moe(expert_shard),
        layers=LAYERS, delta=DELTA._replace(chunk=chunk),
        n_kv_heads=KV_HEADS, head_dim=HEAD_DIM, head_norm=True,
        attn_gate=True, rope_theta=THETA, rotary_dim=ROTARY)


def reference_config(expert_shard=(0, 1), **more):
    return dict(layers=LAYERS, head_dim=LINEAR_DIM, rope_theta=THETA,
                rotary_dim=ROTARY, norm_eps=1e-6, num_experts=EXPERTS,
                experts_per_token=PER_TOKEN, expert_shard=expert_shard, **more)


reference_side = reference_sides(reference_config, reference.loss_and_chosen)


# --- the delta rule with a decay a head over grouped heads ------------------

@functools.partial(jax.jit, static_argnames=(
    "seed", "seq", "low", "d_k", "d_v", "key_heads"))
def rule_inputs(seed, seq=SEQ, low=-20.0, d_k=16, d_v=8,
                key_heads=KEY_HEADS):
    """Unit keys, queries at d_k^-1/2, a value head's log-decay from 0 down to
    `low` a step — every seventh token AT `low` in every head, so that at -20
    a chunk's decay underflows (this gate has no bound) while its neighbours
    hold decays near one."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    heads = (2, seq, VALUE_HEADS)

    def unit(key):
        t = jax.random.normal(key, (2, seq, key_heads, d_k))
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q, k = unit(keys[0]) * d_k ** -0.5, unit(keys[1])
    v = jax.random.normal(keys[2], heads + (d_v,))
    log_alpha = low * jax.random.uniform(keys[3], heads) ** 3
    log_alpha = log_alpha.at[:, ::7].set(low)
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], heads))
    mix = jax.random.normal(keys[5], heads + (d_v,))
    return (q, k, v, log_alpha, beta), mix


def repeated(q, k, v, log_alpha, beta):
    """The operands as the published code hands them to its rule: q and k
    repeated for their value heads, and for `delta_recurrence` the decay on
    every channel."""
    per_key = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, per_key, axis=2) for t in (q, k))
    return q, k, v, jnp.broadcast_to(log_alpha[..., None], q.shape), beta


def token_by_token(*operands):
    return delta_recurrence(*repeated(*operands))


# A chunk that divides the sequence and one that does not, log-decays near 0
# and at -20 a step.
RULE_CASES = [(128, 64, -20.0), (128, 16, -1e-3), (100, 64, -20.0),
              (100, 32, -0.5), (72, 16, -1e-3)]


@pytest.mark.parametrize("seq,chunk,low", RULE_CASES)
def test_head_decay_rule_is_the_recurrence(seq, chunk, low):
    args, _ = rule_inputs(chunk, seq, low)
    got, decay_min = jax.jit(lambda *a: chunked_delta_rule(
        *a, chunk, scope="hvd_gdn_scan"))(*args)
    assert got.shape == args[2].shape
    close(got, jax.jit(token_by_token)(*args), 1e-4)
    padded = jnp.pad(args[3], ((0, 0), (0, -seq % chunk), (0, 0)))
    close(decay_min, padded.reshape(2, -1, chunk, VALUE_HEADS).sum(2).min())


@pytest.mark.parametrize("seq,chunk,low", RULE_CASES)
def test_head_decay_rule_gradients_are_the_recurrences(seq, chunk, low):
    args, mix = rule_inputs(7 + chunk, seq, low)

    def total(fn):
        return lambda *a: (fn(*a) * mix).sum()

    got = jax.jit(jax.grad(total(lambda *a: chunked_delta_rule(*a, chunk)[0]),
                           argnums=range(5)))(*args)
    want = jax.jit(jax.grad(total(token_by_token), argnums=range(5)))(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        close(g, w, 1e-4)


def rule_and_gradients(fn, args, mix):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a) * mix).sum(), argnums=range(5)))(*args)


# What the kernels of the carry meet beside RULE_CASES: a single chunk (whole,
# and one the sequence does not fill), a padded tail behind several chunks, a
# value head a key head, four value heads a key head.
KERNEL_CASES = [(64, 64, KEY_HEADS), (48, 64, KEY_HEADS), (150, 32, KEY_HEADS),
                (128, 32, VALUE_HEADS), (96, 32, 1)]


@pytest.mark.parametrize("seq,chunk,key_heads", KERNEL_CASES)
def test_carry_kernels_are_the_recurrence(seq, chunk, key_heads):
    """The head form's two kernels (the Pallas interpreter runs their bodies
    here) against the rule one step a token: `o` and the gradients of all
    five operands."""
    args, mix = rule_inputs(seq + key_heads, seq, -2.0, key_heads=key_heads)
    got = rule_and_gradients(
        lambda *a: chunked_delta_rule(*a, chunk, scope="hvd_gdn_scan")[0],
        args, mix)
    want = rule_and_gradients(token_by_token, args, mix)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        close(g, w, 1e-4)


def test_carry_kernels_under_shard_map_are_the_recurrence():
    """Head-sharded, as a tensor-parallel mixer would call it: each of two
    devices runs the kernels on its key head and that head's value heads, the
    kernels' scratch and outputs varying over the mesh axis as their operands
    do."""
    args, mix = rule_inputs(3, 96, -2.0)
    mesh = Mesh(np.array(jax.devices()[:KEY_HEADS]), ("heads",))
    by_head = P(None, None, "heads")

    def sharded(*a):
        return jax.shard_map(
            lambda *t: chunked_delta_rule(*t, 32, scope="hvd_gdn_scan")[0],
            mesh=mesh, in_specs=(by_head,) * 5, out_specs=by_head)(*a)

    got = rule_and_gradients(sharded, args, mix)
    want = rule_and_gradients(token_by_token, args, mix)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, w in zip(got[1], want[1]):
        close(g, w, 1e-4)


@pytest.mark.parametrize("chunks,per_key", [(3, 2), (1, 2), (4, 1)])
def test_head_carry_is_the_loops_carry_fed_the_same_operands(chunks, per_key):
    """`_head_carry` (the kernels: q and k a key head, the decays as vectors)
    against `_carry` (the channel form's loops: the decayed Q and K written a
    value head), `O` and the cotangents of all eight operands, to float32
    rounding."""
    batch, key_heads, chunk, d_k, d_v = 2, 2, 16, 16, 8
    heads = key_heads * per_key
    keys = jax.random.split(jax.random.PRNGKey(chunks + per_key), 9)
    of_values, of_keys = (batch, chunks, heads), (batch, chunks, key_heads)
    w = 0.3 * jax.random.normal(keys[0], of_values + (chunk, d_k))
    u0 = jax.random.normal(keys[1], of_values + (chunk, d_v))
    qk = 0.3 * jax.random.normal(keys[2], of_values + (chunk, chunk))
    q = jax.random.normal(keys[3], of_keys + (chunk, d_k))
    k = 0.3 * jax.random.normal(keys[4], of_keys + (chunk, d_k))
    from_start, end_decay = (jax.random.uniform(
        key, of_values + (chunk,), minval=0.2) for key in keys[5:7])
    carried = jax.random.uniform(keys[7], of_values + (1,), minval=0.2)
    mix = jax.random.normal(keys[8], of_values + (chunk, d_v))

    def kernels(*a):
        return (delta_rule._head_carry(*a, "hvd_gdn_scan_carry", True)
                * mix).sum()

    def loops(w, u0, qk, q, k, from_start, end_decay, carried):
        q, k = (jnp.repeat(t, per_key, axis=2) for t in (q, k))
        return (delta_rule._carry(
            w, u0, q * from_start[..., None], qk, k * end_decay[..., None],
            carried) * mix).sum()

    operands = (w, u0, qk, q, k, from_start, end_decay, carried)
    got = jax.jit(jax.value_and_grad(kernels, range(8)))(*operands)
    want = jax.jit(jax.value_and_grad(loops, range(8)))(*operands)
    np.testing.assert_allclose(got[0], want[0], rtol=RTOL)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape
        close(g, w)


def test_channel_form_lowers_to_its_two_loops_and_no_kernel():
    """Ling's cell pins its `while`s and custom calls inside `correct`: a
    four-axis `log_alpha` takes the XLA `_carry`, whatever the backend."""
    q, k, v, log_alpha, beta = repeated(*rule_inputs(0, low=-5.0)[0])
    text = jax.jit(jax.grad(lambda *a: chunked_delta_rule(
        *a, 32)[0].sum(), range(5))).lower(q, k, v, log_alpha, beta).as_text()
    assert text.count("stablehlo.while") == 2
    assert "custom_call" not in text and "hvd_gdn" not in text


@pytest.mark.parametrize("chunk", [16, 64])
def test_grouped_heads_are_repeated_heads(chunk):
    """Value head j reading key head j // 2 is the rule over q and k repeated
    for their value heads, values and gradients: the repeat's transpose sums
    the two value heads' cotangents."""
    args, mix = rule_inputs(chunk)

    def grouped(*a):
        return (chunked_delta_rule(*a, chunk)[0] * mix).sum()

    def by_repeat(q, k, v, log_alpha, beta):
        per_key = v.shape[2] // q.shape[2]
        return (chunked_delta_rule(
            jnp.repeat(q, per_key, 2), jnp.repeat(k, per_key, 2), v,
            log_alpha, beta, chunk)[0] * mix).sum()

    got = jax.jit(jax.value_and_grad(grouped, range(5)))(*args)
    want = jax.jit(jax.value_and_grad(by_repeat, range(5)))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=RTOL)
    for g, w in zip(got[1], want[1]):
        close(g, w)


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_decay_a_head_is_the_channel_form_fed_it_on_every_channel(chunk):
    """Inside the channel gate's bound the two forms must agree: the one Ling's
    cell guards, fed the head's decay on each of its channels and repeated
    heads, against the head form."""
    args, mix = rule_inputs(chunk, low=-5.0)

    def head_form(*a):
        return (chunked_delta_rule(*a, chunk)[0] * mix).sum()

    def channel_form(*a):
        return (chunked_delta_rule(*repeated(*a), chunk)[0] * mix).sum()

    got = jax.jit(jax.value_and_grad(head_form, range(5)))(*args)
    want = jax.jit(jax.value_and_grad(channel_form, range(5)))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=RTOL)
    for g, w in zip(got[1], want[1]):
        close(g, w, 1e-4)


def test_head_decay_rule_never_broadcasts_the_decay_over_channels():
    """Every exponential of the lowered head form is of a value head's sums —
    (batch, chunks, heads, chunk[, chunk]) — and none is d_k wide, where the
    channel form fed the same decay takes them a channel."""
    args, _ = rule_inputs(0)

    def exponentials(operands):
        text = jax.jit(lambda *a: chunked_delta_rule(*a, 64)[0]).lower(
            *operands).as_text()
        return set(re.findall(
            r"stablehlo\.exponential .* : tensor<([0-9x]+)xf32>", text))

    assert exponentials(args) == {"2x2x4x64", "2x2x4x64x64", "2x2x4"}
    assert any(shape.endswith("x16") for shape in exponentials(
        repeated(*args)))


@pytest.mark.parametrize("values,heads", [(6, 4), (4, 3)])
def test_head_decay_rule_refuses_heads_it_cannot_group(values, heads):
    q = jnp.zeros((1, 32, heads, 8))
    v = jnp.zeros((1, 32, values, 8))
    with pytest.raises(ValueError, match="key heads"):
        chunked_delta_rule(q, q, v, jnp.zeros((1, 32, values)),
                           jnp.zeros((1, 32, values)), 16)


@pytest.mark.parametrize("form", ["head", "channel"])
@pytest.mark.parametrize("seq,chunk,loops", [(4096, 64, 2), (100, 32, 2),
                                             (64, 64, 0), (48, 64, 0)])
def test_lowered_plan_counts_the_rules_loops(seq, chunk, loops, form):
    """The plan against the program.  A decay a channel: a `while` forward and
    one backward where the recurrence has more than one step (a single
    chunk's loop is unrolled), no kernel.  A decay a head, which is what the
    plan describes unless told the form: four kernels (the solve's two and
    the carry's two) and no loop, at any length — read from the jaxpr,
    because off a TPU the interpreter runs the kernels' grids as loops of its
    own."""
    channel = form == "channel"
    assert lowered_plan(seq, chunk, form) == {
        "while": loops if channel else 0,
        "tpu_custom_call": 0 if channel else 4}
    assert lowered_plan(seq, chunk) == lowered_plan(seq, chunk, "head")
    if seq > 128:             # the cell's length: the plan alone
        return
    args, mix = rule_inputs(0, seq)
    if channel and seq % chunk:
        return                # the channel form takes whole chunks alone
    grad = jax.grad(lambda *a: (chunked_delta_rule(*a, chunk)[0] * mix).sum(),
                    range(5))
    if channel:
        text = jax.jit(grad).lower(*repeated(*args)).compile().as_text()
        assert text.count(" while(") == loops
        return
    from tests.test_ops import _pallas_call_names

    jaxpr = jax.make_jaxpr(grad)(*args)
    assert sorted(_pallas_call_names(jaxpr.jaxpr)) == [
        "hvd_kda_scan_carry_bwd", "hvd_kda_scan_carry_fwd",
        "hvd_kda_scan_solve_bwd", "hvd_kda_scan_solve_fwd"]
    assert not re.search(r"\b(scan|while)\[", str(jaxpr))


# --- the solve's kernels against float64 ------------------------------------

def solve_case(seed, d_k, d_v, per_key, chunk, dtype, key_heads=2, chunks=2,
               tail=5):
    """A chunked rule's operands as `_head_decay_rule` hands them to the solve
    (`A`, k a KEY head, v, beta, the decay from the chunk's start), with keys a
    hundredth apart (every entry of `K K^T` near one, as in
    `tests/test_ling.py::test_unit_lower_inverse_of_keys_that_resemble_one_another`),
    `beta` up to 2 and the last chunk's last `tail` tokens the padding of a
    length that is no multiple of the chunk (log-decay 0, `beta` 0, zero k, v);
    and cotangents for `W` and `U0`."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    heads = key_heads * per_key
    real = jnp.arange(chunks * chunk).reshape(chunks, 1, chunk) \
        < chunks * chunk - tail
    k = jax.random.normal(keys[0], (1, chunks, key_heads, 1, d_k)) \
        + 0.01 * jax.random.normal(keys[1], (1, chunks, key_heads, chunk, d_k))
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)
         * real[..., None]).astype(dtype)
    v = (jax.random.normal(keys[2], (1, chunks, heads, chunk, d_v))
         * real[..., None]).astype(dtype)
    beta = 2.0 * jax.random.uniform(keys[3], (1, chunks, heads, chunk)) * real
    steps = -jax.random.uniform(keys[4], (1, chunks, heads, chunk)) * real
    within = jnp.cumsum(steps, axis=-1)
    at = jnp.arange(chunk)
    earlier = at[:, None] > at[None, :]
    decay = jnp.exp(jnp.where(
        earlier, within[..., :, None] - within[..., None, :], 0.0))
    kk = jnp.repeat(jnp.einsum("bngtc,bngsc->bngts", k, k,
                               preferred_element_type=jnp.float32,
                               precision="highest"), per_key, axis=2)
    a = jnp.where(earlier, beta[..., None] * decay * kk, 0.0)
    d_w = jax.random.normal(keys[5], v.shape[:-1] + (d_k,)).astype(dtype)
    d_u0 = jax.random.normal(keys[6], v.shape)
    return (a, k, v, beta, jnp.exp(within)), (d_w, d_u0)


def solve_in_float64(operands, cotangents):
    """`T`, `W`, `U0` and the five cotangents, from `np.linalg.inv`."""
    a, k, v, beta, start, d_w, d_u0 = (
        np.asarray(t.astype(jnp.float32), np.float64)
        for t in (*operands, *cotangents))
    per_key = v.shape[2] // k.shape[2]
    k = np.repeat(k, per_key, axis=2)
    solve = np.linalg.inv(np.eye(a.shape[-1]) + a)
    turned = solve.swapaxes(-1, -2)
    scale = (beta * start)[..., None]
    w, u0 = solve @ (scale * k), solve @ (beta[..., None] * v)
    g = d_w @ (scale * k).swapaxes(-1, -2) \
        + d_u0 @ (beta[..., None] * v).swapaxes(-1, -2)
    from_w, from_u0 = turned @ d_w, turned @ d_u0
    d_k = (scale * from_w).reshape(
        k.shape[:2] + (-1, per_key) + k.shape[3:]).sum(axis=3)
    along_k = (from_w * k).sum(axis=-1)
    return (solve, w, u0), (
        -turned @ g @ turned, d_k, beta[..., None] * from_u0,
        along_k * start + (from_u0 * v).sum(axis=-1), along_k * beta)


def check_solve_kernels(d_k, d_v, per_key, chunk, dtype, chunks=2):
    """The pair of kernels (interpreted off a TPU) against float64: `T`, `U0`
    and every cotangent to `RTOL` of the largest value; `W` and the
    cotangents that leave in the operands' dtype to its rounding where that
    is bfloat16."""
    operands, cotangents = solve_case(chunk + d_v, d_k, d_v, per_key, chunk,
                                      dtype, chunks=chunks)
    rounded = RTOL if dtype == jnp.float32 else 2.0 ** -8

    @jax.jit
    def both(operands, cotangents):
        (w, u0), kept = delta_rule._head_solve_fwd(
            *operands, "hvd_gdn_scan_solve", True)
        solve = delta_rule._by_value_head(kept[-1])
        return (solve, w, u0), delta_rule._head_solve_bwd(
            "hvd_gdn_scan_solve", True, kept, cotangents)

    (solve, w, u0), grads = both(operands, cotangents)
    wanted, wanted_grads = solve_in_float64(operands, cotangents)
    close(solve, wanted[0], RTOL)
    close(w.astype(jnp.float32), wanted[1], rounded)
    close(u0, wanted[2], RTOL)
    for got, want, rtol in zip(grads, wanted_grads,
                               (RTOL, rounded, rounded, RTOL, RTOL)):
        assert got.shape == want.shape
        close(got.astype(jnp.float32), want, rtol)
    # A strictly lower A's cotangent is read under the diagonal alone.
    assert grads[0].dtype == grads[3].dtype == jnp.float32


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [64, 16])
@pytest.mark.parametrize("d_k,d_v,per_key", [(128, 128, 2), (8, 8, 1)])
def test_solve_kernels_are_the_float64_solve(d_k, d_v, per_key, chunk, dtype):
    check_solve_kernels(d_k, d_v, per_key, chunk, dtype)


@pytest.mark.parametrize("per_key,chunks", [(2, 8), (1, 3), (1, 11)])
def test_solve_kernels_over_chunks_a_grid_step_does_not_hold(per_key, chunks):
    """A grid step holds `_SOLVES_A_STEP` value heads' chunks where the
    chunks divide: eight chunks of two value heads a key head are two steps
    of four, three chunks one step of three, eleven eleven of one."""
    check_solve_kernels(128, 128, per_key, 64, jnp.bfloat16, chunks)


@pytest.mark.parametrize("chunk", [64, 16])
def test_head_form_is_the_channel_form_on_a_decay_broadcast(chunk):
    """The two solves held to each other while both exist: the head form
    (its kernels) against the channel form's code path (`_solved`,
    `_unit_lower_inverse`, the loops) fed the same decay on every channel —
    ungrouped heads and whole chunks, which is what that form takes — output
    and the five gradients."""
    (q, k, v, log_alpha, beta), mix = rule_inputs(
        chunk, SEQ, low=-0.5, key_heads=VALUE_HEADS)
    beta = 2.0 * beta

    def total(widen):
        def loss(q, k, v, log_alpha, beta):
            decay = jnp.broadcast_to(log_alpha[..., None], q.shape) \
                if widen else log_alpha
            return (chunked_delta_rule(q, k, v, decay, beta, chunk)[0]
                    * mix).sum()
        return jax.jit(jax.value_and_grad(loss, range(5)))

    got, want = (total(widen)(q, k, v, log_alpha, beta)
                 for widen in (False, True))
    close(got[0], want[0], 1e-4)
    for g, w in zip(got[1], want[1]):
        close(g, w, 1e-4)


def test_lowered_plan_refuses_a_form_it_does_not_know():
    with pytest.raises(ValueError, match="neither"):
        lowered_plan(64, 64, "row")


# --- each mixer against the reference's -------------------------------------

@pytest.mark.parametrize("head_shard", [(0, 1), (1, 2)])
@pytest.mark.parametrize("chunk", [16, 64])
def test_gated_delta_mixer_is_the_reference(chunk, head_shard):
    mixer = DeltaMixer(*DELTA._replace(chunk=chunk), gate="head",
                       head_shard=head_shard, dtype=jnp.float32)
    u, params, mix = mixer_case(mixer, chunk + head_shard[0])
    n = head_shard[1]
    assert params["in_proj_kernel"].shape == (
        HIDDEN, (2 * KEY_HEADS + 2 * VALUE_HEADS) * LINEAR_DIM // n
        + 2 * VALUE_HEADS // n)
    assert params["conv_kernel"].shape == (
        4, (2 * KEY_HEADS + VALUE_HEADS) * LINEAR_DIM // n)
    assert params["dt_bias"].shape == params["A_log"].shape == (
        VALUE_HEADS // n,)
    both_ways(lambda p, u: mixer.apply({"params": p}, u),
              lambda p, u: reference.gated_delta(
                  u, p, head_dim=LINEAR_DIM, norm_eps=1e-6),
              u, params, mix, 1e-4)


def test_gated_delta_mixer_writes_its_chunks_decay_under_its_own_name():
    mixer = DeltaMixer(*DELTA, gate="head", dtype=jnp.float32)
    u, params, _ = mixer_case(mixer)
    wrote = sown(mixer, {"params": params}, u)
    assert set(wrote) == {"gdn_chunk_log_decay_min"}
    decay = wrote["gdn_chunk_log_decay_min"][0]
    assert decay.shape == () and float(decay) < 0


@pytest.mark.parametrize("mixer,match", [
    (DeltaMixer(*DELTA), "gate='head'"),                # value heads, channel
    (DeltaMixer(*DELTA, gate="softplus"), "is none of"),
    (DeltaMixer(3, 8, value_heads=4, gate="head"), "value heads"),
    (DeltaMixer(*DELTA, gate="head", head_shard=(0, 4)), "head_shard")])
def test_delta_mixer_refuses_what_it_cannot_build(mixer, match):
    with pytest.raises(ValueError, match=match):
        mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, SEQ, HIDDEN)))


def test_value_heads_unset_is_the_channel_gates_mixer_of_before():
    """`DeltaConfig` and `DeltaMixer` with `value_heads` unset build the
    parameters and the jaxpr the Kimi-delta mixer had."""
    old = DeltaMixer(4, 8, 4, 32, -5.0, dtype=jnp.float32)
    new = DeltaMixer(*DeltaConfig(4, 8, 4, 32), dtype=jnp.float32)
    u = jnp.zeros((1, SEQ, HIDDEN))
    shapes = jax.eval_shape(lambda: new.init(jax.random.PRNGKey(0), u))
    assert shapes["params"]["in_proj_kernel"].shape == (HIDDEN, 5 * 32 + 4)
    texts = [jax.jit(m.apply).lower(shapes, u).as_text() for m in (old, new)]
    assert texts[0] == texts[1] and "hvd_gdn" not in texts[0]


# --- the partial rotation ---------------------------------------------------

@pytest.mark.parametrize("seq_dim,shape", [(-2, (2, 3, 24, HEAD_DIM)),
                                           (1, (2, 24, 3, HEAD_DIM))])
def test_partial_rotation_turns_the_slice_alone(seq_dim, shape):
    """`rotary_dim` against a rotation of the slice alone, values and the
    written-out backward; the channels that pass come back bit for bit."""
    x, mix = (jax.random.normal(jax.random.PRNGKey(i), shape) for i in (0, 1))
    positions = jnp.arange(24) + 5

    def partial(x):
        return rope(x, positions, THETA, seq_dim, ROTARY)

    def sliced(x):
        return jnp.concatenate([rope(x[..., :ROTARY], positions, THETA,
                                     seq_dim), x[..., ROTARY:]], axis=-1)

    got, want = partial(x), sliced(x)
    close(got, want)
    np.testing.assert_array_equal(got[..., ROTARY:], x[..., ROTARY:])
    close(jax.grad(lambda x: (partial(x) * mix).sum())(x),
          jax.grad(lambda x: (sliced(x) * mix).sum())(x))


def test_rotary_dim_of_the_whole_head_is_todays_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 24, HEAD_DIM))
    positions = jnp.arange(24)
    np.testing.assert_array_equal(rope(x, positions, THETA, -2, HEAD_DIM),
                                  rope(x, positions, THETA))
    # Unset, the lowered text is the one before the option.
    texts = [jax.jit(fn).lower(x).as_text() for fn in (
        lambda x: rope(x, positions, THETA),
        lambda x: rope(x, positions, THETA, -2, None))]
    assert texts[0] == texts[1]


@pytest.mark.parametrize("rotary_dim", [3, 0, HEAD_DIM + 2])
def test_rope_refuses_a_rotation_it_cannot_place(rotary_dim):
    with pytest.raises(ValueError, match="rotary_dim"):
        rope(jnp.zeros((1, 1, 8, HEAD_DIM)), jnp.arange(8), THETA, -2,
             rotary_dim)


@pytest.mark.parametrize("use_flash", [False, True])
def test_partly_rotated_gated_attention_is_the_reference(use_flash):
    layer = Attention(HEADS, jnp.float32, use_flash=use_flash,
                      n_kv_heads=KV_HEADS, head_dim=HEAD_DIM, head_norm=True,
                      gate=True, rope_theta=THETA, rotary_dim=ROTARY)
    u, params, mix = mixer_case(layer, use_flash)
    both_ways(lambda p, u: layer.apply({"params": p}, u),
              lambda p, u: reference.gated_attention(
                  u, p, rope_theta=THETA, rotary_dim=ROTARY, norm_eps=1e-6),
              u, params, mix)


def test_partial_rotation_is_training_only():
    layer = Attention(HEADS, jnp.float32, rotary_dim=ROTARY, seq_axis="sp")
    with pytest.raises(ValueError, match="rotary_dim"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, HIDDEN)))
    model = lm()
    params, batch = seeded(model)
    with pytest.raises(ValueError, match="decode_ctx"):
        model.apply({"params": params}, batch[0], decode_ctx=object())


# --- the gated shared expert ------------------------------------------------

@pytest.mark.parametrize("shard", [(0, 1), (0, 4), (3, 4)])
def test_experts_with_a_gated_shared_expert_are_the_dense_loop(shard):
    layer = SparseExperts(moe(shard), jnp.float32)
    u, params, mix = mixer_case(layer, shard[0])
    assert params["shared_output_gate_kernel"].shape == (HIDDEN, 1)

    def plain(p, u):
        return reference.sparse_experts(
            u.reshape(-1, HIDDEN), p, num_experts=EXPERTS, expert_shard=shard,
            experts_per_token=PER_TOKEN)[0].reshape(u.shape)

    both_ways(lambda p, u: layer.apply({"params": p}, u), plain, u, params,
              mix)
    _, want = with_highest(reference.router)(
        u.reshape(-1, HIDDEN), params["router_kernel"],
        experts_per_token=PER_TOKEN)
    np.testing.assert_array_equal(
        jnp.sort(sown(layer, {"params": params}, u)["chosen_experts"][0], -1),
        jnp.sort(want, -1))


def test_shared_output_gate_unset_is_the_layer_of_before():
    """`shared_output_gate=False` is the program before the option: the same
    jaxpr as a configuration that never names it, and no parameter more."""
    unnamed = MoEConfig(EXPERTS, PER_TOKEN, WIDTH, (0, 4), None,
                        renormalize=True, shared_width=SHARED)
    u = jnp.zeros((2, SEQ, HIDDEN))
    texts = []
    for cfg in (moe((0, 4), gate=False), unnamed):
        layer = SparseExperts(cfg, jnp.float32)
        params = jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), u)["params"])
        assert "shared_output_gate_kernel" not in params
        texts.append(jax.jit(jax.grad(
            lambda p, u: layer.apply({"params": p}, u).sum())).lower(
                params, u).as_text())
    assert texts[0] == texts[1]


def test_an_output_gate_wants_a_shared_expert():
    cfg = MoEConfig(EXPERTS, PER_TOKEN, WIDTH, shared_output_gate=True)
    with pytest.raises(ValueError, match="shared_width"):
        SparseExperts(cfg, jnp.float32).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, HIDDEN)))


# --- the shares add up to the uncut layer -----------------------------------

def gated_delta_share(p, shard, n):
    keys, values = KEY_HEADS * LINEAR_DIM, VALUE_HEADS * LINEAR_DIM

    def heads(v, width=VALUE_HEADS):
        return columns(v, [width], shard, n)

    return {"in_proj_kernel": columns(
                p["in_proj_kernel"],
                [keys, keys, values, values, VALUE_HEADS, VALUE_HEADS],
                shard, n),
            "conv_kernel": columns(p["conv_kernel"], [keys, keys, values],
                                   shard, n),
            "dt_bias": heads(p["dt_bias"]), "A_log": heads(p["A_log"]),
            "norm_scale": p["norm_scale"],               # one for every head
            "out_proj_kernel": heads(p["out_proj_kernel"].T, values).T}
