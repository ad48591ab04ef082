"""The chunked scan as a pair of Pallas kernels (`horovod_tpu/ops/ssm.py`
`_scan`: `hvd_ssm_scan_intra_fwd`, `_bwd`, which hold a chunk's masked decay
matrix and `mixed`, and the states between chunks, on the chip) against the
same call on XLA's products, and against the token-by-token recurrence the
benchmark keeps (benchmark/reference/granite_lm.py); and the rule that says
which shapes take which form (`lowered_plan`).  CPU: the kernels run in the
Pallas interpreter, at sizes it walks in seconds.

Tolerances: in float32 the two forms differ by the order of a chunk's sums
(1e-5 of the largest value); in bfloat16 y is the same product of the same
rounded operands (1e-6: a float32 sum's order), and a cotangent differs by where the backward rounds (the
kernels keep `dy (dt x)^T` float32 where autodiff rounds it to bfloat16): 2 %
of the largest value, a few bfloat16 steps."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_lm as reference
from horovod_tpu.ops import ssm
from tests.test_hybrid import with_highest

STATE = 16


def scan_inputs(seed, heads, head_dim, groups, seq, dtype=jnp.float32,
                batch=2, decay=1.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (batch, seq, heads, head_dim))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)) - 2.0)
    A = -decay * jnp.exp(jax.random.normal(keys[2], (heads,)))
    B = jax.random.normal(keys[3], (batch, seq, groups, STATE))
    C = jax.random.normal(keys[4], (batch, seq, groups, STATE))
    D = jax.random.normal(keys[5], (heads,))
    return ((x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype), D),
            jax.random.normal(keys[6], x.shape))


def through(monkeypatch, form, args, mix, chunk, heads_a_step):
    """(y, the cotangents of x, dt, A, B, C, D) of `chunked_scan` with its
    within-chunk stage on ``form``, whatever the rule says of the shape."""
    monkeypatch.setattr(ssm, "lowered_plan", lambda *shape: {
        "scan": form, "heads_a_step": heads_a_step})

    def scan(*a):
        return ssm.chunked_scan(*a, chunk)[0]

    y = jax.jit(scan)(*args)
    grads = jax.jit(jax.grad(lambda *a: (scan(*a) * mix).sum(),
                             argnums=range(6)))(*args)
    return y, grads


def apart(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert np.isfinite(got).all() and np.isfinite(want).all()
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# (heads a group, head_dim, heads a grid step): one wide head, four in one
# step, and many heads on a group cut small (sixteen of 8, two grid steps).
HEADS = {"one_head": (1, 128, 1), "four_heads": (4, 32, 4),
         "many_heads": (16, 8, 8)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("heads", list(HEADS))
def test_the_kernels_are_the_products(monkeypatch, heads, groups, chunk,
                                      dtype):
    """y and the cotangents of x, dt, A, B, C and D through `chunked_scan`:
    the pair of kernels against XLA's products on the same operands, over two
    chunks (so that the carry crosses one)."""
    per_group, head_dim, heads_a_step = HEADS[heads]
    args, mix = scan_inputs(chunk + groups, per_group * groups, head_dim,
                            groups, 2 * chunk, dtype, batch=1)
    got = through(monkeypatch, "kernels", args, mix, chunk, heads_a_step)
    want = through(monkeypatch, "products", args, mix, chunk, heads_a_step)
    narrow = dtype == jnp.bfloat16
    assert apart(got[0], want[0]) < (1e-6 if narrow else 1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert apart(g, w) < (2e-2 if narrow else 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_are_the_products_at_granites_chunk(monkeypatch, dtype):
    """A chunk of 256 tokens, two registers' lanes, as the Granite cell's:
    four heads of 16 on two groups over two chunks, two heads a grid step."""
    args, mix = scan_inputs(5, 4, 16, 2, 512, dtype, batch=1)
    got = through(monkeypatch, "kernels", args, mix, 256, 2)
    want = through(monkeypatch, "products", args, mix, 256, 2)
    narrow = dtype == jnp.bfloat16
    assert apart(got[0], want[0]) < (1e-6 if narrow else 1e-5)
    for g, w in zip(got[1], want[1]):
        assert apart(g, w) < (2e-2 if narrow else 1e-5)


@pytest.mark.parametrize("groups,per_group,head_dim,heads_a_step", [
    (1, 16, 8, 16), (2, 4, 32, 2)])
def test_the_kernels_are_the_token_by_token_recurrence(
        monkeypatch, groups, per_group, head_dim, heads_a_step):
    """Values and every gradient against `reference.recurrence`, float32, as
    tests/test_granite.py holds the products to it."""
    chunk = 32
    args, mix = scan_inputs(7, groups * per_group, head_dim, groups,
                            3 * chunk)
    y, grads = through(monkeypatch, "kernels", args, mix, chunk, heads_a_step)
    assert apart(y, with_highest(reference.recurrence)(*args)) < 1e-4
    want = with_highest(jax.grad(
        lambda *a: (reference.recurrence(*a) * mix).sum(),
        argnums=range(6)))(*args)
    for g, w in zip(grads, want):
        assert apart(g, w) < 1e-4


@pytest.mark.parametrize("form", ["kernels", "products"])
def test_decays_that_underflow_across_a_chunk_leave_no_nan(monkeypatch, form):
    """Log decays of about -40 a token: `exp` of a chunk's cumulative sum
    underflows within a few tokens, and above the diagonal the difference
    `w[i] - w[j]` passes +88, where `exp` is infinite — masked BEFORE the
    exponential, `L` is 0 there, and y and every cotangent are finite and the
    recurrence's."""
    chunk = 32
    args, mix = scan_inputs(3, 4, 32, 1, 2 * chunk, decay=300.0)
    x, dt, A = args[:3]
    steps = (dt * A).reshape(2, 2, chunk, -1)
    assert float(jnp.cumsum(steps, axis=2).min()) < -1000.0
    assert float((-steps.sum(2)).max()) > 88.0 * 8
    y, grads = through(monkeypatch, form, args, mix, chunk, 4)
    assert apart(y, with_highest(reference.recurrence)(*args)) < 1e-4
    want = with_highest(jax.grad(
        lambda *a: (reference.recurrence(*a) * mix).sum(),
        argnums=range(6)))(*args)
    for g, w in zip(grads, want):
        assert apart(g, w) < 1e-4


def test_the_backward_keeps_the_operands_and_the_entering_states():
    """The residuals of `_scan` are its seven operands and the state that
    ENTERED each chunk (heads x head_dim x state floats a chunk): nothing of
    `heads x chunk` elements a token (`L`, `mixed`) or of `chunk` a token
    (`scores`) crosses from the forward to the backward."""
    chunk, heads, head_dim, seq = 32, 4, 32, 64
    args, _ = scan_inputs(0, heads, head_dim, 1, seq, batch=1)
    x, dt, A, B, C, D = args
    rows = jnp.zeros((1, seq // chunk, 1, heads, chunk))
    operands = (x.reshape(1, seq, -1).swapaxes(1, 2), rows + 0.1, rows,
                jnp.zeros((1, seq, heads)), B.reshape(1, 1, seq, -1),
                C.reshape(1, 1, seq, -1), jnp.ones((1, heads, chunk)))
    y, kept = ssm._scan_fwd(*operands, True)
    assert y.shape == operands[0].shape and y.dtype == jnp.float32
    assert all(k is o for k, o in zip(kept, operands))
    (entered,) = kept[len(operands):]
    assert entered.shape == (1, seq // chunk, heads * head_dim, STATE)
    assert entered.dtype == jnp.float32
    assert float(jnp.abs(entered[:, 0]).max()) == 0.0    # nothing came before
    assert float(jnp.abs(entered[:, 1]).max()) > 0.0


# --- which shapes take which form -------------------------------------------

GRANITE = dict(heads=64, groups=1, head_dim=64, state=128, chunk=256)
NEMOTRON_SHARE = dict(heads=16, groups=1, head_dim=64, state=128, chunk=128)


def test_the_plan_is_kernels_at_granites_shape_and_products_at_nemotrons():
    assert ssm.lowered_plan(**GRANITE, dtype=jnp.bfloat16) == {
        "scan": "kernels", "heads_a_step": 16, "tpu_custom_call": 2}
    products = {"scan": "products", "tpu_custom_call": 0}
    assert ssm.lowered_plan(**NEMOTRON_SHARE, dtype=jnp.bfloat16) == products
    # The whole layer as the share: the rule weighs a token's `L` against a
    # token's operands, and a share of 1 / 8 divides both.
    whole = dict(NEMOTRON_SHARE, heads=128, groups=8)
    assert ssm.lowered_plan(**whole, dtype=jnp.bfloat16) == products
    # Granite's heads at Nemotron's chunk: half the `L`, the same operands.
    assert ssm.lowered_plan(**dict(GRANITE, chunk=128), dtype=jnp.bfloat16) \
        == products


def test_the_plan_reads_shapes_and_the_dtype_alone():
    """No model's name, flag or environment variable: six arguments, and the
    same answer whatever the backend."""
    assert list(inspect.signature(ssm.lowered_plan).parameters) == [
        "heads", "groups", "head_dim", "state", "chunk", "dtype"]
    source = inspect.getsource(ssm.lowered_plan)
    assert "environ" not in source and "default_backend" not in source


@pytest.mark.parametrize("change,why", [
    (dict(chunk=192), "a chunk that is no multiple of 128 lanes"),
    (dict(head_dim=24), "heads of 24 bfloat16 channels: a register holds 16 "
                        "sublanes of them")])
def test_the_plan_leaves_what_mosaic_cannot_tile_on_the_products(change, why):
    shape = dict(dict(GRANITE, chunk=512), **change)
    assert ssm.lowered_plan(**shape, dtype=jnp.bfloat16)["scan"] \
        == "products", why
    assert ssm.lowered_plan(**dict(GRANITE, chunk=512),
                            dtype=jnp.bfloat16)["scan"] == "kernels"


@pytest.mark.parametrize("per_group,head_dim,want", [
    (64, 64, 16), (8, 64, 8), (6, 64, 6), (24, 128, 12), (32, 16, 16),
    (3, 128, 3)])
def test_a_grid_step_walks_heads_that_divide_the_groups(per_group, head_dim,
                                                        want):
    """Sixteen heads a grid step, or the most under it that divide a group's
    (a block of heads lies within ONE group: it shares the group's
    `scores`)."""
    plan = ssm.lowered_plan(per_group, 1, head_dim, 128, 1024, jnp.bfloat16)
    assert plan["scan"] == "kernels" and plan["heads_a_step"] == want
    assert per_group % want == 0 and want <= 16
