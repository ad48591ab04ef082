"""JAX binding tests on a virtual 8-device CPU mesh.

The compiled-path analogue of the reference's TF op tests
(/root/reference/test/test_tensorflow.py): allreduce == sum/mean over
participants, allgather concatenates along dim 0, broadcast replicates the
root's value — here asserted over real multi-device SPMD shards instead of
MPI processes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.jax.train import build_train_step
from horovod_tpu.parallel import data_parallel_mesh, replicate, shard_batch

NDEV = len(jax.devices())


@pytest.fixture(scope="module")
def mesh():
    assert NDEV == 8, f"conftest should force 8 CPU devices, got {NDEV}"
    return data_parallel_mesh(axis_name="hvd")


def test_jit_allreduce(mesh):
    x = np.arange(NDEV * 3, dtype=np.float32).reshape(NDEV, 3)

    def f(x):
        return hvd.allreduce(x, average=False, axis_name="hvd")

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P("hvd")))(x)
    per_shard = x.reshape(NDEV, 1, 3).sum(axis=0)
    np.testing.assert_allclose(out, np.tile(per_shard, (NDEV, 1)))

    def g(x):
        return hvd.allreduce(x, average=True, axis_name="hvd")

    out = jax.jit(shard_map(g, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P("hvd")))(x)
    np.testing.assert_allclose(out, np.tile(per_shard / NDEV, (NDEV, 1)),
                               rtol=1e-6)


def test_jit_allgather(mesh):
    x = np.arange(NDEV * 2, dtype=np.int32).reshape(NDEV, 2)

    def f(x):
        return hvd.allgather(x, axis_name="hvd")

    # all_gather output is replicated in value but jax's static VMA check
    # cannot infer that, hence check_vma=False.
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P(), check_vma=False))(x)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_jit_broadcast(mesh):
    x = np.stack([np.full(4, r, dtype=np.float32) for r in range(NDEV)])

    def f(x):
        return hvd.broadcast(x, root_rank=3, axis_name="hvd")

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P("hvd")))(x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full((NDEV, 4), 3, np.float32))


def test_jit_broadcast_bool(mesh):
    x = np.zeros((NDEV, 2), dtype=bool)
    x[5] = True

    def f(x):
        return hvd.broadcast(x, root_rank=5, axis_name="hvd")

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P("hvd")))(x)
    assert out.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(out), np.ones((NDEV, 2), bool))


def test_tracer_without_axis_name_raises():
    def f(x):
        return hvd.allreduce(x)

    with pytest.raises(ValueError, match="axis_name"):
        jax.jit(f)(jnp.ones(3))


@pytest.mark.parametrize("check_vma", [True, False])
def test_distributed_optimizer_matches_global_gradient(mesh, check_vma):
    """Sharded grads + DistributedOptimizer == full-batch gradient descent,
    the correctness property behind the reference's LR-scaling recipe —
    also without the vma check (the setting interpret-mode ring kernels
    need), where autodiff inserts no psum and the step averages itself."""
    w0 = jnp.asarray(np.random.RandomState(0).randn(4).astype(np.float32))
    xs = np.random.RandomState(1).randn(NDEV * 2, 4).astype(np.float32)
    ys = np.random.RandomState(2).randn(NDEV * 2).astype(np.float32)

    def loss_fn(w, batch):
        x, y = batch
        pred = x @ w
        return jnp.mean((pred - y) ** 2)

    # Reference first: plain full-batch SGD on one device.  (The train step
    # donates its inputs, which may alias w0's buffer.)
    ref_loss, ref_grad = jax.value_and_grad(loss_fn)(w0, (xs, ys))
    w0_np = np.asarray(w0)

    tx = optax.sgd(0.1)
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd",
                            check_vma=check_vma)
    params = replicate(mesh, w0)
    opt_state = replicate(mesh, tx.init(w0))
    batch = shard_batch(mesh, (xs, ys))
    new_w, _, loss = step(params, opt_state, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new_w),
                               w0_np - 0.1 * np.asarray(ref_grad), rtol=1e-5)


def test_train_step_with_aux(mesh):
    def loss_fn(w, batch):
        x, y = batch
        pred = x @ w
        loss = jnp.mean((pred - y) ** 2)
        return loss, {"pred_mean": jnp.mean(pred)}

    xs = np.random.RandomState(1).randn(NDEV * 2, 3).astype(np.float32)
    ys = np.random.RandomState(2).randn(NDEV * 2).astype(np.float32)
    w0 = jnp.zeros(3, jnp.float32)
    tx = optax.adam(1e-2)
    step = build_train_step(loss_fn, tx, mesh, has_aux=True)
    _, _, loss, aux = step(replicate(mesh, w0),
                           replicate(mesh, tx.init(w0)),
                           shard_batch(mesh, (xs, ys)))
    np.testing.assert_allclose(float(aux["pred_mean"]), 0.0, atol=1e-6)
    assert float(loss) > 0


@pytest.mark.parametrize("has_aux", [False, True])
@pytest.mark.parametrize("check_vma", [True, False])
def test_train_step_names_its_phases(mesh, check_vma, has_aux):
    """The lowered step carries the phase scopes a device trace is sorted
    by: `hvd_loss` inside what is differentiated (so JAX's own name stack
    marks the backward pass `transpose(jvp(hvd_loss))`), `hvd_optimizer`
    with `hvd_grad_exchange` beneath it, `hvd_loss_report`."""
    def loss_fn(w, batch):
        x, y = batch
        loss = jnp.mean((x @ w - y) ** 2)
        return (loss, {"pred_mean": jnp.mean(x @ w)}) if has_aux else loss

    xs = np.ones((NDEV * 2, 3), np.float32)
    ys = np.ones(NDEV * 2, np.float32)
    w0 = jnp.zeros(3, jnp.float32)
    tx = optax.adam(1e-2)
    step = build_train_step(loss_fn, tx, mesh, has_aux=has_aux,
                            check_vma=check_vma)
    text = step.lower(replicate(mesh, w0), replicate(mesh, tx.init(w0)),
                      shard_batch(mesh, (xs, ys))).as_text(debug_info=True)
    for scope in ("jvp(hvd_loss)", "transpose(jvp(hvd_loss))",
                  "hvd_optimizer", "hvd_optimizer/hvd_grad_exchange",
                  "hvd_loss_report"):
        assert scope in text, scope


def test_profiler_session_shows_the_librarys_spans(mesh, tmp_path):
    """A jax.profiler session around a plain loop holds the library's own
    step span, numbered by the proxy, and an application's hvd.trace_span,
    on one plane: the clock the device's operations are on."""
    import glob

    import horovod_tpu.common as common

    def loss_fn(w, batch):
        x, y = batch
        return jnp.mean((x @ w - y) ** 2)

    xs = np.ones((NDEV * 2, 3), np.float32)
    ys = np.ones(NDEV * 2, np.float32)
    w0 = jnp.zeros(3, jnp.float32)
    tx = optax.sgd(0.1)
    step = build_train_step(loss_fn, tx, mesh)
    params, opt_state = replicate(mesh, w0), replicate(mesh, tx.init(w0))
    batch = shard_batch(mesh, (xs, ys))
    params, opt_state, loss = step(params, opt_state, batch)   # compiles
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with common.trace_span("data_loading"):
            batch = shard_batch(mesh, (xs, ys))
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in profile.planes:
        for line in plane.lines:
            for event in line.events:
                if event.name in ("hvd.train_step", "data_loading"):
                    found.setdefault(plane.name, []).append(
                        (event.start_ns, event.name, dict(event.stats)))
    assert len(found) == 1, sorted(found)
    events = sorted(next(iter(found.values())))
    assert [name for _, name, _ in events] == (["data_loading"]
                                               + ["hvd.train_step"] * 3)
    assert [stats["step_num"] for _, _, stats in events[1:]] == [1, 2, 3]


def test_eager_collectives_size1(single_process_hvd):
    x = jnp.asarray(np.random.randn(3, 2).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(hvd.allreduce(x, average=False, name="jx0")), np.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(hvd.allgather(x, name="jx1")), np.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(hvd.broadcast(x, 0, name="jx2")), np.asarray(x))


def test_broadcast_parameters_size1(single_process_hvd):
    params = {"dense": {"w": jnp.ones((2, 2)), "b": np.zeros(2)},
              "step": 3, "lr": 0.5}
    out = hvd.broadcast_parameters(params, root_rank=0)
    assert isinstance(out["step"], int) and out["step"] == 3
    assert isinstance(out["lr"], float) and out["lr"] == 0.5
    assert isinstance(out["dense"]["b"], np.ndarray)
    np.testing.assert_array_equal(np.asarray(out["dense"]["w"]),
                                  np.ones((2, 2)))


def test_distributed_optimizer_eager_size1(single_process_hvd):
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    params = {"w": jnp.ones(3)}
    state = tx.init(params)
    grads = {"w": jnp.full(3, 0.25)}
    updates, _ = tx.update(grads, state, params)
    np.testing.assert_allclose(np.asarray(updates["w"]), -np.full(3, 0.25))
