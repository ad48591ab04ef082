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


def _linear_problem(n_devices):
    def loss_fn(w, batch):
        x, y = batch
        return jnp.mean((x @ w - y) ** 2)

    sub = data_parallel_mesh(jax.devices()[:n_devices], axis_name="hvd")
    xs = np.random.RandomState(1).randn(n_devices * 2, 3).astype(np.float32)
    ys = np.random.RandomState(2).randn(n_devices * 2).astype(np.float32)
    w0 = jnp.zeros(3, jnp.float32)
    tx = optax.sgd(0.1)
    return loss_fn, tx, sub, (replicate(sub, w0), replicate(sub, tx.init(w0)),
                              shard_batch(sub, (xs, ys)))


@pytest.mark.parametrize("n_devices", [1, 4])
def test_step_takes_no_compiler_option_off_a_tpu_host(monkeypatch, n_devices):
    """On CPU meshes of one device and of four `build_train_step` hands
    `jax.jit` no compiler option (the CPU's compiler refuses the TPU's) and
    the step lowers to the text of a plain `jax.jit` of the same
    `shard_map`; its record of the exchange reads "not applied", 0 / 0,
    before and after a call."""
    jit, seen = jax.jit, []

    def spy(fn, **kwargs):
        seen.append((fn, kwargs))
        return jit(fn, **kwargs)

    loss_fn, tx, sub, args = _linear_problem(n_devices)
    monkeypatch.setattr(jax, "jit", spy)
    step = build_train_step(loss_fn, tx, sub)
    monkeypatch.undo()
    (mapped, kwargs), = seen
    assert kwargs == {"donate_argnums": (0, 1)}
    assert step.lower(*args).as_text() == jit(
        mapped, donate_argnums=(0, 1)).lower(*args).as_text()
    untouched = {"compiler_options": "not applied", "compiled": False,
                 "async_all_reduces": 0, "sync_all_reduces": 0,
                 "async_bytes": 0, "sync_bytes": 0}
    assert step.exchange_overlap == untouched
    step(*args)
    assert step.exchange_overlap == untouched


# A compiled program in the forms libtpu and XLA write, hand-made: a
# libtpu pair around one carrier (a weight's gradient), a synchronous tuple
# (a forward statistic and its cotangent's sum, by their scope), another
# collective's pair without a done, XLA's plain pair (the loss), and a
# fusion after the done that reads its result.
_OP = 'metadata={op_name="jit(shard_step)/shard_map/'
_HAND_MADE_PROGRAM = """HloModule jit_shard_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[1024,4096]) -> (bf16[1024,4096], u32[]) {
  %param_0.1 = bf16[1024,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.54 = bf16[1024,4096]{1,0} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.2, OP_transpose(jvp(hvd_loss))/hvd_mlp/down/psum_invariant"}
  ROOT %custom-call.9 = (bf16[1024,4096]{1,0}, u32[]) custom-call(%all-reduce.54), custom_call_target="x"
}

%fused_computation.2 (param_0.2: bf16[1024,4096]) -> bf16[1024,4096] {
  %param_0.2 = bf16[1024,4096]{1,0} parameter(0)
  %all-reduce.56 = bf16[1024,4096]{1,0} all-reduce(%param_0.2), channel_id=1, to_apply=%region_1.2
  ROOT %custom-call.11 = bf16[1024,4096]{1,0} custom-call(%all-reduce.56), custom_call_target="x"
}

%fused_computation.3 (param_0.3: bf16[8,128]) -> (bf16[8,128], u32[]) {
  %param_0.3 = bf16[8,128]{1,0} parameter(0)
  %collective-permute.1 = bf16[8,128]{1,0} collective-permute(%param_0.3), source_target_pairs={{0,1},{1,0}}
  ROOT %custom-call.13 = (bf16[8,128]{1,0}, u32[]) custom-call(%collective-permute.1), custom_call_target="x"
}

%fused_computation.4 (param_0.4: bf16[1024,4096], param_1.4: u32[], param_2.4: f32[1024]) -> (bf16[1024,4096], u32[], f32[1024]) {
  %param_0.4 = bf16[1024,4096]{1,0} parameter(0)
  %param_1.4 = u32[] parameter(1)
  %param_2.4 = f32[1024]{0} parameter(2)
  %multiply.4 = f32[1024]{0} multiply(%param_2.4, %param_2.4), OP_hvd_optimizer/mul"}
  %all-reduce.55 = bf16[1024,4096]{1,0} all-reduce(%param_0.4), channel_id=1, to_apply=%region_1.2, OP_transpose(jvp(hvd_loss))/hvd_mlp/down/psum_invariant"}
  ROOT %tuple.4 = (bf16[1024,4096]{1,0}, u32[], f32[1024]{0}) tuple(%all-reduce.55, %param_1.4, %multiply.4)
}

%fused_computation.5 (param_0.5: bf16[1024,4096]) -> bf16[1024,4096] {
  %param_0.5 = bf16[1024,4096]{1,0} parameter(0)
  ROOT %negate.5 = bf16[1024,4096]{1,0} negate(%param_0.5)
}

ENTRY %main.7 (p0: bf16[1024,4096], p1: f32[1024], p2: f32[]) -> bf16[1024,4096] {
  %p0 = bf16[1024,4096]{1,0} parameter(0)
  %p1 = f32[1024]{0:T(1024)} parameter(1)
  %p2 = f32[]{} parameter(2)
  %async-collective-start = (bf16[1024,4096]{1,0}, u32[]) fusion(%p0), kind=kCustom, calls=%fused_computation.1
  %get-tuple-element.1 = bf16[1024,4096]{1,0} get-tuple-element(%async-collective-start), index=0
  %get-tuple-element.2 = u32[] get-tuple-element(%async-collective-start), index=1
  %fusion.5 = (bf16[1024,4096]{1,0}, u32[], f32[1024]{0}) fusion(%get-tuple-element.1, %get-tuple-element.2, %p1), kind=kLoop, calls=%fused_computation.4
  %get-tuple-element.3 = bf16[1024,4096]{1,0} get-tuple-element(%fusion.5), index=0
  %get-tuple-element.4 = u32[] get-tuple-element(%fusion.5), index=1
  %all-reduce.2 = (f32[1024]{0}, f32[]) all-reduce(%p1, %p2), channel_id=2, replica_groups=[1,4]<=[4], to_apply=%region_0.1, OP_jvp(hvd_loss)/norm/psum_invariant"}
  %all-reduce.4 = f32[1024]{0} all-reduce(%p1), channel_id=4, replica_groups={{0,1,2,3}}, to_apply=%region_0.1, OP_transpose(jvp(hvd_loss))/norm/psum_invariant"}
  %async-collective-start.1 = (bf16[8,128]{1,0}, u32[]) fusion(%p0), kind=kCustom, calls=%fused_computation.3
  %all-reduce-start.3 = f32[] all-reduce-start(%p2), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%region_0.1, OP_hvd_loss_report/psum_invariant"}
  %all-reduce-done.3 = f32[] all-reduce-done(%all-reduce-start.3)
  %async-collective-done = bf16[1024,4096]{1,0} fusion(%get-tuple-element.3, %get-tuple-element.4), kind=kCustom, calls=%fused_computation.2, OP_transpose(jvp(hvd_loss))/hvd_mlp/down/psum_invariant"}
  ROOT %fusion.6 = bf16[1024,4096]{1,0} fusion(%async-collective-done), kind=kLoop, calls=%fused_computation.5
}
""".replace("OP_", _OP)


def test_count_all_reduces_tells_fused_pairs_from_waiting_instructions():
    """`count_all_reduces` on the forms libtpu writes: an all-reduce inside
    the computation of an `async-collective-start` fusion is asynchronous
    (once: the fusions that carry it and the done repeat its text), an
    `all-reduce` instruction outside any fusion is one the core waits in,
    a tuple all-reduce is one, and another collective's start is neither."""
    from horovod_tpu.jax.train import count_all_reduces

    assert count_all_reduces(_HAND_MADE_PROGRAM) == (2, 2)
    assert count_all_reduces("ENTRY %main.1 () -> f32[] {\n}\n") == (0, 0)


def test_compiled_collectives_is_the_table_of_a_programs_text():
    """`compiled_collectives` on the same text: an entry a collective in the
    program's order; a pair's start, done and the carrier between them (the
    fusion that takes the start's state and holds a slice of the all-reduce;
    without an op_name, and its computation's root a bare tuple, named by
    the last instruction of that computation that is no collective), never
    the fusion behind the done; the operands' bytes, a tuple's summed; the done's op_name; the
    groups as written; and the role from the op_names alone — a backward sum
    under a scope that exchanges in the forward pass is the model's."""
    from horovod_tpu.jax.train import compiled_collectives

    table = compiled_collectives(_HAND_MADE_PROGRAM)
    assert [(e["op"], e["asynchronous"], e["start"] or e["instruction"],
             e["done"], e["bytes"], e["dtype"], e["role"]) for e in table] == [
        ("all-reduce", True, "async-collective-start",
         "async-collective-done", 2 * 1024 * 4096, "bf16", "gradient"),
        ("all-reduce", False, "all-reduce.2", None, 4 * 1024 + 4, "f32",
         "model"),
        ("all-reduce", False, "all-reduce.4", None, 4 * 1024, "f32", "model"),
        ("collective-permute", True, "async-collective-start.1", None,
         2 * 8 * 128, "bf16", "model"),
        ("all-reduce", True, "all-reduce-start.3", "all-reduce-done.3", 4,
         "f32", "report")]
    pair = table[0]
    assert pair["instruction"] is None
    assert pair["carriers"] == ["fusion.5"]
    assert pair["carrier_op_names"] == [
        "jit(shard_step)/shard_map/hvd_optimizer/mul"]
    assert pair["op_name"].endswith("hvd_mlp/down/psum_invariant")
    assert pair["replica_groups"] == "{{0,1,2,3}}"
    assert table[1]["replica_groups"] == "[1,4]<=[4]"
    assert table[3]["replica_groups"] == "{{0,1},{1,0}}"
    assert all(not e["carriers"] for e in table[1:])
    assert compiled_collectives("ENTRY %main.1 () -> f32[] {\n}\n") == []
    # Without the forward statistic, the same backward sum is a gradient's.
    alone = _HAND_MADE_PROGRAM.replace("jvp(hvd_loss)/norm", "jvp(hvd_loss)/n", 1)
    assert [e["role"] for e in compiled_collectives(alone)][1:3] == [
        "model", "gradient"]


@pytest.mark.parametrize("registry_on", [False, True])
def test_overlap_step_reads_its_text_on_demand(monkeypatch, registry_on):
    """Call 0 of a step that took the overlap options reads its
    executable's text not at all with the registry off — the record says
    `compiled` and nothing more — and once with it on (the operator asked);
    reading `step.exchange_overlap` afterwards fills the counts and bytes
    once, from the table `step.collectives()` keeps."""
    from horovod_tpu.common import metrics
    from horovod_tpu.jax.train import _TimedStep

    reads = []
    as_text = jax.stages.Compiled.as_text

    def counted(self, *args, **kwargs):
        reads.append(self)
        return as_text(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Compiled, "as_text", counted)
    loss_fn, tx, sub, args = _linear_problem(4)
    plain = build_train_step(loss_fn, tx, sub, donate=False)
    step = _TimedStep(plain._fn, overlap=True, devices=4)
    metrics.registry.reset()
    if registry_on:
        metrics.registry.enable()
    try:
        step(*args)
        mirrored = metrics.registry.snapshot()["train_step"]
    finally:
        metrics.registry.disable()
        metrics.registry.reset()
    assert len(reads) == int(registry_on)
    assert step._exchange["compiled"]
    if registry_on:
        assert mirrored["sync_all_reduces"] >= 1 and mirrored["sync_bytes"] > 0
    else:
        assert step._exchange["sync_all_reduces"] == 0
    step(*args)
    record = step.exchange_overlap
    assert len(reads) == 1
    table = step.collectives()
    assert table and all(e["op"] == "all-reduce" and not e["asynchronous"]
                         for e in table)
    assert record == {"compiler_options": "applied", "compiled": True,
                      "async_all_reduces": 0, "sync_all_reduces": len(table),
                      "async_bytes": 0,
                      "sync_bytes": sum(e["bytes"] for e in table)}
    # 3 weights and the loss, float32, however the CPU's compiler groups them.
    assert record["sync_bytes"] == 16
    assert step.exchange_overlap is record and step.collectives() is table
    assert len(reads) == 1


def test_a_step_without_an_executable_asks_jax_for_its_table():
    """The jit's own call holds no executable: over one device the step
    answers `[]` without lowering anything, over a CPU mesh of two it takes
    the call's arguments (and says so when given none), and a `pmean` the
    loss makes in its own right is the `model`'s, the weights' sum a
    `gradient`."""
    from jax import lax

    def loss_fn(w, batch):
        x, y = batch
        with jax.named_scope("norm"):
            centred = x - lax.pmean(x.mean(0), "hvd")
        return jnp.mean((centred @ w - y) ** 2)

    _, tx, sub, args = _linear_problem(2)
    step = build_train_step(loss_fn, tx, sub, donate=False)
    with pytest.raises(ValueError, match="pass the call's arguments"):
        step.collectives()
    table = step.collectives(*args)
    assert table and all(not e["asynchronous"] for e in table)
    assert {e["role"] for e in table} == {"model", "gradient"}
    model, = [e for e in table if e["role"] == "model"]
    assert model["op_name"].endswith("jvp(hvd_loss)/norm/psum_invariant")
    assert model["bytes"] == 3 * 4 and model["replica_groups"] == "{{0,1}}"
    step(*args)
    assert step.exchange_overlap["sync_all_reduces"] == 0   # "not applied"

    _, tx, one, args = _linear_problem(1)
    step = build_train_step(loss_fn, tx, one)
    step.lower = None          # nothing is lowered for the answer
    assert step.collectives() == [] == step.collectives(*args)


def test_overlap_step_reads_its_own_first_compile(mesh):
    """A step that took the overlap options compiles at its first call
    through ``lower().compile()``, counts the all-reduces of that text
    (here the CPU's: every one synchronous), mirrors the record into the
    registry when it is on, runs that executable from then on, and hands a
    call with other shapes to the jit."""
    from horovod_tpu.common import metrics
    from horovod_tpu.jax.train import _TimedStep

    loss_fn, tx, sub, (params, opt_state, batch) = _linear_problem(4)
    plain = build_train_step(loss_fn, tx, sub, donate=False)
    step = _TimedStep(plain._fn, overlap=True)
    assert step.exchange_overlap["compiler_options"] == "applied"
    assert not step.exchange_overlap["compiled"]
    metrics.registry.reset()
    metrics.registry.enable()
    try:
        first = step(params, opt_state, batch)
        mirrored = metrics.registry.snapshot()["train_step"]
    finally:
        metrics.registry.disable()
        metrics.registry.reset()
    assert step.exchange_overlap["compiled"]
    assert step.exchange_overlap["async_all_reduces"] == 0
    assert step.exchange_overlap["sync_all_reduces"] >= 1
    assert mirrored == dict(step.exchange_overlap, setup=step.setup)
    # Call 0 lowered and compiled the step itself: it holds the executable.
    assert step.setup["programs"] == {"traced": 1, "lowered": 1, "loaded": 1}
    assert step.setup["code_bytes"] is not None
    want = plain(params, opt_state, batch)
    for got in (first, step(params, opt_state, batch)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # A batch of another length: the first executable refuses it and the
    # jit compiles for it.
    xs, ys = batch
    longer = shard_batch(sub, (np.tile(np.asarray(xs), (2, 1)),
                               np.tile(np.asarray(ys), 2)))
    again = step(params, opt_state, longer)
    np.testing.assert_allclose(np.asarray(again[0]), np.asarray(want[0]),
                               rtol=1e-5)
