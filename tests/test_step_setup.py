"""`step.setup`: what building a compiled step's programs cost, as the step
itself accounts for it (horovod_tpu/jax/train.py `_TimedStep`, `_Staged`;
horovod_tpu/common/metrics.py `SetupTable`, `kernel_trace`).  CPU: the
seconds are this sandbox's and nobody's measurement; the counts are exact."""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu import common
from horovod_tpu.common import metrics
from horovod_tpu.jax.train import build_train_step
from horovod_tpu.models.transformer import (MoEConfig, TransformerLM,
                                            next_token_loss)

ONE_OF_EACH = {"traced": 1, "lowered": 1, "loaded": 1}


def problem(donate=False):
    """A linear model's step on one CPU device, and its arguments."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    tx = optax.sgd(0.1)
    params = {"w": jnp.ones((8, 8))}

    def loss_fn(params, batch):
        return ((batch @ params["w"]) ** 2).mean()

    step = build_train_step(loss_fn, tx, mesh, donate=donate)
    return step, (params, tx.init(params), jnp.ones((4, 8)))


def test_the_staged_route_is_one_program_and_the_calls_build_nothing():
    step, args = problem()
    assert step.setup == metrics.new_step_setup()
    compiled = step.lower(*args).compile()
    staged = metrics.copy_step_setup(step.setup)
    assert staged["programs"] == ONE_OF_EACH
    assert min(staged["trace_s"], staged["lower_s"], staged["load_s"]) > 0
    assert staged["code_bytes"] \
        == compiled.memory_analysis().generated_code_size_in_bytes
    assert staged["first_call_s"] is None
    for _ in range(3):
        step(*args)
    assert step.setup["programs"] == ONE_OF_EACH
    assert step.setup["recompiles"] == 0
    assert step.setup["last_compile_call"] is None
    assert step.setup["first_call_s"] > 0
    assert step.setup["lower_s"] == staged["lower_s"]
    assert step.setup["load_s"] == staged["load_s"]


def test_the_jits_own_first_call_is_one_program_with_no_executable_held():
    step, args = problem()
    step(*args)
    step(*args)
    setup = step.setup
    assert setup["programs"] == ONE_OF_EACH
    assert min(setup["trace_s"], setup["lower_s"], setup["load_s"]) > 0
    assert setup["code_bytes"] is None
    assert setup["first_call_s"] >= setup["load_s"]
    assert setup["recompiles"] == 0
    # The cache is off under test: no verdict, and no retrieval.
    assert (setup["cache_hits"], setup["cache_misses"],
            setup["cache_retrieval_s"]) == (0, 0, 0.0)


def test_the_first_call_drives_its_stages_in_their_spans(monkeypatch):
    spans = []

    @contextlib.contextmanager
    def recorded(name, label=None):
        before = dict(step.setup["programs"])
        yield
        spans.append((name, {k: v - before[k]
                             for k, v in step.setup["programs"].items()}))

    step, args = problem()
    monkeypatch.setattr(common, "trace_span", recorded)
    step(*args)
    step(*args)
    assert spans == [
        ("hvd.step_trace", {"traced": 1, "lowered": 0, "loaded": 0}),
        ("hvd.step_lower", {"traced": 0, "lowered": 1, "loaded": 0}),
        ("hvd.step_load", {"traced": 0, "lowered": 0, "loaded": 1})]


def test_another_batch_shape_is_a_recompile_of_that_call():
    step, (params, opt_state, batch) = problem()
    step(params, opt_state, batch)
    step(params, opt_state, batch)
    assert step.setup["recompiles"] == 0
    step(params, opt_state, jnp.ones((2, 8)))         # step_num 2
    assert step.setup["recompiles"] == 1
    assert step.setup["last_compile_call"] == 2
    assert step.setup["programs"] == {"traced": 2, "lowered": 2, "loaded": 2}
    step(params, opt_state, jnp.ones((2, 8)))
    assert step.setup["recompiles"] == 1


def test_a_stage_the_caller_drives_after_a_call_is_no_recompile():
    step, (params, opt_state, batch) = problem()
    step(params, opt_state, batch)
    step(params, opt_state, batch)
    step.lower(params, opt_state, jnp.ones((2, 8))).compile()
    assert step.setup["programs"]["loaded"] == 2
    assert step.setup["recompiles"] == 0


def test_two_steps_keep_two_accounts():
    (one, args), (other, _) = problem(), problem()
    one(*args)
    assert one.setup["programs"] == ONE_OF_EACH
    assert other.setup == metrics.new_step_setup()
    other.lower(*args).compile()
    assert other.setup["programs"] == ONE_OF_EACH
    assert one.setup["programs"] == ONE_OF_EACH
    assert one.setup["code_bytes"] is None
    assert other.setup["first_call_s"] is None


def test_the_staged_objects_delegate():
    step, args = problem()
    traced = step.trace(*args)
    assert "pmean" in str(traced.jaxpr) or "psum" in str(traced.jaxpr)
    lowered = traced.lower()
    assert lowered.as_text() == step._fn.lower(*args).as_text()
    compiled = lowered.compile()
    assert compiled.as_text().startswith("HloModule jit_shard_step")
    assert "flops" in (compiled.cost_analysis() or {"flops": 0})
    assert compiled.memory_analysis().argument_size_in_bytes > 0
    got, want = compiled(*args), step(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(AttributeError):
        compiled.no_such_attribute


def test_a_donating_step_takes_the_first_calls_route():
    """The trace and the lowering in front of call 0 consume nothing: the
    call donates its arguments as it always did."""
    step, (params, opt_state, batch) = problem(donate=True)
    out = step(params, opt_state, batch)
    assert step.setup["programs"] == ONE_OF_EACH
    assert params["w"].is_deleted()
    # Call 0's arguments sat on no mesh and its outputs do: the jit builds
    # a second program for them, inside call 1, and the account says so.
    out = step(out[0], out[1], batch)
    assert step.setup["recompiles"] == 1
    assert step.setup["last_compile_call"] == 1
    step(out[0], out[1], batch)
    assert step.setup["programs"] == {"traced": 2, "lowered": 2, "loaded": 2}


def test_a_step_inside_an_outer_jit_builds_nothing_of_its_own():
    step, args = problem()
    jax.jit(lambda *a: step(*a))(*args)
    assert step.setup["programs"] == {"traced": 1, "lowered": 0, "loaded": 0}
    assert step.setup["code_bytes"] is None


@pytest.fixture
def registry_on():
    metrics.registry.reset()
    metrics.registry.enable()
    try:
        yield metrics.registry
    finally:
        metrics.registry.disable()
        metrics.registry.reset()


def test_the_account_is_mirrored_with_the_registry_on(registry_on):
    step, args = problem()
    step.lower(*args).compile()
    step(*args)
    snapshot = registry_on.snapshot()
    assert snapshot["train_step"]["setup"] == step.setup
    assert snapshot["train_step"]["setup"] is not step.setup
    text = metrics.prometheus_text(snapshot)
    for stage in metrics.SETUP_STAGES:
        assert f'hvd_tpu_train_step_programs{{stage="{stage}"}} 1' in text
        seconds = [line for line in text.splitlines() if line.startswith(
            f'hvd_tpu_train_step_setup_seconds{{stage="{stage}"}}')]
        assert len(seconds) == 1 and float(seconds[0].split()[-1]) > 0
    assert (f"hvd_tpu_train_step_code_bytes {step.setup['code_bytes']}"
            in text)
    assert "hvd_tpu_train_step_recompiles 0" in text


def test_nothing_is_mirrored_with_the_registry_off():
    metrics.registry.reset()
    step, args = problem()
    step(*args)
    assert step.setup["programs"] == ONE_OF_EACH
    snapshot = metrics.registry.snapshot()
    assert snapshot["train_step"]["setup"] == metrics.new_step_setup()
    text = metrics.prometheus_text(snapshot)
    assert 'hvd_tpu_train_step_programs{stage="load"} 0' in text
    assert "hvd_tpu_train_step_code_bytes 0" in text


def test_the_kernels_mark_themselves_while_the_step_is_traced(monkeypatch):
    """Two attention layers on the flash kernels and two expert layers on
    the tiled grouped products (a TPU backend, said here; only traced): a
    flash call a layer and direction, and ONE grouped call a form and shape
    — gate and up share theirs, down has its own — whatever the depth,
    because `ops.moe._tiled_call` is jitted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=256, d_model=128, n_heads=2, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=True,
        moe=MoEConfig(8, 4, 384, (0, 2), None, renormalize=True),
        layers=("attention", "experts", "attention", "experts"))
    tokens = jnp.zeros((1, 512), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])
    tx = optax.sgd(0.1)

    def loss_fn(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step = build_train_step(loss_fn, tx, mesh)
    before = metrics.setup_table.process()
    step.trace(params, jax.eval_shape(tx.init, params), (tokens, tokens))
    kernels = step.setup["kernels"]
    assert {name: k["calls"] for name, k in kernels.items()} == {
        "hvd_flash_fwd": 2, "hvd_flash_bwd": 2, "hvd_grouped_fwd": 2,
        "hvd_grouped_drows": 2, "hvd_grouped_dweights": 2}
    assert all(k["trace_s"] > 0 for k in kernels.values())
    assert sum(k["trace_s"] for k in kernels.values()) \
        < step.setup["trace_s"]
    assert step.setup["programs"] == {"traced": 1, "lowered": 0, "loaded": 0}
    # The process's table has them too, each with its stamp.
    after = metrics.setup_table.process()
    # (by stamp, not by index: the table drops its older half when full)
    last = before["entries"][-1][0] if before["entries"] else 0.0
    marked = [e for e in after["entries"] if e[0] > last and e[1] == "kernel"]
    assert sorted(e[2] for e in marked) == sorted(
        name for name, k in kernels.items() for _ in range(k["calls"]))
    assert sum(e[3] for e in marked) == pytest.approx(
        sum(k["trace_s"] for k in kernels.values()))
    for name, k in kernels.items():
        assert after["kernels"][name]["calls"] \
            - before["kernels"].get(name, {"calls": 0})["calls"] \
            == k["calls"]


# --- the table alone: no JAX in it -------------------------------------------

class OpenStep:
    name, _calls = "shard_step", 1

    def __init__(self):
        self.setup = metrics.new_step_setup()


def test_a_trace_inside_a_trace_is_counted_once():
    """JAX ends an inner jit's trace before the outer one's: the outer
    entry holds what is left, the sums are the outer trace's seconds."""
    table, step = metrics.SetupTable(), OpenStep()
    with table.building(step):
        time.sleep(0.03)
        table.stage("trace", 0.010, "inner")          # began 10 ms ago
        table.stage("trace", 0.0001, "helper")        # no entry of its own
        table.stage("trace", 0.030, "shard_step")     # began before both
        table.stage("trace", 0.00001, "shard_step")   # JAX's cache answered
    assert step.setup["trace_s"] == pytest.approx(0.03001)
    assert step.setup["programs"]["traced"] == 1
    assert table.totals["trace_s"] == pytest.approx(0.03001)
    assert [(e[2], round(e[3], 4)) for e in table.entries] \
        == [("inner", 0.01), ("shard_step", 0.02)]
    assert table.open.step is None and not table.open.staged


def test_the_table_files_without_a_step_and_can_be_cut_at_a_moment():
    table = metrics.SetupTable()
    table.stage("lower", 0.5, "jit(reference)")
    with table.kernel_trace("hvd_flash_fwd"):
        pass
    cut = time.perf_counter()
    table.stage("load", 2.0, "jit(reference)")
    table.cache(hit=True)
    table.cache(hit=False)
    with table.kernel_trace("hvd_flash_fwd"):
        pass
    account = table.process()
    assert account["compiles"] == 1
    assert (account["cache_hits"], account["cache_misses"]) == (1, 1)
    assert account["lower_s"] == 0.5 and account["load_s"] == 2.0
    assert account["kernels"]["hvd_flash_fwd"]["calls"] == 2
    assert [e[1] for e in account["entries"] if e[0] < cut] \
        == ["lower", "kernel"]
    assert [e[1] for e in account["entries"] if e[0] >= cut] \
        == ["load", "cache_hit", "cache_miss", "kernel"]


def test_a_load_inside_a_later_call_is_that_calls():
    table, step = metrics.SetupTable(), OpenStep()
    step._calls = 8                                    # inside call 7
    table.open.step = step
    table.stage("load", 1.0, "jit(shard_step)")
    table.cache(hit=False)
    table.cache_retrieval(0.25)
    table.open.step = None
    assert step.setup["recompiles"] == 1
    assert step.setup["last_compile_call"] == 7
    assert step.setup["cache_misses"] == 1
    assert step.setup["cache_retrieval_s"] == 0.25
