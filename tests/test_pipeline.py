"""Pipeline-parallel subsystem tests (docs/pipeline.md): schedule
invariants model-checked by ``simulate_schedule``, grid arithmetic,
transformer partitioning, exact loss/gradient parity of the local
pipeline harness against the unpartitioned model, and the multi-rank
p2p plane — send/recv roundtrips, stage-group collectives, the
steady-state response-cache contract, and the fault surface (unmatched
send timeout, mid-schedule stage death -> typed RanksDownError).

The reference (SURVEY.md) has no point-to-point ops and no pipeline
story at all; everything here is new surface, so the parity tests pin
the numerics against the single-process model rather than against a
reference implementation.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.distributed import distributed_test  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Schedules (pure, in-process).
# ---------------------------------------------------------------------------


def test_1f1b_schedule_shape_and_simulation():
    from horovod_tpu.parallel import (schedule_1f1b, simulate_schedule)

    for n_stages in (1, 2, 4):
        for n_micro in (1, 2, 4, 8):
            for stage in range(n_stages):
                sched = schedule_1f1b(stage, n_stages, n_micro)
                fwd = [a for a in sched if a.kind == "fwd"]
                bwd = [a for a in sched if a.kind == "bwd"]
                # Every micro-batch runs exactly one fwd and one bwd, in
                # micro-batch order within each kind.
                assert [a.microbatch for a in fwd] == list(range(n_micro))
                assert [a.microbatch for a in bwd] == list(range(n_micro))
                # Warmup depth: the classic 1F1B ramp.
                warmup = min(n_stages - 1 - stage, n_micro)
                assert all(a.kind == "fwd" for a in sched[:warmup])
            # Dependency-complete and deadlock-free, and the makespan
            # sits inside the 1F1B envelope: 2M work ticks plus at most
            # the warmup/cooldown ramp.
            ticks = simulate_schedule(n_stages, n_micro)
            assert 2 * n_micro <= ticks <= \
                2 * n_micro + 2 * (n_stages - 1), (n_stages, n_micro, ticks)


def test_interleaved_schedule_simulation_and_guards():
    from horovod_tpu.parallel import (schedule_1f1b, schedule_interleaved,
                                      simulate_schedule)

    for n_stages in (2, 4):
        for n_micro in (n_stages, 2 * n_stages):
            ticks = simulate_schedule(n_stages, n_micro, n_chunks=2)
            assert ticks >= 2 * n_micro * 2  # work alone needs 2*M*V ticks
    # One chunk degenerates to plain 1F1B.
    assert schedule_interleaved(1, 4, 8, 1) == schedule_1f1b(1, 4, 8)
    # The interleaved order advances micro-batches in groups of S.
    with pytest.raises(ValueError, match="divisible"):
        schedule_interleaved(0, 4, 6, 2)


def test_bubble_fraction():
    from horovod_tpu.parallel import bubble_fraction

    assert bubble_fraction(1, 4) == 0.0
    assert bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    # Interleaving shrinks the bubble by the chunk count.
    assert bubble_fraction(4, 4, n_chunks=2) == pytest.approx(3 / 11)
    assert bubble_fraction(4, 4, 2) < bubble_fraction(4, 4, 1)


def test_pipeline_grid_arithmetic():
    from horovod_tpu.parallel import PipelineGrid

    g = PipelineGrid(2, 4, 3)  # 2 stages x 2 DP, rank 3
    assert (g.dp, g.stage, g.dp_index) == (2, 1, 1)
    assert g.stage_ranks() == [2, 3]
    assert g.stage_ranks(0) == [0, 1]
    assert g.rank_of(0) == 1  # same dp_index by default
    assert g.stage_of(1) == 0
    # Pipeline neighbours keep the dp_index and wrap modulo stages.
    assert g.prev_rank == 1
    assert g.next_rank == 1
    with pytest.raises(ValueError, match="divide"):
        PipelineGrid(3, 4, 0)


def test_partition_params_covers_every_layer():
    from horovod_tpu.parallel.pipeline import _split_layers

    splits = _split_layers(7, 3)
    assert [len(s) for s in splits] == [3, 2, 2]
    assert sorted(sum(splits, [])) == list(range(7))

    full = {"embed": {"embedding": 1},
            "final_norm": {"scale": 2},
            "lm_head_kernel": 3}
    full.update({f"layer_{i}": {"w": i} for i in range(4)})
    from horovod_tpu.parallel import partition_params

    parts = partition_params(full, 4, 2)
    assert "embed" in parts[0][0] and "lm_head_kernel" in parts[1][0]
    assert set(parts[0][0]) >= {"layer_0", "layer_1"}
    assert set(parts[1][0]) >= {"layer_2", "layer_3"}
    # Interleaved: first virtual gets the embedding, last the head.
    parts = partition_params(full, 4, 2, n_chunks=2)
    assert "embed" in parts[0][0] and "lm_head_kernel" in parts[1][1]
    names = [k for s in range(2) for c in range(2) for k in parts[s][c]]
    assert sorted(n for n in names if n.startswith("layer_")) == \
        [f"layer_{i}" for i in range(4)]


def test_p2p_wire_name_and_stage_group():
    from horovod_tpu.common import StageGroup, _p2p_wire_name, stage_group

    # Canonical wire name (docs/pipeline.md#wire-protocol): sender and
    # receiver derive the SAME string from their opposite perspectives.
    assert _p2p_wire_name("act", 0, 1, 2) == "act.p2p.0-1.t2"
    assert _p2p_wire_name(None, 3, 1, 0) == "p2p.p2p.3-1.t0"
    g = stage_group([3, 1, 1, 2])
    assert isinstance(g, StageGroup)
    assert g.ranks == (1, 2, 3) and g.size == 3 and 2 in g
    assert stage_group([1, 3, 2]) == g and hash(stage_group([2, 1, 3]))
    with pytest.raises(ValueError):
        stage_group([])
    with pytest.raises(ValueError):
        stage_group([-1, 0])


# ---------------------------------------------------------------------------
# Numerics: local pipeline == unpartitioned model (loss AND gradients).
# ---------------------------------------------------------------------------


def _tiny_lm(vocab=64, d_model=32, n_layers=4, n_heads=4, seq=16, batch=4):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          dtype=jnp.float32, use_flash=False)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, seq), jnp.int32))["params"]
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, vocab, (batch, seq + 1)).astype(np.int32)
    return model, params, tokens[:, :-1], tokens[:, 1:]


@pytest.mark.slow  # ~24s of JAX tracing; loss parity with the full model
# stays tier-1 in test_pipeline_2x2_trains_and_caches (the distributed
# acceptance path), schedule semantics in the simulate_schedule tests
@pytest.mark.parametrize("n_stages,n_chunks", [(2, 1), (2, 2)])
def test_local_pipeline_matches_full_model(n_stages, n_chunks):
    """The core parity bar: a partitioned 1F1B (and interleaved) pipeline
    over LocalTransport reproduces the full model's loss and per-leaf
    gradients — same math, only the execution is pipelined."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import next_token_loss
    from horovod_tpu.parallel import (PipelineGrid, PipelineRunner,
                                      LocalTransport, partition_params,
                                      partition_transformer,
                                      run_local_pipeline)

    vocab, d_model, n_layers, n_heads, seq = 64, 32, 4, 4, 16
    model, params, inputs, targets = _tiny_lm(vocab, d_model, n_layers,
                                              n_heads, seq)

    def full_loss(p):
        return next_token_loss(
            model.apply({"params": p}, jnp.asarray(inputs)),
            jnp.asarray(targets))

    want_loss, want_grads = jax.value_and_grad(full_loss)(params)

    modules = partition_transformer(vocab, d_model, n_layers, n_heads,
                                    n_stages=n_stages, n_chunks=n_chunks,
                                    dtype=jnp.float32, use_flash=False)
    parts = partition_params(params, n_layers, n_stages, n_chunks=n_chunks)
    transport = LocalTransport()
    runners = [PipelineRunner(modules[s], parts[s],
                              PipelineGrid(n_stages, n_stages, s),
                              n_micro=2, transport=transport,
                              loss_fn=(next_token_loss
                                       if s == n_stages - 1 else None))
               for s in range(n_stages)]
    loss, grads = run_local_pipeline(runners, inputs, targets)

    assert loss == pytest.approx(float(want_loss), abs=1e-4)
    # Reassemble the sliced gradient trees and compare leaf-for-leaf.
    got = {}
    for stage_grads in grads:
        for chunk_tree in stage_grads:
            got.update(chunk_tree)
    for key, want_sub in want_grads.items():
        got_leaves = jax.tree.leaves(got[key])
        want_leaves = jax.tree.leaves(want_sub)
        for gl, wl in zip(got_leaves, want_leaves):
            np.testing.assert_allclose(np.asarray(gl), np.asarray(wl),
                                       atol=2e-3, rtol=1e-2)


# ---------------------------------------------------------------------------
# Multi-rank: the engine p2p plane.
# ---------------------------------------------------------------------------


@distributed_test(np_=2)
def test_send_recv_roundtrip():
    import os
    # Metrics ON: the gated Python-side recording paths (Handle wait
    # latency, negotiation histogram) must accept p2p ops.
    os.environ["HVD_TPU_METRICS"] = "1"
    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    x = np.arange(32, dtype=np.float32) * (rank + 1)
    out = np.empty(32, np.float32)
    # Full exchange 0<->1 on distinct tags: the canonical wire name
    # pairs each send with exactly one recv.
    peer = 1 - rank
    if rank == 0:
        hvd.send(x, peer, tag=0, name="fwd")
        hvd.recv(out, peer, tag=1, name="bwd")
        np.testing.assert_array_equal(
            out, np.arange(32, dtype=np.float32) * 2)
    else:
        hvd.recv(out, peer, tag=0, name="fwd")
        np.testing.assert_array_equal(out, np.arange(32, dtype=np.float32))
        hvd.send(x, peer, tag=1, name="bwd")
    # Observability parity (docs/pipeline.md#observability): the p2p
    # section counts this rank's transfers and wire bytes.
    snap = hvd.metrics_snapshot()["p2p"]
    assert snap["sends"] == 1 and snap["recvs"] == 1, snap
    assert snap["matched"] >= 1, snap
    assert snap["bytes"]["out"] >= 32 * 4 or snap["bytes"]["in"] >= 32 * 4
    hvd.shutdown()


@distributed_test(np_=2)
def test_send_recv_async_and_validation():
    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    with pytest.raises(ValueError):
        hvd.send(np.ones(4, np.float32), hvd.rank())  # self-send
    with pytest.raises(ValueError):
        hvd.send(np.ones(4, np.float32), 99)  # out of range
    with pytest.raises(ValueError):
        hvd.recv(np.ones(4, np.float32), 1 - rank, tag=-1)  # bad tag
    xs = [np.full(16, i + 10 * rank, np.float32) for i in range(4)]
    if rank == 0:
        handles = [hvd.send_async(xs[i], 1, tag=i) for i in range(4)]
        for h in handles:
            h.wait()
    else:
        outs = [np.empty(16, np.float32) for _ in range(4)]
        handles = [hvd.recv_async(outs[i], 0, tag=i) for i in range(4)]
        for i, h in enumerate(handles):
            h.wait()
            np.testing.assert_array_equal(outs[i], np.full(16, i,
                                                           np.float32))
    hvd.shutdown()


@distributed_test(np_=4)
def test_stage_group_allreduce_values():
    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    stage = rank // 2
    group = hvd.stage_group([2 * stage, 2 * stage + 1])
    x = np.full(8, float(rank + 1), np.float32)
    # Group mean: {0,1} -> 1.5, {2,3} -> 3.5 (names are stage-scoped —
    # disjoint groups negotiate the same leaf concurrently).
    got = hvd.allreduce(x, name=f"grad.s{stage}", group=group)
    want = 1.5 if stage == 0 else 3.5
    np.testing.assert_allclose(got, np.full(8, want, np.float32))
    got = hvd.allreduce(x, average=False, name=f"sum.s{stage}", group=group)
    np.testing.assert_allclose(got, np.full(8, 3.0 if stage == 0 else 7.0,
                                            np.float32))
    assert hvd.metrics_snapshot()["p2p"]["group_ops"] >= 2
    # A plain world collective still works alongside scoped ones.
    total = hvd.allreduce(np.ones(4, np.float32), average=False,
                          name="world")
    np.testing.assert_allclose(total, np.full(4, 4.0, np.float32))
    hvd.shutdown()


@distributed_test(np_=4)
def test_stage_group_mismatch_is_a_typed_precondition():
    """Two disjoint groups announcing the SAME tensor name is a scoping
    bug (the grad-allreduce collision class); the coordinator rejects it
    with a typed ValueError naming the tensor instead of corrupting
    either group's reduction."""
    import horovod_tpu as hvd

    hvd.init()
    stage = hvd.rank() // 2
    group = hvd.stage_group([2 * stage, 2 * stage + 1])
    try:
        hvd.allreduce(np.ones(4, np.float32), name="clash", group=group)
        raise SystemExit(9)  # must not complete on any rank
    except ValueError as e:
        assert "Mismatched stage groups" in str(e) and "clash" in str(e), e
    except hvd.common.HorovodInternalError as e:
        # Ranks that lose the race see the resulting coordinated abort.
        assert "shut down" in str(e), e
    try:
        hvd.shutdown()
    except Exception:
        pass  # the abort may already have torn the engine down


# ---------------------------------------------------------------------------
# End-to-end: 2-stage x 2-DP training smoke (the ISSUE acceptance grid).
# ---------------------------------------------------------------------------


@distributed_test(np_=4, timeout=420.0)
def test_pipeline_2x2_trains_and_caches():
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.jax.train import run_pipeline
    from horovod_tpu.models import TransformerLM, next_token_loss
    from horovod_tpu.parallel import (PipelineGrid, partition_params,
                                      partition_transformer)

    hvd.init()
    vocab, d_model, n_layers, n_heads, seq, batch, micro = \
        32, 16, 2, 2, 8, 4, 2
    grid = PipelineGrid(2, hvd.size(), hvd.rank())
    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_layers=n_layers, n_heads=n_heads,
                          dtype=jnp.float32, use_flash=False)
    full = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, seq), jnp.int32))["params"]
    modules = partition_transformer(vocab, d_model, n_layers, n_heads,
                                    n_stages=2, dtype=jnp.float32,
                                    use_flash=False)[grid.stage]
    params = partition_params(full, n_layers, 2)[grid.stage]
    rng = np.random.RandomState(100 + grid.dp_index)
    tokens = rng.randint(0, vocab, (batch, seq + 1)).astype(np.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    tx = optax.adamw(1e-3)

    # One batch first: the response cache fills during this step.
    params, _, losses = run_pipeline(modules, params, tx,
                                     [(inputs, targets)], n_stages=2,
                                     n_microbatches=micro,
                                     loss_fn=next_token_loss)
    if grid.stage == 1:
        # Loss parity with the unpartitioned model on this DP shard:
        # step 1 runs on the deterministic init params.
        want = float(next_token_loss(
            model.apply({"params": full}, jnp.asarray(inputs)),
            jnp.asarray(targets)))
        assert losses[0] == pytest.approx(want, abs=2e-3), (losses, want)
    else:
        assert losses == [None]
    snap0 = hvd.metrics_snapshot()

    # Steady state: the same fixed-shape bucket stream must replay
    # through the response cache (docs/pipeline.md#steady-state).
    params, _, losses = run_pipeline(modules, params, tx,
                                     [(inputs, targets)] * 2, n_stages=2,
                                     n_microbatches=micro,
                                     loss_fn=next_token_loss)
    snap1 = hvd.metrics_snapshot()
    if grid.stage == 1:
        assert all(np.isfinite(lo) for lo in losses), losses
    hits = snap1["cache"]["engine"]["hits"] - snap0["cache"]["engine"]["hits"]
    misses = (snap1["cache"]["engine"]["misses"]
              - snap0["cache"]["engine"]["misses"])
    assert hits / max(hits + misses, 1) >= 0.9, (hits, misses)
    p2p = snap1["p2p"]
    assert p2p["sends"] >= 3 * micro and p2p["recvs"] >= 3 * micro, p2p
    assert p2p["unmatched"] == 0, p2p
    hvd.shutdown()


@pytest.mark.slow  # ~2 min: the deep-pipeline matrix; the 2x2 grid above
# keeps the contract tier-1
@distributed_test(np_=4, timeout=420.0)
def test_pipeline_4stage_deep():
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.jax.train import run_pipeline
    from horovod_tpu.models import TransformerLM, next_token_loss
    from horovod_tpu.parallel import (PipelineGrid, partition_params,
                                      partition_transformer)

    hvd.init()
    vocab, d_model, n_layers, n_heads, seq, batch, micro = \
        32, 16, 4, 2, 8, 8, 4
    grid = PipelineGrid(4, hvd.size(), hvd.rank())
    full = TransformerLM(vocab_size=vocab, d_model=d_model,
                         n_layers=n_layers, n_heads=n_heads,
                         dtype=jnp.float32, use_flash=False).init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))["params"]
    modules = partition_transformer(vocab, d_model, n_layers, n_heads,
                                    n_stages=4, dtype=jnp.float32,
                                    use_flash=False)[grid.stage]
    params = partition_params(full, n_layers, 4)[grid.stage]
    tokens = np.random.RandomState(5).randint(
        0, vocab, (batch, seq + 1)).astype(np.int32)
    params, _, losses = run_pipeline(
        modules, params, optax.adamw(1e-3),
        [(tokens[:, :-1], tokens[:, 1:])] * 2,
        n_stages=4, n_microbatches=micro, loss_fn=next_token_loss)
    if grid.stage == 3:
        assert all(np.isfinite(lo) for lo in losses), losses
    hvd.shutdown()


# ---------------------------------------------------------------------------
# Fault surface (docs/pipeline.md#faults).
# ---------------------------------------------------------------------------


def _env(**overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    env.setdefault("HVD_TPU_KILL_GRACE_SEC", "3")
    env.update({k: str(v) for k, v in overrides.items()})
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA", "HVD_TPU_FAULT_SPEC"):
        if not env.get(var):
            env.pop(var, None)
    return env


def test_unmatched_send_times_out_naming_tensor_and_peer():
    """A send whose receiver never announces must surface as a
    CollectiveTimeoutError naming the wire tensor AND the missing peer
    (paired readiness is the deadlock backstop: the transfer never
    starts, so nothing can wedge half-written)."""
    from horovod_tpu.runner import run_command

    code = (
        "import os, time, numpy as np, horovod_tpu as hvd\n"
        "from horovod_tpu.common import CollectiveTimeoutError\n"
        "hvd.init()\n"
        "t0 = time.monotonic()\n"
        "if hvd.rank() == 0:\n"
        "    try:\n"
        "        hvd.send(np.ones(8, np.float32), 1, name='act')\n"
        "        os._exit(9)\n"
        "    except CollectiveTimeoutError as e:\n"
        "        assert 'act.p2p.0-1.t0' in str(e), str(e)\n"
        "        assert 'peer rank 1' in str(e), str(e)\n"
        "        assert time.monotonic() - t0 < 15.0\n"
        "        os._exit(7)\n"
        "else:\n"
        "    time.sleep(60)\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 2,
        env=_env(HVD_TPU_COLLECTIVE_TIMEOUT_SEC="2"),
        timeout=90.0, capture=True)
    by_rank = {r.rank: r for r in results}
    assert by_rank[0].returncode == 7, \
        (by_rank[0].returncode, by_rank[0].stderr[-800:])
    assert by_rank[1].returncode == -9  # grace-killed sleeper


def test_stage_death_mid_schedule_names_stage_on_survivors():
    """The ISSUE fault acceptance: killing a stage rank mid-schedule
    (crash fault inside the p2p stream) yields a typed RanksDownError on
    EVERY survivor, naming the dead rank and its pipeline stage, well
    under the 25s bound."""
    from horovod_tpu.runner import run_command

    code = (
        "import time, numpy as np\n"
        "import jax, jax.numpy as jnp\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu.common import RanksDownError\n"
        "from horovod_tpu.models import TransformerLM, next_token_loss\n"
        "from horovod_tpu.parallel import (PipelineGrid, PipelineRunner,\n"
        "                                  EngineTransport,\n"
        "                                  partition_params,\n"
        "                                  partition_transformer)\n"
        "hvd.init()\n"
        "grid = PipelineGrid(2, hvd.size(), hvd.rank())\n"
        "full = TransformerLM(vocab_size=32, d_model=16, n_layers=2,\n"
        "                     n_heads=2, dtype=jnp.float32,\n"
        "                     use_flash=False).init(\n"
        "    jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params']\n"
        "modules = partition_transformer(32, 16, 2, 2, n_stages=2,\n"
        "                                dtype=jnp.float32,\n"
        "                                use_flash=False)[grid.stage]\n"
        "params = partition_params(full, 2, 2)[grid.stage]\n"
        "runner = PipelineRunner(modules, params, grid, 2,\n"
        "                        EngineTransport(),\n"
        "                        loss_fn=(next_token_loss\n"
        "                                 if grid.stage == 1 else None))\n"
        "tokens = np.random.RandomState(0).randint(\n"
        "    0, 32, (4, 9)).astype(np.int32)\n"
        "runner.set_bucket_shape(2, 8)\n"
        "t_last = time.monotonic()\n"
        "try:\n"
        "    for _ in range(4):\n"
        "        runner.step(tokens[:, :-1] if grid.stage == 0 else None,\n"
        "                    tokens[:, 1:] if grid.stage == 1 else None)\n"
        "        t_last = time.monotonic()\n"
        "    raise SystemExit(9)  # survivors must NOT finish\n"
        "except RanksDownError as e:\n"
        "    assert 3 in e.ranks, (e.ranks, str(e))\n"
        "    assert 'pipeline aborted mid-schedule' in str(e), str(e)\n"
        "    assert 'stage 1' in str(e), str(e)\n"
        "    # The ISSUE bound: kill -> typed error on every survivor in\n"
        "    # < 25s.  Measured from the last completed step (first-step\n"
        "    # JAX tracing is compute, not detection latency).\n"
        "    assert time.monotonic() - t_last < 25.0\n"
        "    raise SystemExit(0)\n"
    )
    results = run_command(
        [sys.executable, "-c", code], 4,
        env=_env(
            # Rank 3 enqueues 4 p2p ops per step: op=9 crashes it in
            # its THIRD step, past every rank's first-step JAX tracing
            # (~20s) — the 2 DP chains (0<->2, 1<->3) share no p2p, so
            # an early crash could interrupt a survivor still tracing
            # step 0 with t_last never advanced past the pre-loop stamp.
            HVD_TPU_FAULT_SPEC="rank=3:crash@op=9",
            HVD_TPU_COLLECTIVE_TIMEOUT_SEC="20",
            # Survivors surface the error and exit 0 on their own; a
            # short grace would SIGKILL the one still inside a JAX
            # dispatch when the crashed rank's rc lands.
            HVD_TPU_KILL_GRACE_SEC="20"),
        timeout=180.0, capture=True)
    by_rank = {r.rank: r for r in results}
    from horovod_tpu.common.faults import CRASH_EXIT_CODE

    assert by_rank[3].returncode == CRASH_EXIT_CODE, by_rank[3]
    for r in (0, 1, 2):
        assert by_rank[r].returncode == 0, \
            (r, by_rank[r].returncode, by_rank[r].stderr[-1500:])
