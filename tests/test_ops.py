"""Attention kernel tests: flash/blockwise vs the dense reference, and
ring attention (sequence parallel over the virtual 8-device mesh) vs the
full-sequence result — values and gradients."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops import (blockwise_attention, flash_attention,
                             mha_reference, ring_attention)


def _qkv(batch=2, heads=2, seq=256, d=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, heads, seq, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_reference(causal):
    q, k, v = _qkv(seq=192, d=32)
    want = mha_reference(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_size=64)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(seq=256, d=64)
    want = mha_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_ragged_tail_falls_back():
    q, k, v = _qkv(seq=100, d=32)  # not a multiple of the block size
    want = mha_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_reference():
    q, k, v = _qkv(seq=128, d=32)

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bshd_layout_matches(causal):
    """layout='bshd' ((b, s, h, d), the transpose-free model path) matches
    the reference in values and gradients, including the multi-block
    grid."""
    q, k, v = _qkv(seq=256, d=64, seed=5)

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    def loss_bshd(q, k, v):
        t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
        out = flash_attention(t(q), t(k), t(v), causal=causal,
                              block_q=128, block_k=128, layout="bshd")
        return (t(out) ** 2).sum()

    np.testing.assert_allclose(
        jax.jit(loss_bshd)(q, k, v), loss_ref(q, k, v), rtol=1e-5)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_bshd = jax.grad(loss_bshd, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_bshd, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multiblock(causal):
    """Multi-block grid (seq 384 / block 128): exercises the Pallas
    backward's scratch accumulation across grid steps and, for causal, the
    above-diagonal block pruning."""
    q, k, v = _qkv(seq=384, d=64, seed=3)

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                block_q=128, block_k=128) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


def test_bwd_plan_matches_vmem_calibration():
    """The backward block plan must reproduce the v5e scoped-VMEM compile
    sweep (tools/vmem_sweep.py): the
    combined kernel's viability depends on sequence rows, head width AND
    the batch*heads grid dim (measured non-monotonic), so the plan bands
    are pinned exactly.  The r4 regression — tuned 1024-blocks that
    failed TPU compilation at seq 8192 — is the class of change this
    catches."""
    from horovod_tpu.ops.attention import _bwd_plan

    # bench-protocol shapes (token-constant seq:batch sweep)
    assert _bwd_plan(1024, 64, 1024, 1024, 128) == ("combined", 1024, 1024)
    assert _bwd_plan(2048, 64, 1024, 1024, 64) == ("combined", 1024, 1024)
    assert _bwd_plan(4096, 64, 1024, 1024, 32) == ("combined", 512, 1024)
    assert _bwd_plan(8192, 64, 1024, 1024, 16) == ("combined", 512, 512)
    # 16,384 rows: the combined kernel, asking Mosaic for the scoped VMEM the
    # plan computes (`_combined_vmem_limit`), at the bh the sweep probed
    assert _bwd_plan(16384, 64, 1024, 1024, 8) == ("combined", 512, 512)
    assert _bwd_plan(16384, 128, 1024, 1024, 32) == ("combined", 512, 512)
    assert _bwd_plan(16384, 128, 1024, 1024, 128) == ("combined", 512, 512)
    assert _bwd_plan(16384, 128, 1024, 1024, 256)[0] == "split"
    assert _bwd_plan(12288, 128, 1024, 1024, 32) == ("combined", 512, 512)
    # the bh frontier at seq 8192 (bh=64 measured 0.17 MiB over limit)
    assert _bwd_plan(8192, 64, 1024, 1024, 32)[0] == "combined"
    assert _bwd_plan(8192, 64, 1024, 1024, 64)[0] == "split"
    # bands never extrapolate past their calibrated bh bound
    assert _bwd_plan(1024, 64, 1024, 1024, 2048)[0] == "split"
    assert _bwd_plan(4096, 64, 1024, 1024, 1024)[0] == "split"
    assert _bwd_plan(2048, 128, 1024, 1024, 16)[0] == "combined"
    assert _bwd_plan(8192, 128, 1024, 1024, 16) == ("combined", 512, 512)
    # heads past 128 lanes (PR 67's band, `tools/vmem_sweep.py --wide`): the
    # combined kernel where its call ASKS, up to the whole-sequence dq the
    # 16,384-row band holds — the three cells that run such heads (JoyAI's
    # and Ling's latent attention at 192 / 128, Qwen3-Next's 256) ...
    # in the blocks the timing table chose (`_bwd_plan`): 1,024 past 4,096
    # rows, 512 up to there
    assert _bwd_plan(8192, 192, 1024, 1024, 32, 128) \
        == ("combined", 1024, 1024)
    assert _bwd_plan(8192, 192, 1024, 1024, 4, 128) == ("combined", 1024, 1024)
    assert _bwd_plan(4096, 256, 1024, 1024, 16) == ("combined", 512, 512)
    assert _bwd_plan(4096, 192, 1024, 1024, 128, 128) \
        == ("combined", 512, 512)
    assert _bwd_plan(8192, 256, 1024, 1024, 8) == ("combined", 1024, 1024)
    assert _bwd_plan(6144, 192, 1024, 1024, 8, 128) \
        == ("combined", 1024, 1024)
    assert _bwd_plan(8192, 192, 512, 512, 32, 128) == ("combined", 512, 512)
    # ... and the pair where a kernel that asks for nothing would do (the
    # old frontier: d=256 measured failing at seq 1024/bh 64 where the d=64
    # lane-equivalent passes), past the probes' bh, past the rows whose dq
    # the asking band holds, and past two tiles of lanes
    assert _bwd_plan(1024, 256, 1024, 1024, 64)[0] == "split"
    assert _bwd_plan(2048, 192, 1024, 1024, 32, 128)[0] == "split"
    assert _bwd_plan(8192, 192, 1024, 1024, 256, 128)[0] == "split"
    assert _bwd_plan(16384, 192, 1024, 1024, 4, 128)[0] == "split"
    assert _bwd_plan(16384, 256, 1024, 1024, 8)[0] == "split"
    with pytest.warns(UserWarning, match="clamped"):
        assert _bwd_plan(4096, 320, 1024, 1024, 8)[0] == "split"
    # past 16,384 rows the pair, as ever
    assert _bwd_plan(32768, 128, 1024, 1024, 8) == ("split", 1024, 1024)
    assert _bwd_plan(32768, 64, 1024, 1024, 32)[0] == "split"
    # plan blocks must divide the sequence even for non-pow2 lengths
    mode, bq, bk = _bwd_plan(11520, 64, 1024, 1024, 8)
    assert 11520 % bq == 0 and 11520 % bk == 0


# The benchmark's twelve language-model cells whose heads are 64 or 128 wide:
# rows, head width, batch * heads a chip, and what `_bwd_plan` and
# `_combined_vmem_limit` gave at commit 8ad772b, PR 67's parent.
NARROW_CELLS = [
    ("pythia410m_1chip_4x2k", 2048, 64, 64, ("combined", 1024, 1024), None),
    ("pythia410m_1chip_1x8k", 8192, 64, 16, ("combined", 512, 512), None),
    ("pythia410m_dp4_4x2k", 2048, 64, 64, ("combined", 1024, 1024), None),
    ("olmoe1b7b_1chip_ep4share_2x4k", 4096, 128, 32,
     ("combined", 512, 1024), None),
    ("nemotron3super120b_1chip_tp8ep64share_1x4k", 4096, 128, 4,
     ("combined", 512, 1024), None),
    ("trinitymini_1chip_ep8share_1x8k", 8192, 128, 32,
     ("combined", 512, 512), None),
    ("sdar30ba3b_1chip_ep8share_1x4k_noised", 8192, 128, 32,
     ("combined", 512, 512), None),
    ("mellum2_1chip_ep4share_1x16k", 16384, 128, 32,
     ("combined", 512, 512), 33095680),
    ("ouro2p6b_1chip_pp6share_1x4k", 4096, 128, 16,
     ("combined", 512, 1024), None),
    ("keyevl2_1chip_ep8share_1x8k", 8192, 128, 32,
     ("combined", 512, 512), None),
    ("olmohybrid7b_1chip_tp2share_1x8k", 8192, 128, 15,
     ("combined", 512, 512), None),
    ("granite4hmicro_1chip_pp4share_1x8k", 8192, 64, 32,
     ("combined", 512, 512), None),
]


@pytest.mark.parametrize("cell,seq,d,bh,plan,limit", NARROW_CELLS,
                         ids=[row[0] for row in NARROW_CELLS])
def test_bwd_plan_of_a_narrow_head_cell_is_its_parents(cell, seq, d, bh, plan,
                                                       limit):
    """The wide-head band and the estimate's lane rounding move nothing at a
    head of 64 or 128: each such cell's backward takes the mode and blocks it
    took, its call names the limit it named, and the estimate reads the bytes
    it read at 128 lanes."""
    import horovod_tpu.ops.attention as attn

    assert attn._bwd_plan(seq, d, 1024, 1024, bh) == plan
    assert attn._bwd_plan(seq, d, 1024, 1024, bh, d) == plan
    assert attn._combined_vmem_limit(seq, d, *plan[1:]) == limit
    for mode in ("combined", "split"):
        assert attn._plan_vmem_bytes(mode, seq, d, *plan[1:]) \
            == attn._plan_vmem_bytes(mode, seq, 128, *plan[1:], 128)
    assert attn._fwd_vmem_bytes(seq, d, 1024, 1024) \
        == attn._fwd_vmem_bytes(seq, 128, 1024, 1024)


def test_bwd_plan_fits_vmem_budget(monkeypatch):
    """Every plan the block selection emits must fit the COMPUTED
    scoped-VMEM estimate — the backstop behind the calibrated bands
    (the seq-8192 compile failure was a tuned block choice whose scoped
    footprint nobody computed).  Long-context shapes 8192/16384 are the
    regression region."""
    import horovod_tpu.ops.attention as attn

    asked = set()
    for seq in (8192, 16384, 32768):
        for d in (64, 128, 256):
            for bh in (8, 16, 32, 64, 256):
                mode, bq, bk = attn._bwd_plan(seq, d, 1024, 1024, bh)
                assert seq % bq == 0 and seq % bk == 0
                # what the plan's call asks Mosaic for; a plan that asks for
                # nothing (the pair never does) fits the default
                limit = attn._combined_vmem_limit(seq, d, bq, bk) \
                    if mode == "combined" else None
                assert limit is None or (attn._vmem_budget_bytes() < limit
                                         <= attn._MAX_VMEM_LIMIT)
                assert (attn._plan_vmem_bytes(mode, seq, d, bq, bk)
                        <= (limit or attn._vmem_budget_bytes())), (
                            seq, d, bh, mode)
                if limit:
                    asked.add((seq, d <= 128, bh <= 128))
    # only the band that the raised limit opened asks
    # ... and the wide-head band, every plan of which asks
    assert asked == {(16384, True, True), (8192, False, True)}
    for seq, d, d_v, bh in ((8192, 192, 128, 32), (8192, 192, 128, 4),
                            (4096, 256, 256, 16), (4096, 192, 128, 128),
                            (6144, 192, 128, 8), (8192, 256, 256, 128)):
        mode, bq, bk = attn._bwd_plan(seq, d, 1024, 1024, bh, d_v)
        assert mode == "combined" and seq % bq == 0 and seq % bk == 0
        limit = attn._combined_vmem_limit(seq, d, bq, bk, d_v)
        assert attn._vmem_budget_bytes() < limit <= attn._MAX_VMEM_LIMIT
        assert attn._plan_vmem_bytes(mode, seq, d, bq, bk, d_v) <= limit
    # Mosaic lays 192 lanes out in two whole tiles of 128: the estimate at
    # 192 / 128 is the one at 256 / 128, and at one tile or two it is what it
    # was (every earlier band, every earlier call's `vmem_limit_bytes`)
    for mode in ("combined", "split"):
        assert attn._plan_vmem_bytes(mode, 8192, 192, 1024, 1024, 128) \
            == attn._plan_vmem_bytes(mode, 8192, 256, 1024, 1024, 128)
    assert attn._combined_vmem_limit(16384, 128, 512, 512) == 33095680
    assert attn._plan_vmem_bytes("combined", 8192, 64, 512, 512) \
        == attn._plan_vmem_bytes("combined", 8192, 128, 512, 512) == 16318464
    # The measured r04 failure (combined 1024-blocks at seq 8192:
    # 23.2 MiB) must score over the default 16 MiB budget — the estimate
    # is only a guard if it rejects the shape that actually OOMed.
    assert (attn._plan_vmem_bytes("combined", 8192, 64, 1024, 1024)
            > attn._vmem_budget_bytes())
    # A shrunken budget clamps (with a warning) instead of handing
    # Mosaic a plan that cannot compile; 8 MiB cannot hold seq-8192
    # combined's whole-seq dq at ANY block size, so it demotes to split.
    monkeypatch.setenv("HVD_TPU_VMEM_LIMIT_MB", "8")
    with pytest.warns(UserWarning, match="scoped-VMEM"):
        mode, bq, bk = attn._bwd_plan(8192, 64, 1024, 1024, 16)
    assert mode == "split"
    assert (attn._plan_vmem_bytes(mode, 8192, 64, bq, bk)
            <= attn._vmem_budget_bytes())
    # A budget under the default says the chip has less than the bands were
    # calibrated for: the asking band is not entered, and 16,384 rows take
    # the pair as they did before the band was there.
    for bh in (8, 32, 128):
        mode, bq, bk = attn._bwd_plan(16384, 128, 1024, 1024, bh)
        assert mode == "split"
        assert (attn._plan_vmem_bytes(mode, 16384, 128, bq, bk)
                <= attn._vmem_budget_bytes())
    assert attn._bwd_plan(12288, 64, 1024, 1024, 32)[0] == "split"
    for seq, d, d_v, bh in ((8192, 192, 128, 32), (4096, 256, 256, 16)):
        with pytest.warns(UserWarning, match="clamped"):
            mode, bq, bk = attn._bwd_plan(seq, d, 1024, 1024, bh, d_v)
        assert mode == "split"
        assert (attn._plan_vmem_bytes(mode, seq, d, bq, bk, d_v)
                <= attn._vmem_budget_bytes())
    # The name bounds what a kernel has WITHOUT asking: raised past the
    # 16,384-row plan's need, that plan's call asks for nothing.
    assert attn._combined_vmem_limit(16384, 128, 512, 512) is not None
    monkeypatch.setenv("HVD_TPU_VMEM_LIMIT_MB", "32")
    assert attn._bwd_plan(16384, 128, 1024, 1024, 32)[0] == "combined"
    assert attn._combined_vmem_limit(16384, 128, 512, 512) is None
    monkeypatch.delenv("HVD_TPU_VMEM_LIMIT_MB")
    # The forward guard: explicit oversized blocks clamp to fitting ones
    # instead of compiling a >budget kernel.
    assert (attn._fwd_vmem_bytes(8192, 64, 8192, 1024)
            > attn._vmem_budget_bytes())
    with pytest.warns(UserWarning, match="clamped"):
        fitted = attn._clamp_blocks(
            "forward", 8192, 64, 8192, 1024,
            estimate=lambda _m, s, dd, a, b:
                attn._fwd_vmem_bytes(s, dd, a, b))
    assert fitted is not None
    assert attn._fwd_vmem_bytes(8192, 64, *fitted) <= attn._vmem_budget_bytes()


@pytest.mark.parametrize("mode", ["combined", "split"])
def test_flash_inside_shard_map_default_vma_check(monkeypatch, mode):
    """flash_attention is called inside build_train_step's shard_map, whose
    default check_vma=True refuses a pallas out_shape that does not say how
    it varies: forward and both backward plans must carry the annotation
    (TransformerLM(use_flash=True) through build_train_step failed at trace
    time without it)."""
    import horovod_tpu.ops.attention as attn

    monkeypatch.setattr(attn, "_bwd_plan",
                        lambda q_len, d, bq, bk, bh=1: (mode, 128, 128))
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    spec = P("dp")
    q, k, v = _qkv(batch=2, heads=2, seq=256, d=32, seed=9)

    def loss(q, k, v):
        out = shard_map(
            functools.partial(flash_attention, causal=True, block_q=128,
                              block_k=128),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
        return (out ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


def _pallas_eqns(jaxpr):
    """Every pallas_call equation, sub-programs included."""
    eqns = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            eqns.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            eqns += _pallas_eqns(sub)
    return eqns


def _pallas_call_names(jaxpr):
    """The `name` of every pallas_call equation, sub-programs included."""
    return [eqn.params["name"] for eqn in _pallas_eqns(jaxpr)]


def _flash_program(grad, plan=None):
    q, k, v = _qkv(batch=1, heads=2, seq=256, d=32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128).sum()

    return jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)) if grad else loss)(q, k, v)


def _ring_program(grad, impl="fused"):
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    q, k, v = _qkv(batch=1, heads=2, seq=4 * 128, d=16)
    spec = P(None, None, "sp", None)
    fn = functools.partial(ring_attention, axis_name="sp", causal=True,
                           rotate_impl=impl)

    def loss(q, k, v):
        return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v).sum()

    return jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)) if grad else loss)(q, k, v)


# PERF.md's table of kernel names, a case a pallas_call site: the public
# function whose program holds the call, the backward plan where one picks
# the site, and the names a device trace will show.
@pytest.mark.parametrize("build,plan,names", [
    (lambda: _flash_program(grad=False), None, {"hvd_flash_fwd"}),
    (lambda: _flash_program(grad=True), "combined",
     {"hvd_flash_fwd", "hvd_flash_bwd"}),
    (lambda: _flash_program(grad=True), "split",
     {"hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"}),
    (lambda: _ring_program(grad=False), None,
     {"hvd_ring_flash_fwd", "hvd_ring_flash_closer"}),
    (lambda: _ring_program(grad=True), None,
     {"hvd_ring_flash_fwd", "hvd_ring_flash_bwd",
      "hvd_ring_flash_closer"}),
    (lambda: _ring_program(grad=False, impl="rdma"), None,
     {"hvd_rdma_permute"}),
], ids=["flash_fwd", "flash_bwd_combined", "flash_bwd_split",
        "ring_flash_fwd_and_closer", "ring_flash_bwd", "rdma_permute"])
def test_pallas_calls_are_named(monkeypatch, build, plan, names):
    """Every pallas_call of ops/ names its kernel: the name reaches the
    operation's scope path and Mosaic's kernel_name, which is how a device
    trace tells the forward flash kernel from the backward."""
    import horovod_tpu.ops.attention as attn

    if plan is not None:
        monkeypatch.setattr(attn, "_bwd_plan",
                            lambda q_len, d, bq, bk, bh=1: (plan, 128, 128))
    # The ring's barrier-only closer exists only in the compiled form;
    # nothing is lowered here, so steer the backend query as
    # test_ring_variants_compile_on_mesh does.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert set(_pallas_call_names(build().jaxpr)) == names


def test_flash_split_backward_matches(monkeypatch):
    """The split dkdv/dq kernel pair (long-seq path) must match the
    blockwise gradients — forced via the plan so it runs at test sizes."""
    import horovod_tpu.ops.attention as attn

    monkeypatch.setattr(attn, "_bwd_plan",
                        lambda q_len, d, bq, bk, bh=1: ("split", 128, 128))
    q, k, v = _qkv(seq=384, d=64, seed=5)

    def loss_ref(q, k, v):
        return (blockwise_attention(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                block_q=128, block_k=128) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("window", [None, 400], ids=["causal", "band"])
def test_flash_combined_backward_equals_the_pair(monkeypatch, window):
    """One plan's gradients are the other's: the combined kernel and the
    split pair, each forced at (128, 128) blocks over 1,024 rows — a band of
    400 keys lies across five key tiles a query tile — give `mha_reference`'s
    dq, dk and dv, and each other's to a float32 sum's order."""
    import horovod_tpu.ops.attention as attn

    assert attn._live_tiles(1024, (128, 128), attn.Causal(window)) == (
        36 if window is None else 8 * 5 - 10)
    q, k, v = _qkv(batch=1, heads=2, seq=1024, d=64, seed=11)

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v, causal=True,
                                            window=window) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    by_plan = {}
    for plan in ("combined", "split"):
        monkeypatch.setattr(attn, "_bwd_plan",
                            lambda q_len, d, bq, bk, bh=1, plan=plan:
                            (plan, 128, 128))
        by_plan[plan] = grads(functools.partial(flash_attention, block_q=128,
                                                block_k=128))
    for one, other, ref in zip(by_plan["combined"], by_plan["split"],
                               grads(mha_reference)):
        np.testing.assert_allclose(one, ref, atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(one, other, atol=1e-5, rtol=1e-5)


def test_flash_combined_backward_at_two_widths_equals_the_pair(monkeypatch):
    """Latent attention's widths, 192 (q, k) and 128 (v), causal over 512 rows
    in (128, 128) blocks: the combined kernel — windows, accumulators and the
    whole-sequence dq each at its own width — gives `mha_reference`'s dq, dk
    and dv, each of its operand's shape, and the split pair's to a float32
    sum's order."""
    import horovod_tpu.ops.attention as attn

    keys = jax.random.split(jax.random.PRNGKey(67), 3)
    q, k = (jax.random.normal(key, (1, 2, 512, 192)) for key in keys[:2])
    v = jax.random.normal(keys[2], (1, 2, 512, 128))

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v, causal=True) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    by_plan = {}
    for plan in ("combined", "split"):
        monkeypatch.setattr(attn, "_bwd_plan",
                            lambda q_len, d, bq, bk, bh=1, d_v=None, plan=plan:
                            (plan, 128, 128))
        by_plan[plan] = grads(functools.partial(flash_attention, block_q=128,
                                                block_k=128))
    for one, other, ref in zip(by_plan["combined"], by_plan["split"],
                               grads(mha_reference)):
        assert one.shape == other.shape == ref.shape
        np.testing.assert_allclose(one, ref, atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(one, other, atol=1e-5, rtol=1e-5)


def test_combined_backward_asks_for_vmem_only_past_the_default():
    """A call whose computed need fits the budget a kernel has without asking
    carries the compiler parameters it always carried (`vmem_limit_bytes`
    None: the lowered text of every shape up to 8,192 rows is the parent's);
    Mellum's 16,384 rows of 128 ask for what `_combined_vmem_limit` says, the
    forward for nothing."""
    import horovod_tpu.ops.attention as attn

    def asked(seq, window):
        q = jax.ShapeDtypeStruct((1, 32, seq, 128), jnp.bfloat16)
        program = jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2)))(q, q, q)
        return {eqn.params["name"]: getattr(
            eqn.params["compiler_params"].get("mosaic_tpu"),
            "vmem_limit_bytes", None) for eqn in _pallas_eqns(program.jaxpr)}

    assert asked(8192, 2048) == {"hvd_flash_fwd_window": None,
                                 "hvd_flash_bwd_window": None}
    assert asked(8192, None) == {"hvd_flash_fwd": None, "hvd_flash_bwd": None}
    limit = attn._combined_vmem_limit(16384, 128, 512, 512)
    assert attn._vmem_budget_bytes() < limit <= attn._MAX_VMEM_LIMIT
    assert asked(16384, 1024) == {"hvd_flash_fwd_window": None,
                                  "hvd_flash_bwd_window": limit}
    assert asked(16384, None) == {"hvd_flash_fwd": None,
                                  "hvd_flash_bwd": limit}


def test_flash_nonpow2_scale_matches_reference():
    """head_dim 96: sm_scale is not a power of two — the pow2/residual
    scale split must keep full f32 logit accuracy (ADVICE r4: the old
    single pre-scale rounded q to bf16 under a non-representable
    scale)."""
    q, k, v = _qkv(seq=256, d=96, seed=7)
    want = mha_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                block_q=128, block_k=128) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


def _ring_apply(fn, q, k, v, mesh, axis):
    spec = P(None, None, axis, None)  # shard the sequence dimension
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    devices = jax.devices()
    assert len(devices) >= 8, "conftest forces an 8-device CPU platform"
    mesh = Mesh(np.array(devices[:8]), ("sp",))
    q, k, v = _qkv(batch=1, heads=2, seq=8 * 32, d=16)
    want = mha_reference(q, k, v, causal=causal)
    got = _ring_apply(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        q, k, v, mesh, "sp")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_ring_attention_gradients():
    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    q, k, v = _qkv(batch=1, heads=1, seq=4 * 16, d=8)
    spec = P(None, None, "sp", None)

    def ring_loss(q, k, v):
        out = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
        return (out ** 2).sum()

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_rdma_ring_permute_values_and_grad():
    """ops.rdma.ring_permute (Pallas async remote copy) matches
    lax.ppermute's shift rotation in value and VJP on the virtual mesh
    (interpret-mode remote DMA)."""
    from horovod_tpu.ops.rdma import ring_permute

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("r",))
    x = jnp.arange(4 * 8 * 128, dtype=jnp.float32).reshape(4, 8, 128)
    spec = P("r", None, None)

    def rotated(x, shift):
        return jax.jit(shard_map(
            lambda t: ring_permute(t, "r", shift=shift),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))(x)

    np.testing.assert_array_equal(rotated(x, 1), np.roll(x, 1, axis=0))
    np.testing.assert_array_equal(rotated(x, -1), np.roll(x, -1, axis=0))

    # VJP: d/dx sum(w * rotate(x)) == rotate_back(w).
    w = jnp.asarray(np.random.RandomState(0).rand(4, 8, 128), jnp.float32)

    def loss(x):
        rotated = jax.jit(shard_map(
            lambda t: ring_permute(t, "r"), mesh=mesh, in_specs=spec,
            out_specs=spec, check_vma=False))(x)
        return (rotated * w).sum()

    g = jax.grad(loss)(x)
    np.testing.assert_allclose(g, np.roll(w, -1, axis=0), rtol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_rdma_rotate_matches(causal):
    """ring_attention(rotate_impl='rdma') — K/V rotation as raw Pallas
    remote DMAs — matches the dense reference in value and gradient.
    (check_vma=False: interpret-mode pallas does not propagate the
    varying-manual-axes annotation through its internals.)"""
    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    q, k, v = _qkv(batch=1, heads=2, seq=4 * 32, d=16)
    want = mha_reference(q, k, v, causal=causal)
    spec = P(None, None, "sp", None)
    fn = functools.partial(ring_attention, axis_name="sp", causal=causal,
                           rotate_impl="rdma")
    got = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def ring_loss(q, k, v):
        out = shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
        return (out ** 2).sum()

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_rdma_phase_alternates_through_backward(monkeypatch):
    """The barrier-namespace discipline of ring_permute (rdma.py): within
    each DEPENDENCY CHAIN of rotations (ring_attention's K stream, and
    its V stream) the phase sequence must strictly alternate across the
    whole autodiff-composed program — forward, backward (the VJP flips
    within the chain pair), and the fwd/bwd seam — while the two
    independent chains use DISJOINT namespace pairs, so a lagging
    device's ready-wait can never be satisfied by a signal from either
    its chain's next invocation or the concurrently-scheduled other
    chain.  (The old single-pair global-alternation scheme asserted on
    jax's tracing order, which current jax no longer interleaves: custom
    VJP transposes now trace grouped per cotangent chain.)"""
    import horovod_tpu.ops.rdma as rdma

    phases = []
    real_raw = rdma._ring_permute_raw

    def recording_raw(x, axis_name, shift, interpret, phase):
        phases.append(phase % 4)
        return real_raw(x, axis_name, shift, interpret, phase)

    monkeypatch.setattr(rdma, "_ring_permute_raw", recording_raw)

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    q, k, v = _qkv(batch=1, heads=1, seq=4 * 16, d=8)
    spec = P(None, None, "sp", None)
    fn = functools.partial(ring_attention, axis_name="sp", causal=False,
                           rotate_impl="rdma")

    def ring_loss(q, k, v):
        out = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
        return (out ** 2).sum()

    jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    # Two chains (phase // 2), each recorded over forward AND backward
    # (3 fwd + 3 bwd rotations per chain on a 4-device ring).
    chains = {0: [], 1: []}
    for p in phases:
        chains[p // 2].append(p % 2)
    assert len(chains[0]) >= 4 and len(chains[1]) >= 4, phases
    # Within a chain, trace order follows the dependency chain (each
    # rotation consumes the previous one's output — forward — and each
    # transpose the next one's cotangent — backward), so the recorded
    # per-chain stream is the execution-order stream: it must strictly
    # alternate, seam included.
    for chain, stream in chains.items():
        for a, b in zip(stream, stream[1:]):
            assert a != b, (
                f"chain {chain}: adjacent invocations share a namespace: "
                f"{phases}")
    # Distinct chains map to disjoint collective_id namespaces.
    ids = {c: {rdma._COLLECTIVE_IDS[2 * c + p] for p in stream}
           for c, stream in chains.items()}
    assert not (ids[0] & ids[1]), ids


def test_blockwise_offsets_compose():
    """Shifted-window blockwise calls (the ring building block) agree with
    one global causal call."""
    q, k, v = _qkv(batch=1, heads=1, seq=64, d=16)
    full = blockwise_attention(q, k, v, causal=True, block_size=16)
    # Second half of queries attending over both halves of keys, via two
    # offset calls merged by hand is exactly what ring_attention does; here
    # just check the offset mask itself.
    got = blockwise_attention(q[:, :, 32:], k, v, causal=True,
                              block_size=16, q_offset=32, k_offset=0)
    np.testing.assert_allclose(got, full[:, :, 32:], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_ring_flash_matches_dense(causal):
    """VERDICT r2 #3: the fused ring-flash kernel (rotation DMA inside the
    Pallas program, per-step flash + lse merge) matches the dense
    reference in value AND gradient on the virtual mesh (interpret-mode
    remote DMA), for both causal and dense masks."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    q, k, v = _qkv(batch=1, heads=2, seq=4 * 32, d=16)
    want = mha_reference(q, k, v, causal=causal)
    spec = P(None, None, "sp", None)
    fn = functools.partial(ring_attention, axis_name="sp", causal=causal,
                           rotate_impl="fused")
    got = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def ring_loss(q, k, v):
        out = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
        return (out ** 2).sum()

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_fused_ring_flash_oversized_shard_raises_typed(monkeypatch):
    """Local shards whose combined-backward VMEM plan cannot compile are
    refused by name at trace time — neither a Mosaic compile failure on
    the backward pass (ADVICE r4) nor a quiet reroute to the separable
    ring, which would let a caller time the wrong kernel.  Forced via the
    plan so it runs at test sizes.  Ragged shard lengths raise the same
    type."""
    import horovod_tpu.ops.ring_flash as rf

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    spec = P(None, None, "sp", None)
    fn = functools.partial(rf.fused_ring_attention, axis_name="sp",
                           causal=True)

    def run(q, k, v):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))(q, k, v)

    with pytest.raises(rf.FusedRingUnsupported, match="does not tile"):
        run(*_qkv(batch=1, heads=2, seq=4 * 1100, d=16))
    # ring_flash binds _bwd_plan by value at import; patch its binding.
    monkeypatch.setattr(rf, "_bwd_plan", lambda *a: ("split", 128, 128))
    with pytest.raises(rf.FusedRingUnsupported, match="scoped VMEM"):
        run(*_qkv(batch=1, heads=2, seq=4 * 32, d=16))
    # A combined plan whose call would ask Mosaic for more than the default
    # (16,384 rows a shard) is no shape the ring's rotation was probed at.
    monkeypatch.setattr(rf, "_bwd_plan", lambda *a: ("combined", 32, 32))
    monkeypatch.setattr(rf, "_combined_vmem_limit", lambda *a: 32 << 20)
    with pytest.raises(rf.FusedRingUnsupported, match="asking for more"):
        run(*_qkv(batch=1, heads=2, seq=4 * 32, d=16))


@pytest.mark.parametrize("rows,d,chose", [
    (128, 192, "'split'"), (2048, 192, "'split'"),
    (4096, 192, "'combined', asking for more"),
    (8192, 192, "'combined', asking for more"),
    (4096, 256, "'combined', asking for more")])
def test_fused_ring_refuses_a_head_past_128_lanes(rows, d, chose):
    """The plan's wide-head band sends heads of 192 and 256 to the combined
    kernel only where its call asks Mosaic for more than the default, and the
    ring's rotating kernel was never probed there: a shard of such a head is
    refused by name at any length, by the plan as it stands (nothing
    forced) — the pair below the band, an asking plan inside it."""
    import horovod_tpu.ops.ring_flash as rf

    q = jax.ShapeDtypeStruct((1, 4, rows, d), jnp.bfloat16)
    with pytest.raises(rf.FusedRingUnsupported, match="scoped VMEM") as err:
        rf.fused_ring_attention(q, q, q, "sp", causal=True)
    assert f"head_dim {d}" in str(err.value) and chose in str(err.value)


@pytest.mark.slow  # ~15s; ring-flash numerics stay tier-1 in
# test_fused_ring_flash_matches_dense
def test_ring_flash_phase_stream_alternates(monkeypatch):
    """The fused ring kernels' barrier-namespace stream (collective_ids
    15/16, ops/ring_flash.py) must strictly alternate across the WHOLE
    fwd+bwd program AND across re-executions of the same jitted step —
    the rdma.py invariant (mirror of
    test_rdma_phase_alternates_through_backward).  Checks both the pure
    schedule (_rotation_phases: closer appended whenever a pass's
    rotating count is odd) and the wiring (the phases the step functions
    actually receive during an autodiff-composed run)."""
    import horovod_tpu.ops.ring_flash as rf

    # Pure schedule: for every ring size, one pass's barrier stream
    # (rotating steps + optional closer on 1) has even length and
    # alternates, so any concatenation of passes alternates cyclically.
    for n in range(2, 9):
        phases, needs_closer = rf._rotation_phases(n)
        stream = phases + ([1] if needs_closer else [])
        assert len(stream) % 2 == 0, (n, stream)
        for a, b in zip(stream, stream[1:]):
            assert a != b, (n, stream)
        assert not stream or stream[0] == 0, (n, stream)

    # Wiring: record the phases the rotating step kernels are invoked
    # with through a full forward+backward on a 4-device ring.
    events = []
    real_fwd, real_bwd = rf._ring_flash_step, rf._bwd_ring_step

    def rec_fwd(*args, **kw):
        if kw["rotate"]:
            events.append(("fwd", kw["phase"]))
        return real_fwd(*args, **kw)

    def rec_bwd(*args, **kw):
        if kw["rotate"]:
            events.append(("bwd", kw["phase"]))
        return real_bwd(*args, **kw)

    monkeypatch.setattr(rf, "_ring_flash_step", rec_fwd)
    monkeypatch.setattr(rf, "_bwd_ring_step", rec_bwd)

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    q, k, v = _qkv(batch=1, heads=2, seq=4 * 32, d=16)
    spec = P(None, None, "sp", None)
    fn = functools.partial(rf.fused_ring_attention, axis_name="sp",
                           causal=True)

    def loss(q, k, v):
        out = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
        return (out ** 2).sum()

    jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = rf._rotation_phases(4)[0]
    got_fwd = [p for kind, p in events if kind == "fwd"]
    got_bwd = [p for kind, p in events if kind == "bwd"]
    assert got_fwd == want, events
    assert got_bwd == want, events


def test_fused_ring_flash_bf16_and_uneven_heads():
    """Fused ring flash in bf16 with several heads stays close to the f32
    dense reference (bf16 tolerance), exercising the merge in the
    kernel's production dtype."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("sp",))
    q, k, v = _qkv(batch=2, heads=3, seq=4 * 16, d=32)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    want = mha_reference(q, k, v, causal=True)
    spec = P(None, None, "sp", None)
    fn = functools.partial(ring_attention, axis_name="sp", causal=True,
                           rotate_impl="fused")
    got = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False))(qb, kb, vb)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=3e-2, rtol=3e-2)
