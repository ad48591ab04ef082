"""Attention kernel tests: flash/blockwise vs the dense reference, and
ring attention (sequence parallel over the virtual 8-device mesh) vs the
full-sequence result — values and gradients."""

import functools
import re

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
    assert _bwd_plan(16384, 64, 1024, 1024, 8)[0] == "split"
    # the bh frontier at seq 8192 (bh=64 measured 0.17 MiB over limit)
    assert _bwd_plan(8192, 64, 1024, 1024, 32)[0] == "combined"
    assert _bwd_plan(8192, 64, 1024, 1024, 64)[0] == "split"
    # bands never extrapolate past their calibrated bh bound
    assert _bwd_plan(1024, 64, 1024, 1024, 2048)[0] == "split"
    assert _bwd_plan(4096, 64, 1024, 1024, 1024)[0] == "split"
    # wide heads never take the combined kernel (d=256 measured failing
    # at seq 1024/bh 64 where the d=64 lane-equivalent passes)
    assert _bwd_plan(2048, 128, 1024, 1024, 16)[0] == "combined"
    assert _bwd_plan(8192, 128, 1024, 1024, 16) == ("combined", 512, 512)
    assert _bwd_plan(1024, 256, 1024, 1024, 64)[0] == "split"
    assert _bwd_plan(4096, 256, 1024, 1024, 16)[0] == "split"
    assert _bwd_plan(32768, 128, 1024, 1024, 8)[0] == "split"
    # plan blocks must divide the sequence even for non-pow2 lengths
    mode, bq, bk = _bwd_plan(11520, 64, 1024, 1024, 8)
    assert 11520 % bq == 0 and 11520 % bk == 0


def test_bwd_plan_fits_vmem_budget(monkeypatch):
    """Every plan the block selection emits must fit the COMPUTED
    scoped-VMEM estimate — the backstop behind the calibrated bands
    (the seq-8192 compile failure was a tuned block choice whose scoped
    footprint nobody computed).  Long-context shapes 8192/16384 are the
    regression region."""
    import horovod_tpu.ops.attention as attn

    for seq in (8192, 16384):
        for d in (64, 128, 256):
            for bh in (8, 16, 32, 64, 256):
                mode, bq, bk = attn._bwd_plan(seq, d, 1024, 1024, bh)
                assert seq % bq == 0 and seq % bk == 0
                assert (attn._plan_vmem_bytes(mode, seq, d, bq, bk)
                        <= attn._vmem_budget_bytes()), (seq, d, bh, mode)
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


# ---------------------------------------------------------------------------
# The chip's compiler, without the chip: libtpu compiles for a DESCRIBED
# v5e 2x2 host (jax.experimental.topologies), which refuses what the chip
# would refuse — scoped-VMEM overruns, tiling violations, a kernel that
# cannot be partitioned — and interpret mode cannot.  Nothing runs, so
# these say nothing about results or times.  One file, in-process: libtpu
# takes a lock file, so two processes cannot describe a topology at once.
# Code that asks jax.default_backend() still sees the CPU, so the kernels
# get interpret=False explicitly (or the test steers the backend query).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e():
    """The devices of a described (not attached) v5e 2x2 host; skips where
    libtpu cannot describe one."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu, or another process holds its lock
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")
    return topo.devices


def _compile_flash_grad(device, shape, **kwargs):
    from jax.sharding import SingleDeviceSharding

    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(device))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               **kwargs).astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return grad.lower(q, q, q).compile().as_text()


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("seq", [1024, 4096, 8192, 16384])
def test_flash_bwd_seq_sweep_compiles(v5e, seq, d):
    """The documented long-context sweep {1k, 4k, 8k, 16k} x head_dim
    {64, 128} must COMPILE for fwd+bwd at the bench-protocol batch
    (token-constant seq:batch pairs — batch*heads feeds _bwd_plan's bh
    frontier) through the chip's own compiler: a scoped-VMEM OOM (the r4
    failure) or a block/shape mismatch in the plan routing fails here."""
    from horovod_tpu.ops.attention import _bwd_plan

    batch = {1024: 16, 4096: 4, 8192: 2, 16384: 1}[seq]
    text = _compile_flash_grad(v5e[0], (batch, 8, seq, d))
    # forward + combined backward, or forward + the split dkdv/dq pair
    mode = _bwd_plan(seq, d, 1024, 1024, batch * 8)[0]
    assert text.count("tpu_custom_call") == {"combined": 2, "split": 3}[mode]


def test_flash_head128_at_olmoe_shape_compiles(v5e):
    """OLMoE's attention as the benchmark's sparse-expert cell runs it — 2
    sequences x 16 heads of 128 x 4,096 — in the mode _bwd_plan picks
    (rows128 = 4096, bh = 32: the combined backward at (512, 1024) blocks).
    The chip's compiler accepts the plan: the band needed no recalibration
    (PR 26; the whole step of that cell compiles with it too)."""
    from horovod_tpu.ops.attention import _bwd_plan

    assert _bwd_plan(4096, 128, 1024, 1024, 32) == ("combined", 512, 1024)
    text = _compile_flash_grad(v5e[0], (2, 16, 4096, 128))
    assert text.count("tpu_custom_call") == 2


def test_delta_rule_carry_kernels_at_qwen3next_widths_compile(v5e,
                                                              monkeypatch):
    """The head form's recurrence as the Qwen3-Next cell runs it — heads of
    128 channels, two value heads a key head, chunks of 64, bfloat16 — through
    the chip's compiler, forward and backward: two Mosaic kernels and no
    loop, as `lowered_plan` says (the rule asks the backend which way to run
    its kernels; here it is compiling for the described chip)."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.delta_rule import chunked_delta_rule, lowered_plan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16, sharding=on_chip)
    v = jax.ShapeDtypeStruct((1, 512, 4, 128), jnp.bfloat16, sharding=on_chip)
    gate = jax.ShapeDtypeStruct((1, 512, 4), jnp.float32, sharding=on_chip)

    def loss(*operands):
        return chunked_delta_rule(*operands, 64, scope="hvd_gdn_scan")[0].sum()

    text = jax.jit(jax.grad(loss, range(5))).lower(
        q, q, v, gate, gate).compile().as_text()
    plan = lowered_plan(512, 64)
    assert text.count("custom_call_target=\"tpu_custom_call\"") \
        == plan["tpu_custom_call"] == 2
    assert text.count(" while(") == plan["while"] == 0
    for kernel in ("hvd_gdn_scan_carry_fwd", "hvd_gdn_scan_carry_bwd"):
        assert f"%{kernel}" in text, kernel


def test_flash_two_widths_at_latent_attention_shape_compile(v5e):
    """Latent attention as the Ling-3.0-flash cell runs it — 4 heads, 8,192
    tokens, query and key 192 wide, value 128 — forward and backward through
    the chip's compiler with nothing padded: the plan enters its bands with
    the wider width, so the backward is the split pair at 1024-blocks, and
    the gradients keep their operands' widths (PR 32)."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.attention import _bwd_plan

    assert _bwd_plan(8192, 192, 1024, 1024, 4, 128) == ("split", 1024, 1024)
    # One width, as every call before PR 32: the same plan with and without.
    assert _bwd_plan(8192, 64, 1024, 1024, 16, 64) \
        == _bwd_plan(8192, 64, 1024, 1024, 16) == ("combined", 512, 512)
    on_chip = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 4, 8192, 192), jnp.bfloat16, sharding=on_chip)
    v = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16, sharding=on_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"):
        assert name in text
    assert [g.shape[-1] for g in compiled.out_info] == [192, 192, 128]


def test_flash_head256_at_qwen3next_shape_compiles(v5e):
    """Gated attention as the Qwen3-Next cell runs it — 1 x 16 heads of 256 at
    4,096 rows (a key/value head repeated for its 8 query heads before the
    kernels) — in the band `_bwd_plan` sends it to: no combined backward past
    128 lanes, so the split pair at 1,024-blocks, which with the forward
    compiles for the described chip (PR 46: the first cell past a head of
    128)."""
    from horovod_tpu.ops.attention import _bwd_plan

    assert _bwd_plan(4096, 256, 1024, 1024, 16) == ("split", 1024, 1024)
    text = _compile_flash_grad(v5e[0], (1, 16, 4096, 256))
    assert text.count('"tpu_custom_call"') == 3
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"):
        assert name in text


def test_grouped_matmul_lowers_to_libtpu_kernels(v5e):
    """ops.moe.grouped_matmul at the sparse-expert cell's shapes — 24,576
    rows of 2,048 against 16 experts of 1,024 — forward and both gradients:
    libtpu lowers each ragged_dot to a Mosaic kernel of its own (custom
    calls named ragged-dot-*), not to a dense product over every group."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.moe import grouped_matmul

    on_chip = SingleDeviceSharding(v5e[0])
    rows = jax.ShapeDtypeStruct((24576, 2048), jnp.bfloat16,
                                sharding=on_chip)
    weights = jax.ShapeDtypeStruct((16, 2048, 1024), jnp.bfloat16,
                                   sharding=on_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=on_chip)

    def loss(rows, weights, sizes):
        return grouped_matmul(rows, weights, sizes).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        rows, weights, sizes).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 2
    assert " while(" not in text
    # One dense product over the buffer's rows each, not one per group.
    dense = 2 * 24576 * 2048 * 1024
    assert 1.9 * dense < compiled.cost_analysis()["flops"] < 2.2 * dense


@pytest.mark.parametrize("seq,blocks", [(2048, 2048), (4096, 4096)])
def test_flash_oversized_explicit_block_compiles(v5e, seq, blocks):
    """ADVICE r5 #2: an explicit block past the calibrated 1024 passes the
    divisibility checks but is refused by the chip's compiler (2048-row
    blocks at seq 2048 fail the backward, 4096 at seq 4096 the forward);
    flash_attention cuts it to the calibrated maximum as it does the
    default."""
    text = _compile_flash_grad(v5e[0], (4, 8, seq, 64), block_q=blocks,
                               block_k=blocks)
    assert text.count("tpu_custom_call") == 2


def _sp_mesh(devices):
    return Mesh(np.array(devices).reshape(1, 4), ("dp", "sp"))


def test_rdma_ring_permute_compiles_on_mesh(v5e):
    """The raw remote-DMA rotation compiles for four described chips on a
    two-axis mesh (MESH device ids) under shard_map's default vma check,
    forward and transposed."""
    from jax.sharding import NamedSharding

    from horovod_tpu.ops.rdma import ring_permute

    mesh = _sp_mesh(v5e)
    spec = P("dp", None, "sp", None)
    x = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))

    def loss(x):
        out = shard_map(
            functools.partial(ring_permute, axis_name="sp", interpret=False),
            mesh=mesh, in_specs=spec, out_specs=spec)(x)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss)).lower(x).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # the rotation and its VJP
    assert "collective-permute" not in text


@pytest.mark.parametrize("impl", ["ppermute", "rdma", "fused"])
def test_ring_variants_compile_on_mesh(v5e, monkeypatch, impl):
    """Every rotate_impl compiles fwd+bwd for four described chips at
    (2, 8, 8192, 64) bf16 — 2048 rows a chip — under shard_map's default
    check_vma=True (the fused ring's barrier-only closer used to fail the
    check), and the compiled text holds the rotation that was asked for,
    not a stand-in."""
    import re

    from jax.sharding import NamedSharding

    # ring_attention resolves interpret mode from the backend; steer that
    # query here rather than give the program an option for tests.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _sp_mesh(v5e)
    spec = P("dp", None, "sp", None)
    q = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    fn = functools.partial(ring_attention, axis_name="sp", causal=True,
                           rotate_impl=impl)

    def loss(q, k, v):
        out = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec)(q, k, v)
        return (out.astype(jnp.float32) ** 2).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.lower(q, q, q).compile().as_text()
    kernels = text.count("tpu_custom_call")
    permutes = text.count("collective-permute-start(")
    if impl == "ppermute":
        assert kernels == 0 and permutes > 0
    elif impl == "rdma":
        # K and V, three rotations each, forward and transposed.
        assert kernels == 12 and permutes == 0
    else:
        # Four step kernels and a barrier-only closer per pass; only the
        # float32 dk/dv partials still travel by collective-permute.  The
        # barrier namespaces alternate through both passes, closers
        # included (a dropped closer would leave 15,16,15,15,16,15).
        assert kernels == 10 and permutes == 8
        ids = re.findall(r'collective_id\W+(\d+)', text)
        assert ids == ["15", "16"] * 4, ids


_LM_STEPS = {}     # devices -> what _compile_lm_step gave for them


def _compile_lm_step(devices):
    """A two-layer dense LM at pythia-410m's widths (a smaller vocabulary,
    512 tokens a chip) through `build_train_step` on a data-parallel mesh
    of the described ``devices``: (the step, its compiled text, the number
    of weights whose gradient is over a megabyte in either dtype).  Compiled
    once a process for a number of devices."""
    if len(devices) not in _LM_STEPS:
        _LM_STEPS[len(devices)] = _compiled_lm_step(devices)
    return _LM_STEPS[len(devices)]


def _compiled_lm_step(devices):
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.jax.train import _EXCHANGE_OVERLAP, build_train_step
    from horovod_tpu.models import TransformerLM, next_token_loss
    from horovod_tpu.parallel import data_parallel_mesh

    model = TransformerLM(vocab_size=8192, d_model=1024, n_layers=2,
                          n_heads=16, d_ff=4096, dtype=jnp.bfloat16,
                          logits_dtype=jnp.bfloat16, use_flash=True)
    mesh = data_parallel_mesh(devices, axis_name="hvd")
    tx = optax.adamw(1e-4)

    def loss_fn(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    def init(key):
        params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
        return params, tx.init(params)

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params, opt_state = shaped(jax.eval_shape(init, jax.random.PRNGKey(0)),
                               P())
    tokens = shaped(jax.ShapeDtypeStruct((len(devices), 512), jnp.int32),
                    P("hvd"))
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")
    try:
        text = step.lower(params, opt_state,
                          (tokens, tokens)).compile().as_text()
    except Exception as exc:  # noqa: BLE001 - libtpu names the option
        pytest.fail("this libtpu refuses the step under the compiler options "
                    f"of jax/train.py _EXCHANGE_OVERLAP "
                    f"{sorted(_EXCHANGE_OVERLAP)}: {exc}")
    # Over 2**19 elements a gradient is over a megabyte in bf16 and in f32;
    # the model's other leaves (norm scales) are under it in both.
    sizes = [x.size for x in jax.tree.leaves(params)]
    assert all(n >= 2**19 or n * 4 < 2**20 for n in sizes), sizes
    return step, text, sum(n >= 2**19 for n in sizes)


def test_dp_step_exchanges_large_gradients_asynchronously(v5e, monkeypatch):
    """What `build_train_step` promises of the gradient exchange, asked of
    the chip's compiler.  Over four described chips every weight gradient
    over a megabyte is an `async-collective-start`/`-done` pair of its own,
    no all-reduce the core waits in has an operand that large (the small
    leaves and the loss still travel, together), and the text still holds
    an all-reduce for the benchmark's count; over one described chip the
    step takes no option and holds neither.  This is the test that fails
    when a libtpu upgrade renames, drops or re-reads one of the options."""
    import math

    from horovod_tpu.jax.train import _EXCHANGE_OVERLAP, count_all_reduces

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    options = (f"(jax/train.py _EXCHANGE_OVERLAP: {sorted(_EXCHANGE_OVERLAP)}"
               "; PERF.md section 6, PR 29)")

    step, text, large = _compile_lm_step(v5e[:4])
    assert step.exchange_overlap["compiler_options"] == "applied"
    n_async, n_sync = count_all_reduces(text)
    dones = len(re.findall(r"^\s*%async-collective-done[\w.\-]* = ", text,
                           re.M))
    assert n_async == dones == large, (
        f"{large} gradients over a megabyte, {n_async} asynchronous "
        f"all-reduces, {dones} dones: one of the options lost its meaning "
        f"{options}")
    assert n_sync >= 1 and re.search(r"\ball-reduce\(", text)
    waiting = [line.split(" all-reduce(")[0]
               for line in _instructions_outside_fusions(text)
               if " all-reduce(" in line]
    assert len(waiting) == n_sync
    width = {"bf16": 2, "f32": 4}
    for result in waiting:
        for dtype, dims in re.findall(r"\b(bf16|f32)\[([\d,]*)\]", result):
            nbytes = width[dtype] * math.prod(
                int(d) for d in dims.split(",") if d)
            assert nbytes < 2**20, (
                f"a synchronous all-reduce carries {nbytes} bytes: {result} "
                f"{options}")

    step, text, _ = _compile_lm_step(v5e[:1])
    assert step.exchange_overlap["compiler_options"] == "not applied"
    assert count_all_reduces(text) == (0, 0)
    assert "async-collective-start" not in text
    assert "all-reduce" not in text


def _assert_scopes_forward_and_backward(text, scopes):
    """Every scope of ``scopes`` is in the compiled text's op_names under
    `jvp(hvd_loss)` and under `transpose(jvp(hvd_loss))`."""
    for scope in scopes:
        assert re.search(rf'op_name="jit\([^"]*/jvp\(hvd_loss\)/[^"]*{scope}/',
                         text), f"{scope} is not in the forward pass"
        assert re.search(
            rf'op_name="jit\([^"]*transpose\(jvp\(hvd_loss\)\)/[^"]*{scope}/',
            text), f"{scope} is not in the backward pass"


def test_dense_step_names_its_layers(v5e, monkeypatch):
    """The dense LM's step compiled for one described chip: the embedding,
    the three parts of attention, the MLP, the head and the loss's own pass
    each keep a scope of their own in the compiled text's op_names, forward
    and backward (benchmark/layer_metrics/_layers.py sorts a device trace by
    them), and the flash kernels lie beneath `hvd_attn_attend`."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, text, _ = _compile_lm_step(v5e[:1])
    _assert_scopes_forward_and_backward(
        text, ("hvd_embed", "hvd_attn_qkv", "hvd_attn_attend", "hvd_attn_out",
               "hvd_mlp", "hvd_lm_head", "hvd_token_xent"))
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd"):
        assert re.search(rf'%{kernel}[.\d]* = .*op_name="[^"]*/hvd_attn_attend/'
                         rf'{kernel}/pallas_call"', text), kernel


def _instructions_outside_fusions(text):
    """The instruction lines of a compiled program's text that are not in a
    fused computation: what the core runs one after another."""
    fused = set(re.findall(r"\bfusion\(.*calls=%([\w.\-]+)", text))
    skipping = False
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if opened:
            skipping = opened.group(1) in fused
        elif not skipping and " = " in line:
            yield line


def _written_float32_elements(text):
    """Element counts of the float32 arrays that instructions OUTSIDE fused
    computations yield in a compiled program's text: what is written to
    memory, where a fusion's body holds values that never leave the core."""
    import math

    counts = []
    for line in _instructions_outside_fusions(text):
        yielded = line.split(" = ", 1)[1].split(", metadata=")[0]
        counts += [math.prod(map(int, dims.split(",")))
                   for dims in re.findall(r"\bf32\[([\d,]+)\]", yielded)]
    return counts


def test_lm_loss_keeps_no_float32_logits(v5e):
    """The gradient of a small TransformerLM under next_token_loss, bf16
    logits of 2,048 tokens x 8,192 classes, compiled for the described
    chip, writes no float32 array of the logits' size: the softmax is
    float32 inside fusions only (plain autodiff of a cross-entropy on
    ``logits.astype(float32)`` wrote that copy out for its backward)."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import TransformerLM, next_token_loss

    tokens, vocab = 2048, 8192
    model = TransformerLM(vocab_size=vocab, d_model=128, n_layers=1,
                          n_heads=2, d_ff=256, dtype=jnp.bfloat16,
                          logits_dtype=jnp.bfloat16, use_flash=False)
    shape = jax.ShapeDtypeStruct((1, tokens), jnp.int32)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], shape)
    on_chip = SingleDeviceSharding(v5e[0])
    params, inputs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_chip),
        (params, shape))

    def loss(params, inputs, targets):
        return next_token_loss(model.apply({"params": params}, inputs),
                               targets)

    text = jax.jit(jax.grad(loss)).lower(params, inputs,
                                         inputs).compile().as_text()
    written = _written_float32_elements(text)
    assert written, "the text's float32 arrays were not found"
    assert tokens * vocab not in written


def test_hybrid_step_is_products_and_kernels_with_no_loop(v5e, monkeypatch):
    """A Mamba-2, an attention (4 query heads on 1 key/value head of 128, no
    rotary) and a latent sparse-expert layer at Nemotron-3's per-head widths
    through `build_train_step`, compiled for the described chip: the chunked
    scan is products over chunks (no `while` anywhere in the step), attention
    is the two flash kernels, the experts are libtpu's grouped-matmul kernels
    (six and two tile schedules), and every scope of the layers is in the
    text forward and backward."""
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (Mamba2Config, MoEConfig, TransformerLM,
                                    next_token_loss)
    from horovod_tpu.parallel import data_parallel_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=4, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=True, norm_eps=1e-5,
        layers=("ssm", "experts", "attention"),
        ssm=Mamba2Config(16, 64, 1, 128, 4, 128), n_kv_heads=1, rope=False,
        moe=MoEConfig(64, 8, 512, (0, 8), 1.5, "sigmoid", True, 5.0, "relu2",
                      256, 1024))
    mesh = data_parallel_mesh(v5e[:1], axis_name="hvd")
    tx = optax.adamw(1e-4)

    def loss_fn(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    def init(key):
        params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
        return params, tx.init(params)

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params, opt_state = shaped(jax.eval_shape(init, jax.random.PRNGKey(0)),
                               P())
    tokens = shaped(jax.ShapeDtypeStruct((1, 2048), jnp.int32), P("hvd"))
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")
    text = step.lower(params, opt_state,
                      (tokens, tokens)).compile().as_text()
    assert not re.search(r"\bwhile\(", text)
    assert len(re.findall(r"%hvd_flash_fwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%hvd_flash_bwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 6
    # 16,384 pairs for a buffer of 3,072 rows: under the row walk's 8 pairs a
    # row (the cell's own 35 are over it) and past `HELD_PAIRS_PER_ROW`, so
    # the way back is the kernel's two calls.
    assert len(re.findall(r"%hvd_moe_pair_rows[.\d]* = ", text)) == 2
    assert text.count('"tpu_custom_call"') == 2 + 6 + 2 + 2
    _assert_scopes_forward_and_backward(
        text, ("hvd_ssm_in_proj", "hvd_ssm_conv", "hvd_ssm_scan",
               "hvd_ssm_gate_norm", "hvd_ssm_out_proj", "hvd_moe_latent",
               "hvd_moe_shared", "hvd_moe_router", "hvd_moe_dispatch",
               "hvd_moe_combine", "hvd_embed", "hvd_attn_qkv",
               "hvd_attn_attend", "hvd_attn_out", "hvd_lm_head"))


def test_ling_step_is_products_kernels_and_one_loop_a_pass(v5e, monkeypatch):
    """A Kimi-delta layer, a dense gated MLP, a latent-attention layer (4
    heads, 192 and 128 wide) and group-limited gated experts with a shared one
    at Ling-3.0-flash's per-head widths through `build_train_step`, compiled
    for the described chip: the delta rule's recurrence between chunks is the
    step's only loops (one `while` forward, one backward, each carrying the
    state alone through a handful of fusions), latent attention is
    the flash forward and the split backward pair at two widths, the experts
    are libtpu's grouped-matmul kernels (nine and two tile schedules), and
    every scope of the layers and every stage of the delta rule is in the text
    forward and backward."""
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (DeltaConfig, LatentConfig, MoEConfig,
                                    TransformerLM, next_token_loss)
    from horovod_tpu.parallel import data_parallel_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=4, d_ff=1024,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, use_flash=True,
        layers=("delta", "gated_mlp", "latent_attention", "experts"),
        delta=DeltaConfig(4, 128), latent=LatentConfig(512, 128, 64, 128, 6e6),
        moe=MoEConfig(64, 8, 256, (0, 8), 1.5, "sigmoid", True, 2.5,
                      shared_width=256, n_group=8, topk_group=4))
    mesh = data_parallel_mesh(v5e[:1], axis_name="hvd")
    tx = optax.adamw(1e-4)

    def loss_fn(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    def init(key):
        params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
        return params, tx.init(params)

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params, opt_state = shaped(jax.eval_shape(init, jax.random.PRNGKey(0)),
                               P())
    tokens = shaped(jax.ShapeDtypeStruct((1, 2048), jnp.int32), P("hvd"))
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")
    text = step.lower(params, opt_state,
                      (tokens, tokens)).compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 2
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 9
    # The experts' rows come back by the kernel, as in the hybrid step above.
    assert len(re.findall(r"%hvd_moe_pair_rows[.\d]* = ", text)) == 2
    assert text.count('"tpu_custom_call"') == 3 + 9 + 2 + 2
    _assert_scopes_forward_and_backward(
        text, ("hvd_kda_in_proj", "hvd_kda_conv", "hvd_kda_gate",
               "hvd_kda_scan", "hvd_kda_gate_norm", "hvd_kda_out_proj",
               "hvd_mla_q_proj", "hvd_mla_kv_latent", "hvd_mla_attend",
               "hvd_mla_out_proj", "hvd_moe_router", "hvd_moe_shared",
               "hvd_embed", "hvd_mlp", "hvd_lm_head",
               "hvd_kda_scan/hvd_kda_scan_decays",
               "hvd_kda_scan/hvd_kda_scan_chunk",
               "hvd_kda_scan/hvd_kda_scan_solve",
               "hvd_kda_scan/hvd_kda_scan_carry"))
    # The stages partition the scope: no operation, a cast either, lies under
    # `hvd_kda_scan` and under no stage (their shares must sum to its own).
    under = re.findall(r'op_name="([^"]*/hvd_kda_scan/[^"]*)"', text)
    assert len(under) > 100 and all(re.search(
        r"/hvd_kda_scan/hvd_kda_scan_(decays|chunk|solve|carry)/", path)
        for path in under), [p for p in under if "scan_" not in p][:3]
    # The two loops are the carry stage's, one a pass.
    loops = re.findall(r'^\s*%[\w.\-]+ = .* while\(.*op_name="([^"]*)"', text,
                       re.M)
    assert len(loops) == 2 and all(
        "/hvd_kda_scan/hvd_kda_scan_carry/" in path for path in loops), loops
    assert sum("transpose(jvp(hvd_loss))" in path for path in loops) == 1
    # Each loop carries the state alone: its body is the state's two products
    # and what stores them; an iteration costs a microsecond a fusion whatever
    # it computes, so nothing else belongs in it.
    bodies = re.findall(r"\bwhile\(.*body=%([\w.\-]+)", text)
    for body in bodies:
        start = text.index(f"\n%{body} ")
        fusions = text[start:text.index("\n}", start)].count(" fusion(")
        assert 2 <= fusions <= 5, (body, fusions)


@pytest.mark.parametrize("width", [1024, 1280, 2560])
def test_embedding_gradient_is_slabs_under_its_scope(v5e, width):
    """The gradient of a one-layer dense LM compiled for the described chip,
    2,048 tokens into a table of 1,536 rows.  Past `ops.moe.WHOLE_ROW_WIDTH`
    the table's cotangent is one scatter-add a slab of `ROW_SLAB_WIDTH`
    columns (the last narrower at 1,280) and none of the whole width; at
    pythia-410m's 1,024 it is one scatter-add of whole rows.  Every
    instruction that yields the table in the compute dtype, its cotangent or
    a slab of it carries an op_name under `hvd_embed`, forward and backward:
    `embed_time_share_pct` reads the slabs and their join, and
    `model_unscoped_pct` does not take them."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import TransformerLM, next_token_loss
    from horovod_tpu.ops.moe import ROW_SLAB_WIDTH, WHOLE_ROW_WIDTH

    vocab, tokens = 1536, 2048
    model = TransformerLM(vocab_size=vocab, d_model=width, n_layers=1,
                          n_heads=width // 128, d_ff=256, dtype=jnp.bfloat16,
                          logits_dtype=jnp.bfloat16, use_flash=False)
    shape = jax.ShapeDtypeStruct((1, tokens), jnp.int32)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], shape)
    on_chip = SingleDeviceSharding(v5e[0])
    params, inputs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_chip),
        (params, shape))

    def loss(params, inputs, targets):
        with jax.named_scope("hvd_loss"):
            return next_token_loss(model.apply({"params": params}, inputs),
                                   targets)

    text = jax.jit(jax.grad(loss)).lower(params, inputs,
                                         inputs).compile().as_text()
    slabs = [width] if width <= WHOLE_ROW_WIDTH else \
        [min(ROW_SLAB_WIDTH, width - at)
         for at in range(0, width, ROW_SLAB_WIDTH)]
    scattered = re.findall(rf"= bf16\[{vocab},(\d+)\]\S* scatter\(", text)
    assert sorted(map(int, scattered)) == sorted(slabs)
    _assert_scopes_forward_and_backward(text, ("hvd_embed",))
    table_shaped = re.compile(
        rf"= \(?bf16\[{vocab},(?:{'|'.join(map(str, {width, *slabs}))})\]")
    found = 0
    for line in text.splitlines():
        if table_shaped.search(line) and "op_name=" in line \
                and " parameter(" not in line:
            found += 1
            assert re.search(r'op_name="[^"]*/hvd_embed/', line), line
    assert found >= 2 * len(slabs)


@pytest.mark.parametrize("plan", ["combined", "split"])
def test_banded_flash_at_trinity_shape_compiles(v5e, monkeypatch, plan):
    """The trinitymini cell's windowed layers: 1 x 32 heads of 128 at 8,192
    rows under a window of 2,048.  The banded forward (1,024-blocks, a band of
    3 key blocks) and the combined backward the plan gives the shape
    ((512, 512), a band of 5), and the split pair at 1,024-blocks, compile for
    the described chip: index maps that divide and clamp, grids as long as the
    band."""
    import horovod_tpu.ops.attention as attn

    assert attn._bwd_plan(8192, 128, 1024, 1024, 32) == ("combined", 512, 512)
    if plan == "split":
        monkeypatch.setattr(attn, "_bwd_plan",
                            lambda q_len, d, bq, bk, bh=1: ("split", bq, bk))
    text = _compile_flash_grad(v5e[0], (1, 32, 8192, 128), window=2048)
    names = {"combined": ("hvd_flash_fwd_window", "hvd_flash_bwd_window"),
             "split": ("hvd_flash_fwd_window", "hvd_flash_bwd_dkdv_window",
                       "hvd_flash_bwd_dq_window")}[plan]
    # Outside a layer's scope the instruction is named after the whole path
    # (`%jvp_hvd_flash_fwd_window_.1`).
    for kernel in names:
        assert len(re.findall(rf"%\w*?_{kernel}_*\.\d+ = ", text)) == 1, kernel
    assert text.count('"tpu_custom_call"') == len(names)


def test_trinity_step_is_banded_and_causal_kernels_and_a_named_gate(
        v5e, monkeypatch):
    """A windowed layer, a dense gated MLP, a full layer and sigmoid-routed
    experts with a shared one at Trinity-Mini's per-head widths (heads of 128
    on 2 key/value heads, the gate, the per-head norms, the post-norms, the
    embedding multiplier) through `build_train_step`, compiled for the
    described chip: the windowed layer's kernels are the banded ones, the
    full layer's the causal ones, no loop, and the gate's scope is in the
    text forward and backward beside the other scopes of `Attention`."""
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import MoEConfig, TransformerLM, next_token_loss
    from horovod_tpu.parallel import data_parallel_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=8, d_ff=1024,
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16, use_flash=True,
        norm_eps=1e-5,
        layers=("window_attention", "gated_mlp", "attention", "experts"),
        n_kv_heads=2, rope=False, head_dim=128, window=512, head_norm=True,
        attn_gate=True, post_norm=True, embed_scale=512 ** 0.5,
        moe=MoEConfig(64, 8, 256, (0, 8), 1.5, "sigmoid", True, 2.826,
                      shared_width=256))
    mesh = data_parallel_mesh(v5e[:1], axis_name="hvd")
    tx = optax.adamw(1e-4)

    def loss_fn(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    def init(key):
        params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
        return params, tx.init(params)

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params, opt_state = shaped(jax.eval_shape(init, jax.random.PRNGKey(0)),
                               P())
    tokens = shaped(jax.ShapeDtypeStruct((1, 2048), jnp.int32), P("hvd"))
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")
    text = step.lower(params, opt_state,
                      (tokens, tokens)).compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 0
    for kernel in ("hvd_flash_fwd_window", "hvd_flash_bwd_window",
                   "hvd_flash_fwd", "hvd_flash_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 9
    _assert_scopes_forward_and_backward(
        text, ("hvd_attn_qkv", "hvd_attn_attend", "hvd_attn_gate",
               "hvd_attn_out", "hvd_mlp", "hvd_moe_router", "hvd_moe_shared",
               "hvd_embed", "hvd_lm_head"))


@pytest.mark.parametrize("window", [1024, None], ids=["band", "causal"])
def test_split_flash_at_mellum_shape_compiles(v5e, window):
    """The mellum2 cell's attention: 1 x 32 heads of 128 at 16,384 rows, the
    windowed layers under a window of 1,024.  The plan leaves the combined
    backward (its whole-sequence dq scratch) for the split pair in
    1,024-blocks, banded — a band two tiles wide, 31 tile pairs a head — and
    causal; forward and pair compile for the described chip."""
    import horovod_tpu.ops.attention as attn

    assert attn._bwd_plan(16384, 128, 1024, 1024, 32) == ("split", 1024, 1024)
    text = _compile_flash_grad(v5e[0], (1, 32, 16384, 128), window=window)
    suffix = "_window" if window else ""
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"):
        assert len(re.findall(rf"%\w*?_{kernel}{suffix}_*\.\d+ = ",
                              text)) == 1, kernel
    assert text.count('"tpu_custom_call"') == 3


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rows,inner,outer", [
    (49152, 2304, 896), (49152, 896, 2304), (12288, 2048, 768),
    (12288, 768, 2048)])
def test_grouped_matmul_at_mellum_and_sdar_widths_compiles(
        v5e, monkeypatch, rows, inner, outer, dtype):
    """Experts 896 = 7 x 128 wide on rows 2,304 = 9 x 256 wide, 49,152 buffer
    rows over 16 experts, and SDAR's 768 on 2,048 over 12,288, both ways
    through an expert: widths libtpu's grouped kernels take in their smallest
    tiles, so `grouped_matmul` runs the tiled kernels of `ops/moe.py` —
    forward and both gradients compile for the described chip in the tiles
    `_row_tiles` and `_weight_tiles` choose under the scoped-VMEM budget, in
    bf16 and (windows twice as large) in float32."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.moe import grouped_matmul

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = SingleDeviceSharding(v5e[0])
    buffer = jax.ShapeDtypeStruct((rows, inner), dtype, sharding=on_chip)
    weights = jax.ShapeDtypeStruct((16, inner, outer), dtype,
                                   sharding=on_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=on_chip)

    def loss(rows, weights, sizes):
        return grouped_matmul(rows, weights, sizes).astype(
            jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        buffer, weights, sizes).compile().as_text()
    for form in ("fwd", "drows", "dweights"):
        assert len(re.findall(rf"%\w*hvd_grouped_{form}[.\d]* = ",
                              text)) == 1, form
    assert "ragged-dot" not in text and " while(" not in text


@pytest.mark.parametrize("tokens,rows,width,form", [
    (16384, 49152, 2304, "held_pairs"), (8192, 24576, 2048, "pairs")],
    ids=["mellum", "olmoe"])
def test_the_way_back_at_mellum_and_olmoe_shapes_compiles(
        v5e, monkeypatch, tokens, rows, width, form):
    """The rows' two movements and their gradients at a chip's quarter share
    of 64 experts, 8 a token, for the described chip.  Mellum's 226 MB buffer
    is past `HELD_PAIRS_BUFFER_BYTES`: the combine's forward and the
    dispatch's backward are one `hvd_moe_pair_rows` call each (its blocks'
    landing place and the sum fit the VMEM the call asks for) and no array
    of every pair's row is left in the program; OLMoE's 101 MB keeps the
    k-wide gathers and no kernel."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.moe import (buffer_rows_to_tokens, dispatch_rows,
                                     token_rows_to_buffer, way_back)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = SingleDeviceSharding(v5e[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    def loss(flat, weight, expert, mix):
        sent = dispatch_rows(expert, 0, 16, rows)
        assert way_back(sent, width, 2) == form
        mixed = buffer_rows_to_tokens(token_rows_to_buffer(flat, sent),
                                      weight, sent)
        return (mixed * mix).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        shaped((tokens, width), jnp.bfloat16), shaped((tokens, 8),
                                                      jnp.float32),
        shaped((tokens, 8), jnp.int32),
        shaped((tokens, width), jnp.bfloat16)).compile().as_text()
    kernels = len(re.findall(r"%\w*hvd_moe_pair_rows[.\d]* = ", text))
    every_pairs_row = f"bf16[{tokens},8,{width}]" in text
    assert (kernels, every_pairs_row) == ((2, False) if form == "held_pairs"
                                          else (0, True))
    assert " while(" not in text and "scatter" not in text


@pytest.mark.parametrize("d_model,experts,grouped", [
    (512, 64, {"ragged-dot-none": 18}),
    (384, 32, {"hvd_grouped_fwd": 6, "hvd_grouped_drows": 6,
               "hvd_grouped_dweights": 6})], ids=["ragged_dot", "tiled"])
def test_mellum_step_recomputes_its_layers_under_jaxs_marker(
        v5e, monkeypatch, d_model, experts, grouped):
    """A windowed layer at the plain rotary frequencies, a full one at
    YaRN's, each followed by softmax-routed experts, every pattern entry
    recomputed (`TransformerLM(recompute=True)`), through `build_train_step`
    for the described chip: every flash kernel is in the step once a layer,
    as without recomputation, and so is every grouped matmul, nine a layer
    (a recomputing layer keeps the forward kernel's outputs, the grouped
    products' and its router's decision) — libtpu's `ragged_dot` kernels at
    512 wide on 384 rows a group, the tiled kernels of `ops/moe.py` at 384
    wide on 768, three of each form a layer — no loop; what is computed
    again — the projections, the rotation of either kind, the rows'
    movement — carries `rematted_computation` inside the backward phase and
    keeps its layer's scope, and nothing of the first forward pass does."""
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (MoEConfig, RopeScaling, TransformerLM,
                                    next_token_loss)
    from horovod_tpu.parallel import data_parallel_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=d_model, n_heads=8, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=True, norm_eps=1e-6,
        layers=("window_attention", "experts", "attention", "experts"),
        n_kv_heads=2, head_dim=128, window=512, head_norm=True,
        rope_theta=500000.0, rope_scaling=RopeScaling(16, 8192),
        window_rope=(500000.0, None), recompute=True,
        moe=MoEConfig(experts, 8, 256, (0, 4), 1.5, renormalize=True))
    mesh = data_parallel_mesh(v5e[:1], axis_name="hvd")
    tx = optax.adamw(1e-4)

    def loss_fn(params, batch):
        return next_token_loss(model.apply({"params": params}, batch[0]),
                               batch[1])

    def init(key):
        params = model.init(key, jnp.zeros((1, 128), jnp.int32))["params"]
        return params, tx.init(params)

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params, opt_state = shaped(jax.eval_shape(init, jax.random.PRNGKey(0)),
                               P())
    tokens = shaped(jax.ShapeDtypeStruct((1, 2048), jnp.int32), P("hvd"))
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd")
    text = step.lower(params, opt_state,
                      (tokens, tokens)).compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 0
    for kernel in ("hvd_flash_fwd_window", "hvd_flash_fwd",
                   "hvd_flash_bwd_window", "hvd_flash_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 1, kernel
    found = {name: len(re.findall(rf"%\w*{name}[.\d]* = ", text))
             for name in ("ragged-dot-none", "hvd_grouped_fwd",
                          "hvd_grouped_drows", "hvd_grouped_dweights")}
    assert {name: n for name, n in found.items() if n} == grouped
    paths = re.findall(r'op_name="([^"]*)"', text)
    again = [path for path in paths if "rematted_computation" in path]
    assert again and all("transpose(jvp(hvd_loss))" in path
                         for path in again)
    for layer in (0, 2):
        assert any(f"layer_{layer}" in path and "hvd_attn_rotate" in path
                   for path in again)
        assert any(f"layer_{layer}" in path and "hvd_attn_qkv" in path
                   for path in again)
    assert not any("hvd_flash" in path or "hvd_grouped" in path
                   for path in again)
    assert any("hvd_moe_dispatch" in path for path in again)
    assert not any("hvd_lm_head" in path or "hvd_embed" in path
                   for path in again)
    _assert_scopes_forward_and_backward(
        text, ("hvd_attn_qkv", "hvd_attn_rotate", "hvd_attn_attend",
               "hvd_attn_out", "hvd_moe_router", "hvd_embed", "hvd_lm_head"))


@pytest.mark.parametrize("heads,kv_heads,head_dim",
                         [(32, 4, 128), (16, 2, 256)],
                         ids=["trinity", "qwen3next"])
def test_gated_attention_writes_no_float32_array_of_the_kernels_output(
        v5e, monkeypatch, heads, kv_heads, head_dim):
    """`Attention(gate=True)` at the two cells' heads over 2,048 rows, forward
    and backward, compiled for the described chip: no instruction under
    `hvd_attn_gate` writes a float32 array of the kernels' output's size (the
    float32 gate plain autodiff kept, 134 MB a layer in the Trinity cell);
    the gate's product writes the gated output and the rounded gate from one
    fusion, and the backward's two products are there under the scope."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models.transformer import Attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq = 2048
    layer = Attention(heads, jnp.bfloat16, use_flash=True, n_kv_heads=kv_heads,
                      head_dim=head_dim, head_norm=True, gate=True)
    on_chip = SingleDeviceSharding(v5e[0])

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=on_chip), tree)

    # A hidden width that is not the rows: the weight's float32 gradient
    # then has a size of its own.
    x = jax.ShapeDtypeStruct((1, seq, 1024), jnp.bfloat16, sharding=on_chip)
    params = shaped(jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros(x.shape, x.dtype))["params"]))

    def loss(params, x):
        with jax.named_scope("hvd_loss"):
            return layer.apply({"params": params}, x).astype(
                jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    _assert_scopes_forward_and_backward(text, ("hvd_attn_gate",))
    entry = text[text.index("ENTRY "):]
    under = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\(.*"
                       r"op_name=\"([^\"]*/hvd_attn_gate/[^\"]*)\"", entry,
                       re.M)
    for result, op_name in under:
        for dims in re.findall(r"f32\[([\d,]+)\]", result):
            assert np.prod([int(n) for n in dims.split(",")]) \
                != heads * seq * head_dim, (result, op_name)
    kernels_output = (rf"bf16\[(?:1,)?{heads},"
                      rf"(?:{seq},{head_dim}|{head_dim},{seq})\]")
    assert [result for result, op_name in under
            if "/jvp(hvd_loss)/" in op_name
            and len(re.findall(kernels_output, result)) == 2], under
    for product in ("bhse,dhe->bsd", "bsd,bhse->dhe"):
        assert any(f"hvd_attn_gate/{product}/dot_general" in op_name
                   and "transpose(" in op_name
                   for _, op_name in under), product


@pytest.mark.parametrize("plan", ["combined", "split"])
@pytest.mark.parametrize("block", [4, 32, 96])
def test_blockdiff_flash_at_sdar_shape_compiles(v5e, monkeypatch, plan,
                                                block):
    """The sdar30ba3b cell's attention: 1 x 32 heads of 128 over the two
    copies of 4,096 rows under the block mask.  The forward (1,024-tiles, a
    walk of 5 key tiles: four clean, the tile's own noised one), the combined
    backward the plan gives the 8,192 rows ((512, 512), a walk of 16) and the
    split pair at 1,024-tiles compile for the described chip at the cell's
    block length, at 32, and at a length that is no power of two: index maps
    that walk two runs, masks from block ids on a column and a row."""
    import horovod_tpu.ops.attention as attn
    from jax.sharding import SingleDeviceSharding

    assert attn._bwd_plan(8192, 128, 1024, 1024, 32) == ("combined", 512, 512)
    if plan == "split":
        monkeypatch.setattr(attn, "_bwd_plan",
                            lambda q_len, d, bq, bk, bh=1: ("split", bq, bk))
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))

    def loss(q, k, v):
        return flash_attention(q, k, v, block_diffusion=block,
                               interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    names = {"combined": ("hvd_flash_fwd_blockdiff",
                          "hvd_flash_bwd_blockdiff"),
             "split": ("hvd_flash_fwd_blockdiff",
                       "hvd_flash_bwd_dkdv_blockdiff",
                       "hvd_flash_bwd_dq_blockdiff")}[plan]
    for kernel in names:
        assert len(re.findall(rf"%\w*?_{kernel}_*\.\d+ = ", text)) == 1, kernel
    assert text.count('"tpu_custom_call"') == len(names)


def test_sdar_step_is_blockdiff_kernels_and_a_named_loss(v5e, monkeypatch):
    """Two published layers of SDAR's pattern (block-diffusion attention at
    heads of 128 on 2 key/value heads with the per-head norms, softmax-routed
    experts with renormalised weights) through `build_train_step` with
    `masked_diffusion_loss`, compiled for the described chip: every attention
    kernel is a block-diffusion one, no causal kernel and no loop is in the
    text, the head's product has the noised half's rows alone, and the loss's
    scope is in the text forward and backward beside the attention's."""
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.jax.train import build_train_step
    from horovod_tpu.models import (MoEConfig, TransformerLM,
                                    masked_diffusion_loss)
    from horovod_tpu.parallel import data_parallel_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = TransformerLM(
        vocab_size=2048, d_model=512, n_heads=8, dtype=jnp.bfloat16,
        logits_dtype=jnp.bfloat16, use_flash=True,
        layers=("blockdiff_attention", "experts") * 2, n_kv_heads=2,
        head_dim=128, head_norm=True, block_diffusion=4, rope_theta=1e6,
        moe=MoEConfig(64, 8, 256, (0, 8), 1.5, renormalize=True))
    mesh = data_parallel_mesh(v5e[:1], axis_name="hvd")
    tx = optax.adamw(1e-4)

    def loss_fn(params, batch):
        tokens, noised, masked, level = batch
        return masked_diffusion_loss(
            model.apply({"params": params}, tokens, noised=noised), tokens,
            masked, level)

    def init(key):
        blank = jnp.zeros((1, 128), jnp.int32)
        params = model.init(key, blank, noised=blank)["params"]
        return params, tx.init(params)

    def shaped(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params, opt_state = shaped(jax.eval_shape(init, jax.random.PRNGKey(0)),
                               P())
    rows = 1024
    tokens, masked, level = (shaped(jax.ShapeDtypeStruct((1, rows), dtype),
                                    P("hvd"))
                             for dtype in (jnp.int32, jnp.bool_, jnp.float32))
    step = build_train_step(loss_fn, tx, mesh, axis_name="hvd",
                            batch_spec=(P("hvd"),) * 4)
    text = step.lower(params, opt_state,
                      (tokens, tokens, masked, level)).compile().as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 0
    for kernel in ("hvd_flash_fwd_blockdiff", "hvd_flash_bwd_blockdiff"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 2, kernel
    assert not re.search(r"%hvd_flash_(fwd|bwd)[.\d]* = ", text)
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 18
    # The head over the noised half: logits of 1,024 rows, not 2,048.
    assert re.search(r"bf16\[(1,)?1024,2048\]", text)
    assert not re.search(r"bf16\[(1,)?2048,2048\]", text)
    _assert_scopes_forward_and_backward(
        text, ("hvd_attn_qkv", "hvd_attn_attend", "hvd_attn_out",
               "hvd_moe_router", "hvd_embed", "hvd_lm_head",
               "hvd_diffusion_loss"))


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


def _pallas_call_names(jaxpr):
    """The `name` of every pallas_call equation, sub-programs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_call_names(sub)
    return names


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
