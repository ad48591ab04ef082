"""The chip's compiler, without the chip — the kernels' own compiles.

libtpu compiles for a DESCRIBED v5e 2x2 host (jax.experimental.topologies;
the `v5e` fixture of tests/conftest.py), which refuses what the chip would
refuse — scoped-VMEM overruns, tiling violations, a kernel that cannot be
partitioned — and interpret mode cannot.  Nothing runs, so these say nothing
about results or times.  Code that asks jax.default_backend() still sees the
CPU, so the kernels get interpret=False explicitly (or the test steers the
backend query).  Here: the flash kernels, the delta rule's carry, the chunked
scan's within-chunk pair, the grouped products, the rows' way back and the ring's rotations at the benchmark's
shapes; tests/test_chip_steps.py reads whole steps.  (tests/test_ops.py keeps
the interpret-mode numerics.)"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops import flash_attention, ring_attention


def _compile_flash_grad(device, shape, **kwargs):
    from jax.sharding import SingleDeviceSharding

    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(device))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               **kwargs).astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return grad.lower(q, q, q).compile().as_text()



def _assert_forward_and_combined_backward(text):
    """Two custom calls, each once by name: the forward and the combined
    backward, and nothing of the split pair."""
    assert text.count('"tpu_custom_call"') == 2
    for name in ("hvd_flash_fwd", "hvd_flash_bwd"):
        assert len(re.findall(rf"%\w*?_{name}_*\.\d+ = ", text)) == 1, name
    assert "hvd_flash_bwd_dkdv" not in text and "hvd_flash_bwd_dq" not in text


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


def _compile_delta_rule_grad(device, key_heads, value_heads, d_k, d_v,
                             seq=512):
    """The compiled text of the head form's rule and its five gradients at
    these heads and widths, bfloat16, chunks of 64, for the described chip."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.delta_rule import chunked_delta_rule

    on_chip = SingleDeviceSharding(device)
    q = jax.ShapeDtypeStruct((1, seq, key_heads, d_k), jnp.bfloat16,
                             sharding=on_chip)
    v = jax.ShapeDtypeStruct((1, seq, value_heads, d_v), jnp.bfloat16,
                             sharding=on_chip)
    gate = jax.ShapeDtypeStruct((1, seq, value_heads), jnp.float32,
                                sharding=on_chip)

    def loss(*operands):
        return chunked_delta_rule(*operands, 64, scope="hvd_gdn_scan")[0].sum()

    return jax.jit(jax.grad(loss, range(5))).lower(
        q, q, v, gate, gate).compile().as_text()


@pytest.mark.parametrize("key_heads,value_heads,d_k,d_v", [
    (2, 4, 128, 128), (15, 15, 96, 192)], ids=["qwen3next", "olmohybrid"])
def test_delta_rule_carry_kernels_at_the_cells_widths_compile(
        v5e, monkeypatch, key_heads, value_heads, d_k, d_v):
    """The head form's solve and recurrence as the two cells with Gated
    DeltaNet run them
    — Qwen3-Next's heads of 128 channels, two value heads a key head; Olmo-
    Hybrid's 15 heads with keys of 96 and values of 192, three quarters of a
    lane tile and one and a half, blocks at the TRUE widths with nothing
    padded by us (Mosaic lays a 96-wide block out in 128 lanes) — through the
    chip's compiler, forward and backward: four Mosaic kernels (the solve's
    pair, whose lane gather, transposes and products of split terms the
    interpreter cannot refuse, and the carry's) and no loop, as
    `lowered_plan` says (the rule asks the backend which way to run its
    kernels; here it is compiling for the described chip)."""
    from horovod_tpu.ops.delta_rule import lowered_plan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compile_delta_rule_grad(v5e[0], key_heads, value_heads, d_k, d_v)
    plan = lowered_plan(512, 64)
    assert text.count("custom_call_target=\"tpu_custom_call\"") \
        == plan["tpu_custom_call"] == 4
    assert text.count(" while(") == plan["while"] == 0
    for kernel in ("hvd_gdn_scan_carry_fwd", "hvd_gdn_scan_carry_bwd",
                   "hvd_gdn_scan_solve_fwd", "hvd_gdn_scan_solve_bwd"):
        assert f"%{kernel}" in text, kernel


@pytest.mark.parametrize("heads,head_dim,chunk,seq,kernels", [
    (64, 64, 256, 8192, True), (16, 64, 128, 4096, False),
    (64, 16, 128, 4096, True)],
    ids=["granite", "nemotron_share", "a_chunk_of_one_register"])
def test_the_chunked_scan_at_the_cells_shapes(
        v5e, monkeypatch, heads, head_dim, chunk, seq, kernels):
    """`chunked_scan` with its gradient at the two shapes the benchmark runs,
    heads of 64 on ONE group at a state of 128, bfloat16, through the chip's
    compiler: Granite's 64 heads at a chunk of 256 take the pair of Mosaic
    kernels (whose dynamic slices of a block's sublanes, lane selects and
    transposed products the interpreter cannot refuse), Nemotron's share of
    16 at 128 stays products — as `lowered_plan` says, and no loop either
    way; and 64 narrow heads at a chunk of ONE register's lanes, which the
    rule also hands the kernels (a chunk's last token is then a register's
    last lane: a (1, 1) decay scales a state as a scalar)."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.ssm import chunked_scan, lowered_plan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = SingleDeviceSharding(v5e[0])

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    x = shaped((1, seq, heads, head_dim), jnp.bfloat16)
    b = shaped((1, seq, 1, 128), jnp.bfloat16)
    vector = shaped((heads,))

    def loss(x, dt, A, B, C, D):
        return jnp.square(chunked_scan(x, dt, A, B, C, D, chunk)[0]).sum()

    text = jax.jit(jax.grad(loss, range(6))).lower(
        x, shaped((1, seq, heads)), vector, b, b, vector).compile().as_text()
    plan = lowered_plan(heads, 1, head_dim, 128, chunk, jnp.bfloat16)
    assert plan["scan"] == ("kernels" if kernels else "products")
    assert text.count("custom_call_target=\"tpu_custom_call\"") \
        == plan["tpu_custom_call"] == (2 if kernels else 0)
    assert text.count(" while(") == 0
    for kernel in ("hvd_ssm_scan_intra_fwd", "hvd_ssm_scan_intra_bwd"):
        assert (f"%{kernel}" in text) == kernels, kernel


@pytest.mark.parametrize("heads", [4, 32], ids=["ling", "joyai"])
def test_flash_two_widths_at_latent_attention_shape_compile(v5e, heads):
    """Latent attention as the Ling-3.0-flash cell runs it (4 heads) and as the
    JoyAI-LLM-Flash cell does (32) — 8,192 tokens, query and key 192 wide,
    value 128 — forward and backward through the chip's compiler with nothing
    padded: the plan's wide-head band (PR 67) sends it to the combined kernel
    in (1024, 1024) blocks, whose call names the `vmem_limit_bytes` its
    whole-sequence dq at 256 lanes needs, and the gradients keep their
    operands' widths (PR 32)."""
    from jax.sharding import SingleDeviceSharding

    import horovod_tpu.ops.attention as attn

    assert attn._bwd_plan(8192, 192, 1024, 1024, heads, 128) \
        == ("combined", 1024, 1024)
    limit = attn._combined_vmem_limit(8192, 192, 1024, 1024, 128)
    assert 16 << 20 < limit == 53084160 <= attn._MAX_VMEM_LIMIT
    # One width, as every call before PR 32: the same plan with and without.
    assert attn._bwd_plan(8192, 64, 1024, 1024, 16, 64) \
        == attn._bwd_plan(8192, 64, 1024, 1024, 16) == ("combined", 512, 512)
    on_chip = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, heads, 8192, 192), jnp.bfloat16,
                             sharding=on_chip)
    v = jax.ShapeDtypeStruct((1, heads, 8192, 128), jnp.bfloat16,
                             sharding=on_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile()
    text = compiled.as_text()
    _assert_forward_and_combined_backward(text)
    # the limit the call names, as the compiled custom call carries it
    assert f'"memory_space":"1","offset":"0","size":"{limit}"' in text
    assert [g.shape[-1] for g in compiled.out_info] == [192, 192, 128]


def test_flash_head256_at_qwen3next_shape_compiles(v5e):
    """Gated attention as the Qwen3-Next cell runs it — 1 x 16 heads of 256 at
    4,096 rows (a key/value head repeated for its 8 query heads before the
    kernels) — in the band `_bwd_plan` sends it to since PR 67: the combined
    backward in (512, 512) blocks asking for the scoped VMEM two tiles of
    lanes need, which with the forward compiles for the described chip
    (PR 46: the first cell past a head of 128, then on the split pair)."""
    import horovod_tpu.ops.attention as attn

    assert attn._bwd_plan(4096, 256, 1024, 1024, 16) == ("combined", 512, 512)
    assert attn._combined_vmem_limit(4096, 256, 512, 512) > 16 << 20
    text = _compile_flash_grad(v5e[0], (1, 16, 4096, 256))
    _assert_forward_and_combined_backward(text)


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



@pytest.mark.parametrize("window", [1024, None], ids=["band", "causal"])
@pytest.mark.parametrize("plan", ["combined", "split"])
def test_flash_at_mellum_shape_compiles(v5e, monkeypatch, plan, window):
    """The mellum2 cell's attention: 1 x 32 heads of 128 at 16,384 rows, the
    windowed layers under a window of 1,024.  The combined backward the plan
    gives the shape — (512, 512) blocks, a band three tiles wide, its
    whole-sequence dq scratch past the 16 MiB a kernel has without asking, so
    the call names its `vmem_limit_bytes` — and the split pair in
    1,024-blocks (a band two tiles wide, 31 tile pairs a head), which was the
    plan until PR 57 and is past 16,384 rows, compile for the described chip
    beside the forward, banded and causal, each call once by name."""
    import horovod_tpu.ops.attention as attn

    assert attn._bwd_plan(16384, 128, 1024, 1024, 32) == ("combined", 512, 512)
    assert attn._combined_vmem_limit(16384, 128, 512, 512) > 16 << 20
    if plan == "split":
        monkeypatch.setattr(attn, "_bwd_plan",
                            lambda q_len, d, bq, bk, bh=1: ("split", bq, bk))
    text = _compile_flash_grad(v5e[0], (1, 32, 16384, 128), window=window)
    suffix = "_window" if window else ""
    names = {"combined": ("hvd_flash_fwd", "hvd_flash_bwd"),
             "split": ("hvd_flash_fwd", "hvd_flash_bwd_dkdv",
                       "hvd_flash_bwd_dq")}[plan]
    for kernel in names:
        assert len(re.findall(rf"%\w*?_{kernel}{suffix}_*\.\d+ = ",
                              text)) == 1, kernel
    assert text.count('"tpu_custom_call"') == len(names)


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
