"""The flash kernels' schedule (`ops.attention._tile_table`): a host-built
table of the live (query tile, key tile) pairs is each kernel's grid — against
the masks evaluated element by element, at the benchmark's cells' shapes; the
counter that holds `grid_steps == grid_live`; the kernels' values against
`mha_reference` under the three masks, combined and split, and bit for bit
against what the rectangular grids of commit 585dfaa gave; the bodies a
kernel holds (one under the causal mask and a window, block diffusion's two);
and a kind of mask that `ops/attention.py` has never heard of, through the
same kernels."""

import dataclasses

import functools
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu.ops.attention as attn
from horovod_tpu.ops import flash_attention, mha_reference
from horovod_tpu.ops.attention import Mask, flash_grid_steps, mask_blocks


def seen_pairs(seq, mask, *operands):
    """``mask`` (a `Mask`, or `flash_attention`'s keywords for one) as a
    (seq, seq) boolean matrix, position by position: its `seen`, on its
    ``operands`` where the kind has some (one batch row's)."""
    if isinstance(mask, dict):
        mask = Mask.of(seq, seq, **mask)
    return np.broadcast_to(np.asarray(mask.seen(
        np.arange(seq)[:, None], np.arange(seq)[None, :], *operands)),
        (seq, seq))


def equations(jaxpr, primitive):
    """Every equation of ``primitive`` in ``jaxpr``, sub-programs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub, primitive)


def pallas_calls(jaxpr):
    """{kernel name: grid} of every pallas_call, sub-programs included."""
    return {eqn.params["name"]: tuple(eqn.params["grid_mapping"].grid)
            for eqn in equations(jaxpr, "pallas_call")}


def check_fed_tables(mask, seq, block_q, block_k, *operands):
    """`check_tables` for a kind with operands (one batch row's, as `seen`
    takes them; ``tile_of(operand, i, j)`` below cuts `cut`'s slice as the
    kind's `specs` do): `live` covers every tile that holds a seen pair
    whatever the data; a tile the static part alone decides (`tiles`'
    second matrix, flagged in the table) is seen as ``flagged`` sees it; in
    every live tile `cut` — with the operands' tiles, or ``flagged``'s
    without — leaves the logits exactly where `seen` is true; and the table
    flags what `tiles` says.  Returns the live pairs."""
    seen = seen_pairs(seq, mask, *operands)
    num_q, num_k = seq // block_q, seq // block_k
    tiles = seen.reshape(num_q, block_q, num_k, block_k)
    live, flagged = mask.tiles(num_q, num_k, block_q, block_k)
    assert not (tiles.any((1, 3)) & ~live).any() and not (flagged & ~live).any()
    plain = seen_pairs(seq, mask.flagged).reshape(tiles.shape)
    zeros = jnp.zeros((block_q, block_k), jnp.float32)
    for i, j in zip(*np.nonzero(live)):
        if flagged[i, j]:
            assert (tiles[i, :, j] == plain[i, :, j]).all(), (i, j)
            cut = mask.flagged.cut(zeros, jnp.int32(i * block_q),
                                   jnp.int32(j * block_k), block_q, block_k)
        else:
            cut = mask.cut(
                zeros, jnp.int32(i * block_q), jnp.int32(j * block_k),
                block_q, block_k, *(
                    np.asarray(o)[
                        i * block_q:(i + 1) * block_q if o.shape[0] > 1
                        else slice(None),
                        j * block_k:(j + 1) * block_k if o.shape[1] > 1
                        else slice(None)] for o in operands))
        assert ((np.asarray(cut) == 0) == tiles[i, :, j]).all(), (i, j)
    want = set(zip(*np.nonzero(live)))
    for by_key in (False, True):
        q_tile, k_tile, flags = attn._tile_table(
            num_q, num_k, block_q, block_k, mask, by_key=by_key)
        assert set(zip(q_tile.tolist(), k_tile.tolist())) == want
        assert (((flags & attn._WHOLE) != 0) == flagged[q_tile, k_tile]).all()
    return want


def check_tables(mask, seq, block_q, block_k):
    """The forms of ``mask`` against its `seen` over ``seq`` rows: `tiles` is
    `live` where a tile holds a seen pair and `whole` where it holds no
    unseen one, and `cut` leaves a live tile's logits where `seen` is true
    and nowhere else (in each row of tiles the first live tile that is whole
    and the first that is not: a kernel asks `cut` of no dead tile).  Then
    both walks of the table
    (queries outer, keys outer): every live tile is a step exactly once and
    no other tile is; a row's steps are contiguous, its tiles ascend, and
    `first` and `last` are set once a row, on its first and last step;
    `whole` is flagged for a mask whose kernels hold a second body
    (`whole_body`: block diffusion's) and for no other.  Returns the live
    pairs."""
    seen = seen_pairs(seq, mask)
    num_q, num_k = seq // block_q, seq // block_k
    tiles = seen.reshape(num_q, block_q, num_k, block_k)
    live, whole = tiles.any((1, 3)), tiles.all((1, 3))
    got_live, got_whole = mask.tiles(num_q, num_k, block_q, block_k)
    assert (got_live == live).all() and (got_whole == whole).all()
    zeros = jnp.zeros((block_q, block_k), jnp.float32)
    for i in range(num_q):
        firsts = {}
        for j in np.flatnonzero(live[i]):
            firsts.setdefault(whole[i, j], j)
        for j in firsts.values():
            cut = mask.cut(zeros, jnp.int32(i * block_q),
                           jnp.int32(j * block_k), block_q, block_k)
            assert ((np.asarray(cut) == 0) == tiles[i, :, j]).all(), (i, j)
    want = set(zip(*np.nonzero(live)))
    for by_key in (False, True):
        q_tile, k_tile, flags = attn._tile_table(
            num_q, num_k, block_q, block_k, mask, by_key=by_key)
        pairs = list(zip(q_tile.tolist(), k_tile.tolist()))
        assert len(pairs) == len(set(pairs)) and set(pairs) == want
        outer, inner = (k_tile, q_tile) if by_key else (q_tile, k_tile)
        starts = np.flatnonzero(np.r_[True, np.diff(outer) != 0])
        # contiguous rows, each outer tile one row, in ascending order
        assert (outer[starts] == np.arange(len(starts))).all()
        assert len(starts) == (num_k if by_key else num_q)
        same_row = np.diff(outer) == 0
        assert (np.diff(inner)[same_row] > 0).all()
        first = (flags & attn._FIRST) != 0
        last = (flags & attn._LAST) != 0
        assert (np.flatnonzero(first) == starts).all()
        assert (np.flatnonzero(last)
                == np.r_[starts[1:] - 1, len(outer) - 1]).all()
        assert (((flags & attn._WHOLE) != 0)
                == (whole[q_tile, k_tile] if mask.whole_body
                    else False)).all()
        assert flags.max() < 8
    return want


# The benchmark's cells: (rows, mask, head width, batch * heads).  Causal at
# 2,048 (`_4x2k`, dp4), 4,096 (OLMoE, Nemotron) and 8,192 (`_1x8k`, Trinity's
# full layer, Ling's two widths); Trinity's window of 2,048 at 8,192; SDAR's
# blocks of 4 over two copies of 4,096.
CELLS = [
    (2048, dict(causal=True), 64, None, 64),
    (4096, dict(causal=True), 128, None, 32),
    (8192, dict(causal=True), 64, None, 16),
    (8192, dict(causal=True), 128, None, 32),
    (8192, dict(causal=True), 192, 128, 4),
    (8192, dict(causal=True, window=2048), 128, None, 32),
    (8192, dict(block_diffusion=4), 128, None, 32),
]


@pytest.mark.parametrize("seq,mask,d,d_v,bh", CELLS, ids=str)
def test_the_tables_at_the_cells_shapes(seq, mask, d, d_v, bh):
    """The forward's table at the blocks it takes and the backward's at the
    plan's: exactly the mask's tiles, and the counter's `live` is what
    `mask_blocks` and the causal count give."""
    kind = Mask.of(seq, seq, **mask)
    mode, plan_q, plan_k = attn._bwd_plan(seq, d, 1024, 1024, bh,
                                          **({"d_v": d_v} if d_v else {}))
    forward = len(check_tables(kind, seq, 1024, 1024))
    backward = len(check_tables(kind, seq, plan_q, plan_k))
    grids = flash_grid_steps(seq, d, bh, d_v, **mask)
    suffix = kind.suffix
    names = {"combined": ["hvd_flash_bwd"],
             "split": ["hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"]}[mode]
    rectangle = (seq // plan_q) * (seq // plan_k)
    assert grids == {
        "hvd_flash_fwd" + suffix: (forward, forward, (seq // 1024) ** 2),
        **{name + suffix: (backward, backward, rectangle) for name in names}}
    assert mask_blocks(seq, d, **mask)[0] == forward
    if mask == dict(causal=True):
        assert forward == sum(i + 1 for i in range(seq // 1024))
        assert mask_blocks(seq, d, causal=True, window=seq) \
            == (forward, forward)


MASKS = [dict(causal=True), dict(causal=True, window=200),
         dict(block_diffusion=32), dict()]


@pytest.mark.parametrize("plan", ["combined", "split"])
@pytest.mark.parametrize("mask", MASKS, ids=str)
def test_no_grid_step_computes_nothing(monkeypatch, mask, plan):
    """The grids of the traced program, forward and backward: `(bh, the
    mask's live tiles)`, which is what the counter reports as both its
    numbers."""
    monkeypatch.setattr(attn, "_bwd_plan",
                        lambda q_len, d, bq, bk, bh=1: (plan, 128, 256))
    seq, bh = 1024, 2
    shape = jax.ShapeDtypeStruct((1, bh, seq, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=256, block_k=128,
                               interpret=True, **mask
                               ).astype(jnp.float32).sum()

    grids = pallas_calls(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        shape, shape, shape).jaxpr)
    counted = flash_grid_steps(seq, 64, bh, block_q=256, block_k=128, **mask)
    assert len(counted) == {"combined": 2, "split": 3}[plan]
    assert grids == {name: (bh, steps) for name, (_, steps, _)
                     in counted.items()}
    seen = seen_pairs(seq, mask)
    for name, (live, steps, rectangle) in counted.items():
        block_q, block_k = (256, 128) if "fwd" in name else (128, 256)
        tiles = seen.reshape(seq // block_q, block_q, seq // block_k, block_k)
        assert live == steps == tiles.any((1, 3)).sum(), name
        assert rectangle == 32 and (live < 32) == bool(mask), name


def test_the_ring_reads_all_pairs():
    """The ring's offsets are traced: its table holds every pair, with the
    rows' `first` and `last` and nothing else, whatever the mask (the
    kernel's predicate decides a tile on the device)."""
    for mask in (attn.Causal(), Mask()):
        q_tile, k_tile, flags = attn._tile_table(3, 2, 128, 256, mask,
                                                 by_key=True, every=True)
        assert list(zip(k_tile, q_tile)) == [(j, i) for j in range(2)
                                             for i in range(3)]
        assert flags.tolist() == [attn._FIRST, 0, attn._LAST] * 2


def test_more_tiles_than_a_table_holds_take_the_scan():
    """A table lives in SMEM, so its steps are bounded (`_TABLE_STEPS` pairs,
    counted before the mask): past it a shape leaves the kernels as a ragged
    one does, forward and backward, and the counter has no entry for it."""
    fits = 128 * int(attn._TABLE_STEPS ** 0.5)
    for seq, kept in ((fits, True), (fits + 128, False)):
        assert (attn._forward_blocks(seq, seq, 64, 64, 128, 128, Mask())
                is not None) == kept
        assert (attn._backward_blocks(seq, seq, 64, 64, 128, 128, 1, Mask())
                is not None) == kept
        assert bool(flash_grid_steps(seq, 64, 1, causal=True, block_q=128,
                                     block_k=128)) == kept
    # the default blocks reach a quarter of a million rows
    assert attn._forward_blocks(1 << 17, 1 << 17, 64, 64, 1024, 1024, Mask())


@pytest.mark.parametrize("plan", ["combined", "split"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("mask", MASKS[:3], ids=str)
def test_values_and_gradients_are_the_references(monkeypatch, mask, d, plan):
    """Output, lse, dq, dk and dv of the table-driven kernels (interpreted)
    under the three masks, with tiles the mask cuts and whole ones, against
    `mha_reference`."""
    monkeypatch.setattr(attn, "_bwd_plan",
                        lambda q_len, d, bq, bk, bh=1: (plan, 128, 256))
    rng = np.random.default_rng(d + len(mask))
    q, k, v, mix = (jnp.asarray(rng.standard_normal((1, 2, 512, d)),
                                jnp.float32) for _ in range(4))

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=256, block_k=128,
                               interpret=True, **mask)

    def reference(q, k, v):
        return mha_reference(q, k, v, **mask)

    np.testing.assert_allclose(flash(q, k, v), reference(q, k, v),
                               atol=2e-5, rtol=2e-5)
    _, lse = attn._flash_forward(q, k, v, Mask.of(512, 512, **mask),
                                 d ** -0.5, 256, 128, True)
    logits = jnp.where(seen_pairs(512, mask), jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, precision="highest") * d ** -0.5, -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(logits, -1),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * mix).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (reference(*a) * mix).sum(),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-3, rtol=1e-3)


# --- bit for bit what the rectangular grids gave ------------------------------

def kernel_digests(attn, mask, d, plan):
    """sha256 (16 hex digits) of the float32 bytes of out, lse, dq, dk, dv of
    the interpreted kernels on a fixed seed: 2 heads of 512 rows, forward in
    (256, 128) blocks, backward ``plan`` in (128, 256).  ``attn``: the
    module (`AT_585DFAA` came from asking a copy of commit 585dfaa the same,
    through the signatures it had), and why the plan is patched by hand."""
    rng = np.random.default_rng([d, len(mask), plan == "split"])
    q, k, v, mix = (jnp.asarray(rng.standard_normal((1, 2, 512, d)),
                                jnp.bfloat16) for _ in range(4))
    real, attn._bwd_plan = attn._bwd_plan, \
        lambda q_len, d, bq, bk, bh=1: (plan, 128, 256)
    try:
        out, lse = attn._flash_forward(q, k, v, attn.Mask.of(512, 512, **mask),
                                       d ** -0.5, 256, 128, True)
        grads = jax.grad(lambda *a: (attn.flash_attention(
            *a, block_q=256, block_k=128, interpret=True, **mask).astype(
                jnp.float32) * mix.astype(jnp.float32)).sum(),
            (0, 1, 2))(q, k, v)
    finally:
        attn._bwd_plan = real
    return {name: hashlib.sha256(np.asarray(
        x.astype(jnp.float32)).tobytes()).hexdigest()[:16]
        for name, x in zip(("out", "lse", "dq", "dk", "dv"),
                           (out, lse) + tuple(grads))}


def ring_digests(fused_ring_attention, causal):
    """The same of the fused ring's dq, dk, dv on four devices: shards of 256
    rows in 128-blocks, the backward step the combined kernel."""
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.default_rng([4, causal])
    q, k, v, mix = (jnp.asarray(rng.standard_normal((1, 2, 1024, 64)),
                                jnp.float32) for _ in range(4))
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    spec = P(None, None, "sp", None)
    fn = functools.partial(fused_ring_attention, axis_name="sp",
                           causal=causal, block_q=128, block_k=128)

    def loss(q, k, v):
        out = jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                            out_specs=spec, check_vma=False)(q, k, v)
        return (out * mix).sum()

    grads = jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v)
    return {name: hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
            for name, x in zip(("dq", "dk", "dv"), grads)}


def kernel_dots(attn, mask, plan):
    """{kernel name: the `dot_general`s its traced body holds}, forward and
    backward ``plan`` of a 512-row call."""
    real, attn._bwd_plan = attn._bwd_plan, \
        lambda q_len, d, bq, bk, bh=1: (plan, 128, 256)
    try:
        shape = jax.ShapeDtypeStruct((1, 2, 512, 64), jnp.bfloat16)
        program = jax.make_jaxpr(jax.grad(
            lambda *a: attn.flash_attention(
                *a, block_q=256, block_k=128, interpret=True, **mask).astype(
                    jnp.float32).sum(), (0, 1, 2)))(shape, shape, shape).jaxpr
    finally:
        attn._bwd_plan = real
    return {eqn.params["name"]: len(list(equations(eqn.params["jaxpr"],
                                                   "dot_general")))
            for eqn in equations(program, "pallas_call")}


# What the kernels of commit 585dfaa — rectangular grids, a dead step a
# `pl.when` — gave for `kernel_digests` and `ring_digests` (my sandbox run,
# PR 44: tests' CPU, one thread, jax 0.9.0): "out lse dq dk dv".
AT_585DFAA = {
    ("causal", 64, "combined"):
        "51b5ac60505070cc 1c28bf07c20f90c9 34811145dfd841c9 "
        "cacbf0a8b46d3a7a 07ca09b520ab1e9e",
    ("causal", 128, "combined"):
        "ab0008f032afdb89 105a28bd76affc86 2264b3669e44a2aa "
        "5cc892fef96e2a54 dedb7967cc803eb3",
    ("causal", 64, "split"):
        "e446efaa7a7ae87c 7d5706aa1d627f69 1f190df0a10c3a3e "
        "afc998af084cdc5c 7a690e07806a95a4",
    ("causal", 128, "split"):
        "82ab99b6d8419934 21d7378f462d0edc 116ca8fabb575eeb "
        "1362faaa591c759d fbc99d90cc165691",
    ("window", 64, "combined"):
        "39888813254f2931 7f2c8855cea9a0be 16b107c10a969b9d "
        "08481e2850f3ab4b 0d14f36488f38dc3",
    ("window", 128, "combined"):
        "5650d5f6f0c088fb cb37851d65d35160 9e81b9bb2456d533 "
        "64c1a57ff22cbd03 b895055985c97a9d",
    ("window", 64, "split"):
        "63546bedb16b0ccd b120014d2691e9bf 9aa2f280d33b1c7b "
        "053b86da87d1e7d8 40616ef193dced1f",
    ("window", 128, "split"):
        "8d2d1919d3458897 d76ae852ce6d2e2c f531e54399ba3cd2 "
        "c19b503ad4cc807b ff93f42b8cb3dc69",
    ("blockdiff", 64, "combined"):
        "c9e6432b9ffc8fa7 67b1fa249f726edf 80485a65ac9f300d "
        "1f59dd8e6355e8c9 3b4ca7a90d727d7b",
    ("blockdiff", 128, "combined"):
        "7d03abd2c314fd0e cf91dc63b569c4fd 13b82d5e2af978f6 "
        "6460d43b6705936c e87263ccf51a6831",
    ("blockdiff", 64, "split"):
        "3ba393d68f76ad23 0f319087303ead93 c8aa3488acfc1fbd "
        "173bdd5a7296218b 4f46fb65ef1a206a",
    ("blockdiff", 128, "split"):
        "058c56ea4f7c3b75 889f53180d26dd85 bf77fcfd6fe0d5e0 "
        "f44d819c705a1c91 512d5551f1e344ad",
}
RING_AT_585DFAA = {   # causal: "dq dk dv"
    True: "fc3974e071218aad 4fe77be1a11f22fb c8528052c4369502",
    False: "4f482053f7b04cbf 26a9a65ddadb619a 497f9800819681bd",
}
MASK_OF = {"causal": dict(causal=True),
           "window": dict(causal=True, window=200),
           "blockdiff": dict(block_diffusion=32)}


def every_digest():
    """{"name-d-plan": the kernels' five digests, "ring-causal": the ring's
    three}: what the two tests below compare, as one JSON-able table."""
    from horovod_tpu.ops.ring_flash import fused_ring_attention

    table = {f"{name}-{d}-{plan}": kernel_digests(attn, MASK_OF[name], d, plan)
             for name, d, plan in AT_585DFAA}
    table.update({f"ring-{causal}": ring_digests(fused_ring_attention, causal)
                  for causal in RING_AT_585DFAA})
    return table


@functools.cache
def digests_at_the_default_level():
    """`every_digest()` from a process of its own whose XLA flags leave the
    backend's optimisation level alone.  tests/conftest.py compiles the
    suite's CPU programs at level 0, where LLVM vectorises no sum and a
    float's last bits move; the digests were taken at the default level, and
    it is the kernels they pin, not the compiler."""
    flags = " ".join(flag for flag in os.environ.get("XLA_FLAGS", "").split()
                     if "xla_backend_optimization_level" not in flag)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", "import json; from tests import "
         "test_flash_table as t; print(json.dumps(t.every_digest()))"],
        env=dict(os.environ, XLA_FLAGS=flags, PYTHONPATH=repo), cwd=repo,
        capture_output=True, text=True, timeout=280)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("name,d,plan", list(AT_585DFAA), ids=str)
def test_bit_for_bit_what_the_rectangular_grids_gave(name, d, plan):
    """Same blocks, same arithmetic in a live tile, same order of every
    accumulation: out, lse, dq, dk, dv are the parent's to the bit."""
    got = digests_at_the_default_level()[f"{name}-{d}-{plan}"]
    assert " ".join(got.values()) == AT_585DFAA[name, d, plan], got


@pytest.mark.parametrize("causal", [True, False])
def test_the_rings_backward_step_bit_for_bit(causal):
    """The fused ring's gradients on a four-device mesh: the combined kernel
    over the table of every pair, its predicate on the traced offsets."""
    got = digests_at_the_default_level()[f"ring-{causal}"]
    assert " ".join(got.values()) == RING_AT_585DFAA[causal], got


# The products of one body: q k^T and p v forward; s, dp, dv, dk, dq in the
# combined backward; the dk/dv kernel without dq's, the dq kernel without
# dk's and dv's.
ONE_BODY = {"hvd_flash_fwd": 2, "hvd_flash_bwd": 5, "hvd_flash_bwd_dkdv": 4,
            "hvd_flash_bwd_dq": 3}


@pytest.mark.parametrize("plan", ["combined", "split"])
@pytest.mark.parametrize("name", list(MASK_OF))
def test_a_kernel_holds_the_bodies_it_had(name, plan):
    """A causal and a banded kernel hold ONE body (every tile through the
    masked one), block diffusion's two (masked and unmasked, by the table's
    flag), as at commit 585dfaa: a body more is 5,600 to 9,800 bundles a
    kernel in every cell's program and seconds of every run's set-up
    (PERF.md section 6, PR 44), so one that comes back has to say so here."""
    suffix = {"causal": "", "window": "_window",
              "blockdiff": "_blockdiff"}[name]
    bodies = 2 if name == "blockdiff" else 1
    names = ["hvd_flash_fwd"] + {
        "combined": ["hvd_flash_bwd"],
        "split": ["hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"]}[plan]
    assert kernel_dots(attn, MASK_OF[name], plan) == {
        kernel + suffix: bodies * ONE_BODY[kernel] for kernel in names}


# --- a kind of mask the module has never heard of -----------------------------

@dataclasses.dataclass(frozen=True)
class PrefixLM(Mask):
    """Every query sees the keys below ``prefix``; after it the mask is
    causal.  The three forms `Mask` asks a kind for, and a second kernel body
    for the tiles it leaves whole, as block diffusion has."""

    prefix: int

    suffix = "_prefix"
    whole_body = True

    def seen(self, q_pos, k_pos):
        return (k_pos < self.prefix) | (q_pos >= k_pos)

    def tiles(self, num_q, num_k, block_q, block_k):
        q_lo, q_hi, k_lo, k_hi = self.bounds(num_q, num_k, block_q, block_k)
        return ((k_lo < self.prefix) | (q_hi >= k_lo),
                (k_hi < self.prefix) | (q_lo >= k_hi))

    def cut(self, s, q_start, k_start, block_q, block_k):
        key = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        query = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        return jnp.where((key < self.prefix) | (query >= key), s,
                         attn.NEG_INF)


@pytest.mark.parametrize("plan", ["combined", "split"])
def test_a_mask_is_one_class(monkeypatch, plan):
    """`PrefixLM`, defined above and nowhere else, through the private
    `_flash_attention`: its tables hold its live tiles and no other and flag
    its whole ones, its kernels carry its suffix and both bodies, and output
    and gradients are the dense softmax's under its own `seen` — with no edit
    to a kernel, a `pallas_call` wrapper, a planner or the table builder."""
    monkeypatch.setattr(attn, "_bwd_plan",
                        lambda q_len, d, bq, bk, bh=1: (plan, 128, 256))
    seq, d = 512, 64
    mask = PrefixLM(200).checked(seq, seq)
    live = check_tables(mask, seq, 256, 128)
    assert live == check_tables(attn.Causal(), seq, 256, 128) | {(0, 1)}
    check_tables(mask, seq, 128, 256)
    rng = np.random.default_rng(45)
    q, k, v, mix = (jnp.asarray(rng.standard_normal((1, 2, seq, d)),
                                jnp.float32) for _ in range(4))

    def flash(q, k, v):
        return attn._flash_attention(q, k, v, mask, d ** -0.5, 256, 128, True)

    def dense(q, k, v):
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            precision="highest") * d ** -0.5
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(
            seen_pairs(seq, mask), logits, -jnp.inf), -1), v,
            precision="highest")

    def loss(fn):
        return lambda *a: (fn(*a) * mix).sum()

    program = jax.make_jaxpr(jax.grad(loss(flash), (0, 1, 2)))(q, k, v).jaxpr
    names = ["hvd_flash_fwd"] + {
        "combined": ["hvd_flash_bwd"],
        "split": ["hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"]}[plan]
    assert {eqn.params["name"]: len(list(equations(eqn.params["jaxpr"],
                                                   "dot_general")))
            for eqn in equations(program, "pallas_call")} == {
        name + "_prefix": 2 * ONE_BODY[name] for name in names}
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               atol=2e-5, rtol=2e-5)
    for g, w in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                    jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, w, atol=1e-3, rtol=1e-3)


# --- kinds whose decision is data ---------------------------------------------

def selection_of(seq, batch=2, keep=0.5, seed=56):
    """A seeded int8 selection (batch, seq, seq), not even causal: `seen`
    and `cut` are."""
    return jnp.asarray(np.random.default_rng(seed).random(
        (batch, seq, seq)) < keep, jnp.int8)


@pytest.mark.parametrize("topk", [128, 200, 256, 384])
@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
def test_the_selected_kinds_tables(topk, block_q, block_k):
    """`Selected` through the three forms: the causal kind's live tiles, the
    query tiles that end under `topk` rows flagged (the causal `cut` serves
    them), a tile astride row `topk` cut by both."""
    seq = 512
    mask = attn.Selected(topk).checked(seq, seq)
    chosen = selection_of(seq)[0]
    live = check_fed_tables(mask, seq, block_q, block_k, chosen)
    assert live == check_tables(attn.Causal(), seq, block_q, block_k)
    flagged = mask.tiles(seq // block_q, seq // block_k, block_q, block_k)[1]
    assert flagged.any(1).sum() == topk // block_q
    assert mask.operands == 1 and mask.flagged == attn.Causal()
    # every earlier key kept IS the causal mask, program for program
    assert attn.Selected(seq).checked(seq, seq) == attn.Causal()
    with pytest.raises(ValueError, match="whole sequences"):
        attn.Selected(topk).checked(seq, None)


def test_a_flagged_tile_fetches_no_operand():
    """The selection's block spec stays on the first tile that reads it while
    the table's step is flagged."""
    mask = attn.Selected(256)
    table = attn._tile_table(4, 4, 128, 128, mask)
    (spec,) = mask.specs(128, 128, heads=2)
    at = [tuple(int(x) for x in spec.index_map(3, s, table))
          for s in range(table.shape[1])]
    for s, (q_tile, k_tile, flags) in enumerate(table.T):
        assert at[s] == ((1, 2, 0) if flags & attn._WHOLE
                         else (1, q_tile, k_tile))
    assert sum(1 for x in at if x == (1, 2, 0)) == 3 + 1   # and (2, 0) itself


def dense_under(seen, d):
    def attend(q, k, v):
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            precision="highest") * d ** -0.5
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(
            seen[:, None], logits, -jnp.inf), -1), v, precision="highest")
    return attend


def fed_against_dense(monkeypatch, plan, mask, operands, seen, seq, d,
                      blocks=(256, 128), bodies=2):
    """Output, lse and the three gradients of `masked_flash_attention` under
    ``mask`` with ``operands`` (interpreted, two batch rows of two heads)
    against the dense softmax under ``seen`` (batch, seq, seq); the kernels'
    names, and the bodies each holds."""
    monkeypatch.setattr(attn, "_bwd_plan",
                        lambda q_len, d, bq, bk, bh=1: (plan, 128, 256))
    jax.clear_caches()            # `_fed_call` is jitted: the plan is traced
    rng = np.random.default_rng(56)
    q, k, v, mix = (jnp.asarray(rng.standard_normal((2, 2, seq, d)),
                                jnp.float32) for _ in range(4))

    def flash(q, k, v):
        return attn.masked_flash_attention(
            q, k, v, mask, *operands, block_q=blocks[0], block_k=blocks[1],
            interpret=True)

    def loss(fn):
        return lambda *a: (fn(*a) * mix).sum()

    dense = dense_under(seen, d)
    out, lse = flash(q, k, v)
    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-5, rtol=2e-5)
    logits = jnp.where(seen[:, None], jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, precision="highest") * d ** -0.5, -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(logits, -1),
                               atol=2e-5, rtol=2e-5)
    first = lambda *a: flash(*a)[0]  # noqa: E731
    for g, w in zip(jax.grad(loss(first), (0, 1, 2))(q, k, v),
                    jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, w, atol=1e-3, rtol=1e-3)
    program = jax.make_jaxpr(jax.grad(loss(first), (0, 1, 2)))(q, k, v).jaxpr
    names = ["hvd_flash_fwd"] + {
        "combined": ["hvd_flash_bwd"],
        "split": ["hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq"]}[plan]
    assert {eqn.params["name"]: len(list(equations(eqn.params["jaxpr"],
                                                   "dot_general")))
            for eqn in equations(program, "pallas_call")} == {
        name + mask.suffix: bodies * ONE_BODY[name] for name in names}
    jax.clear_caches()


@pytest.mark.parametrize("plan", ["combined", "split"])
@pytest.mark.parametrize("topk", [128, 200])
def test_the_selected_kernels_are_the_dense_softmax(monkeypatch, plan, topk):
    """The three kernel sites under `Selected`, fed a seeded selection: two
    bodies a kernel (the operand's, and the causal one for the flagged
    tiles), `_selected` behind every name."""
    seq, d = 512, 64
    chosen = selection_of(seq)
    mask = attn.Selected(topk).checked(seq, seq)
    seen = np.stack([seen_pairs(seq, mask, row) for row in chosen])
    fed_against_dense(monkeypatch, plan, mask, (chosen,), seen, seq, d)


@dataclasses.dataclass(frozen=True)
class Documents(Mask):
    """Packed documents, defined here and nowhere else: a query sees the
    earlier keys of its OWN document.  The operand is the document of every
    position, ``int32[batch, seq]`` — handed to the kernels twice, as a
    column for a tile's queries and as a row for its keys, and nothing else.
    The live tiles are the causal kind's; one body."""

    suffix = "_documents"
    square = True
    operands = 2

    def seen(self, q_pos, k_pos, column, row):
        return (q_pos >= k_pos) & (column[..., q_pos, 0] == row[..., 0, k_pos])

    def tiles(self, num_q, num_k, block_q, block_k):
        q_lo, q_hi, k_lo, _ = self.bounds(num_q, num_k, block_q, block_k)
        return q_hi - k_lo >= 0, np.zeros((num_q, num_k), bool)

    def specs(self, block_q, block_k, heads):
        return (attn.pl.BlockSpec((1, block_q, 1), lambda b, s, tab, *_: (
                    b // heads, tab[0, s], 0)),
                attn.pl.BlockSpec((1, 1, block_k), lambda b, s, tab, *_: (
                    b // heads, 0, tab[1, s])))

    def cut(self, s, q_start, k_start, block_q, block_k, column, row):
        diff = (q_start - k_start) + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) - jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return jnp.where((diff >= 0) & (column.reshape(block_q, 1)
                                        == row.reshape(1, block_k)), s,
                         attn.NEG_INF)


@pytest.mark.parametrize("plan", ["combined", "split"])
def test_a_mask_of_data_is_one_class(monkeypatch, plan):
    """`Documents` through `masked_flash_attention` with no edit to a kernel,
    a `pallas_call` wrapper, a planner or the table builder: the seam hands a
    kind's operands to the three sites under the kind's own specs."""
    seq, d = 512, 64
    starts = np.array([[0, 100, 256, 300], [0, 7, 130, 500]])
    documents = jnp.asarray(
        (np.arange(seq)[None, :, None] >= starts[:, None, :]).sum(-1),
        jnp.int32)
    operands = (documents[:, :, None], documents[:, None, :])
    mask = Documents().checked(seq, seq)
    seen = np.stack([seen_pairs(seq, mask, c, r)
                     for c, r in zip(*operands)])
    assert (seen == (np.tril(np.ones((seq, seq), bool))
                     & (np.asarray(documents)[:, :, None]
                        == np.asarray(documents)[:, None, :]))).all()
    fed_against_dense(monkeypatch, plan, mask, operands, seen, seq, d,
                      bodies=1)


def test_operands_are_counted_and_stay_on_the_grid():
    q = jnp.zeros((1, 2, 200, 64))
    with pytest.raises(ValueError, match="operand"):
        attn.masked_flash_attention(q, q, q, attn.Selected(64), interpret=True)
    with pytest.raises(ValueError, match="grid"):
        attn.masked_flash_attention(q, q, q, attn.Selected(64),
                                    jnp.ones((1, 200, 200), jnp.int8),
                                    interpret=True)
    # a kind without operands goes the same way, and is the public call
    q = jnp.asarray(np.random.default_rng(1).standard_normal((1, 2, 256, 64)),
                    jnp.float32)
    out, _ = attn.masked_flash_attention(q, q, q, attn.Causal(),
                                         interpret=True)
    np.testing.assert_allclose(out, flash_attention(q, q, q, causal=True,
                                                    interpret=True),
                               atol=1e-6, rtol=1e-6)
