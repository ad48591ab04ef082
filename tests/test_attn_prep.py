"""`ops.attn_prep.normed_and_turned` against the composition it replaces on q in
a grouped `Attention` layer — the per-head RMSNorm, rounded, then `rope`,
rounded again — through the Pallas interpreter, at each kind of table the
benchmark's four cells with such layers hand it.  (The kernels' compiles for
the chip, the steps that hold them and what a body costs to trace:
tests/test_chip_steps.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models.transformer import (RopeScaling, rope,
                                            rotary_tables)
from horovod_tpu.ops import attn_prep
from horovod_tpu.ops.attn_prep import normed_and_turned, prep_rows

EPS = 1e-6
D = attn_prep.LANES


def streams(rows):
    """Keye's positions of a text row: its three streams (time, height,
    width) are one and the same, so the interleaved table is the plain one."""
    at = jnp.stack([jnp.arange(rows)] * 3)
    assert bool((at[0] == at[1]).all() and (at[1] == at[2]).all())
    return at[0]


# name -> (heads, rows, rope's keywords, positions of the rows)
CASES = {
    "head128_64_rows": (4, 64, dict(base=1e6), jnp.arange),
    "head128_512_rows": (2, 512, dict(base=1e6), jnp.arange),
    "yarn_table_and_magnitude": (
        4, 128, dict(base=5e5, scaling=RopeScaling(16, 32)), jnp.arange),
    "block_diffusion_positions": (
        4, 128, dict(base=1e6), lambda rows: jnp.arange(rows) % (rows // 2)),
    "keye_streams": (4, 128, dict(base=1e7), streams),
    "rows_of_8": (3, 264, dict(base=1e4), jnp.arange),
}


def case(name, dtype):
    """(x, scale, a cotangent, positions, rope's keywords)."""
    heads, rows, turn, positions = CASES[name]
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    x = (3.0 * jax.random.normal(keys[0], (2, heads, rows, D))).astype(dtype)
    scale = 1.0 + 0.2 * jax.random.normal(keys[1], (D,))
    d_out = jax.random.normal(keys[2], x.shape).astype(dtype)
    return x, scale, d_out, positions(rows), turn


def composed(x, scale, at, turn):
    """`Attention._head_norm` then `rope`, in ``x``'s type."""
    wide = x.astype(jnp.float32)
    mean_sq = jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
    y = (wide * lax.rsqrt(mean_sq + EPS) * scale).astype(x.dtype)
    return rope(y, at, seq_dim=-2, **turn)


def prepared(x, scale, at, turn):
    return normed_and_turned(x, scale, *rotary_tables(
        at, D, turn["base"], scaling=turn.get("scaling")), EPS)


def gradients(fn, x, scale, d_out):
    return jax.vjp(fn, x, scale)[1](d_out)


def _values(name, dtype):
    """In float32 the two are one function; in bfloat16 the kernels' result
    is the float32 result rounded once."""
    x, scale, _, at, turn = case(name, dtype)
    got = jax.jit(lambda x, s: prepared(x, s, at, turn))(x, scale)
    assert got.dtype == dtype and got.shape == x.shape
    exact = composed(x.astype(jnp.float32), scale, at, turn)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, exact, rtol=0, atol=1e-6 * float(
            jnp.abs(exact).max()))
        return
    # One rounding: within half a bfloat16 spacing of the exact value, and a
    # float32 ulp or two of the kernels' own evaluation order.
    spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(
        jnp.abs(exact), 1e-30))) - 7)
    off = jnp.abs(got.astype(jnp.float32) - exact)
    assert bool((off <= 0.5 * spacing + 1e-6).all()), float(
        (off / spacing).max())
    twice = jnp.abs(composed(x, scale, at, turn).astype(jnp.float32) - exact)
    assert float(off.mean()) <= float(twice.mean())


def _gradients(name, dtype):
    """By the input and by the scale, from one random cotangent: in float32
    to rounding; in bfloat16 no further from the float32 gradients than the
    composition's own."""
    x, scale, d_out, at, turn = case(name, dtype)
    ours = jax.jit(lambda *o: gradients(
        lambda x, s: prepared(x, s, at, turn), *o))(x, scale, d_out)
    exact = gradients(lambda x, s: composed(x, s, at, turn),
                      x.astype(jnp.float32), scale,
                      d_out.astype(jnp.float32))
    assert ours[0].dtype == dtype and ours[1].dtype == jnp.float32
    assert ours[1].shape == scale.shape

    def off(got, want):
        return float(jnp.linalg.norm(
            (got.astype(jnp.float32) - want).ravel())
            / jnp.linalg.norm(want.ravel()))

    if dtype == jnp.float32:
        assert off(ours[0], exact[0]) <= 1e-5
        assert off(ours[1], exact[1]) <= 1e-5
        return
    theirs = gradients(lambda x, s: composed(x, s, at, turn), x, scale,
                       d_out)
    assert off(ours[0], exact[0]) <= off(theirs[0], exact[0])
    assert off(ours[1], exact[1]) <= max(off(theirs[1], exact[1]), 1e-3)


def _residuals(name, dtype):
    """What the backward is handed: the input as stored, the scale and the
    tables — where autodiff of the composition keeps float32 arrays of the
    activation's size."""
    from jax._src.ad_checkpoint import saved_residuals

    x, scale, _, at, turn = case(name, dtype)

    def kept(fn):
        return [aval for aval, _ in saved_residuals(
            lambda x, s: fn(x, s, at, turn), x, scale)]

    ours = kept(prepared)
    assert [(a.shape, a.dtype) for a in ours if a.size >= x.size] \
        == [(x.shape, dtype)]
    assert all(a.ndim <= 2 for a in ours if a.size < x.size)
    if dtype == jnp.bfloat16:
        assert [a for a in kept(composed) if a.size >= x.size
                and a.dtype == jnp.float32]


def _chunks(name, dtype):
    """The compiled kernels walk a tile of 2,048 rows in chunks of 256, a
    chunk's row sums staged in front of the chunk before's turn, the chunks
    between the first and the last one loop body; the interpreter's tiles are
    one chunk.  The same rows through tiles of two, four and eight chunks:
    the same bits, forward and backward."""
    rows = {"two": 512, "four": 1024, "eight": 2048}[name]
    chunk = attn_prep._CHUNK_ROWS
    assert prep_rows(rows, D, interpret=True) == chunk
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (2, rows, D)).astype(dtype)
    d_out = jax.random.normal(keys[1], x.shape).astype(dtype)
    scale = 1.0 + 0.2 * jax.random.normal(keys[2], (D,))
    tables = rotary_tables(jnp.arange(rows), D, 1e4)
    one = attn_prep._fwd_call(x, scale, *tables, EPS, chunk, True)
    many = attn_prep._fwd_call(x, scale, *tables, EPS, rows, True)
    np.testing.assert_array_equal(one, many)
    one = attn_prep._bwd_call(d_out, x, scale, *tables, EPS, chunk, True)
    many = attn_prep._bwd_call(d_out, x, scale, *tables, EPS, rows, True)
    np.testing.assert_array_equal(one[0], many[0])
    # The partial sums of the scale's gradient add in another order.
    np.testing.assert_allclose(one[1], many[1], rtol=0, atol=1e-5 * float(
        jnp.abs(one[1]).max()))


def _refused(name, dtype):
    """`prep_rows` names the layers whose q takes the pass: heads of 128
    turned whole on whole sequences, over rows the tiles divide.  The others
    keep the composition, and the function itself refuses them by name."""
    del dtype
    keywords = {
        "width_256": dict(head_dim=256), "width_64": dict(head_dim=64),
        "partial_rotary_dim": dict(rotary_dim=64),
        "rope_off": dict(rope=False),
        "cached_decode_or_ring": dict(whole=False),
        "rows_off_the_tiles": dict(seq=4100),
    }[name]
    asked = dict(dict(seq=4096, head_dim=D), **keywords)
    for interpret in (False, True):
        assert prep_rows(**asked, interpret=interpret) is None
    assert prep_rows(4096, D, interpret=False) == 2048
    assert prep_rows(16384, D, interpret=False) == 2048
    assert prep_rows(8192 + 512, D, interpret=False) == 512
    assert prep_rows(128, D, interpret=False) == 128
    assert prep_rows(4104, D, interpret=False) is None
    assert prep_rows(4104, D, interpret=True) == 8
    if "head_dim" in keywords or "seq" in keywords:
        with pytest.raises(ValueError, match="prep_rows"):
            normed_and_turned(
                jnp.zeros((1, 1, asked["seq"], asked["head_dim"])),
                jnp.ones((asked["head_dim"],)),
                *rotary_tables(jnp.arange(asked["seq"]), asked["head_dim"],
                               1e4), EPS)


def _layer(name, dtype, monkeypatch):
    """A grouped, per-head-normed `Attention` layer whose q takes the pass
    against the same layer, same parameters, with the composition (its
    `prep_rows` made to refuse): output and every gradient, float32; the
    kernels are told the part of ``head_dim ** -0.5`` that did not ride q's
    scale, whichever they are."""
    from horovod_tpu.models import transformer
    from horovod_tpu.models.transformer import Attention, IndexerConfig

    keywords = {
        "scan": dict(use_flash=False),
        "flash_window": dict(use_flash=True, window=128),
        "flash_blockdiff": dict(use_flash=True, block_diffusion=4),
        "flash_selected": dict(use_flash=True,
                               indexer=IndexerConfig(2, 16, 64)),
    }[name]
    layer = Attention(n_heads=4, dtype=dtype, n_kv_heads=2, head_dim=D,
                      head_norm=True, rope_theta=1e6, **keywords)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (1, 256, 64), dtype)
    params = layer.init(keys[1], x)["params"]
    params = jax.tree.map(
        lambda leaf, key: leaf + 0.2 * jax.random.normal(key, leaf.shape)
        if leaf.ndim == 1 else leaf, params,
        jax.tree.unflatten(jax.tree.structure(params), list(jax.random.split(
            keys[2], len(jax.tree.leaves(params))))))

    def out_and_gradients():
        def loss(params, x):
            out = layer.apply({"params": params}, x,
                              mutable=["intermediates"])[0]
            return jnp.sum(out * jnp.cos(out)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True))(params, x)
        return out, grads

    lowered = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["intermediates"])[0]).lower(params, x)
    assert "hvd_attn_prep_fwd" in lowered.as_text(debug_info=True)
    ours = out_and_gradients()
    monkeypatch.setattr(transformer, "prep_rows", lambda *a, **k: None)
    theirs = out_and_gradients()
    for got, want in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * float(
            jnp.abs(want).max()))


def _trains(name, dtype):
    """Inside `build_train_step`'s `shard_map` on two CPU devices, the kernels
    interpreted a tile a chunk: a windowed and a full layer (recomputed or
    not) train, and the replicated weights stay equal."""
    from horovod_tpu.models import TransformerLM
    from tests.test_hybrid import trains_and_replicas_stay_equal

    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=1, head_dim=D,
        head_norm=True, d_ff=64, dtype=dtype, use_flash=True, window=128,
        layers=("window_attention", "gated_mlp", "attention", "gated_mlp"),
        recompute=name == "recomputed")
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    tokens = jax.random.randint(keys[0], (2, 257), 0, 64)
    params = model.init(keys[1], tokens[:, :128])["params"]
    trains_and_replicas_stay_equal(model, params,
                                   (tokens[:, :-1], tokens[:, 1:]))


CHECKS = [(check, name, dtype)
          for check, names in ((_values, CASES), (_gradients, CASES))
          for name in names for dtype in (jnp.float32, jnp.bfloat16)] \
    + [(_residuals, name, dtype)
       for name, dtype in (("head128_64_rows", jnp.bfloat16),
                           ("yarn_table_and_magnitude", jnp.float32))] \
    + [(_chunks, name, dtype)
       for name, dtype in (("two", jnp.bfloat16), ("four", jnp.float32),
                           ("eight", jnp.bfloat16))] \
    + [(_refused, name, None)
       for name in ("width_256", "width_64", "partial_rotary_dim",
                    "rope_off", "cached_decode_or_ring",
                    "rows_off_the_tiles")] \
    + [(_trains, name, jnp.float32) for name in ("kept", "recomputed")] \
    + [(_layer, name, jnp.float32)
       for name in ("scan", "flash_window", "flash_blockdiff",
                    "flash_selected")]


@pytest.mark.parametrize(
    "check,name,dtype", CHECKS,
    ids=[f"{check.__name__[1:]}-{name}"
         + ("" if dtype is None else f"-{jnp.dtype(dtype).name}")
         for check, name, dtype in CHECKS])
def test_normed_and_turned(check, name, dtype, monkeypatch):
    check(name, dtype, *([monkeypatch] if check is _layer else []))
