"""A query head's RMSNorm and its rotary turn as one pass each way.

``normed_and_turned(x, scale, cos, sin, eps)`` takes the q projection's
output ``(batch, heads, seq, 128)`` to the attention kernels' operand: with
``r = rsqrt(mean(x^2) + eps)`` a row, ``y = x r scale`` and ``S`` the signed
permutation that swaps a pair's two components (``(y S)[2i] = -y[2i + 1]``,
``(y S)[2i + 1] = y[2i]``),

    out = y cos + (y S) sin

— ``rope``'s adjacent-pair rotation of the per-head norm's output
(``models.transformer``), float32 inside and rounded ONCE, where the
composition rounds after the norm and again after the turn.  ``cos`` and
``sin`` are ``(seq, 128)`` float32 tables (a scaled table carries its
magnitude).

Each direction is one Pallas kernel over ``(rows, 128)`` tiles
(``hvd_attn_prep_fwd``, ``hvd_attn_prep_bwd`` in a device trace): the input is
read once and the result written once; the pair swap is two lane rolls and a
select on the lane's parity, exact, no product.  The backward reads the
cotangent and the kept ``x``, turns the cotangent back (``d_out cos + (d_out
sin) S^T``), recomputes ``r`` and writes ``dx``; the scale's gradient leaves as
float32 partial sums a grid step, summed outside.  It keeps ``x`` as stored —
the projection's output — the scale and the tables, and nothing of the
activation's size besides.

:func:`prep_rows` says where the pass pays (PERF.md section 6, PRs 62 and 63:
q at 128 lanes with a whole-head turn; four key heads, a norm alone and a
partial turn of 256 lanes are at a call's floor either way), and a body is
written ONCE: a tile's chunks are a `lax.fori_loop` that the lowering unrolls,
so Python traces one chunk, not a tile of them (a body of sixteen written-out
chunks took the chip machine's host 1.2 to 2.1 s to trace: ledger, PR 62).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.metrics import kernel_trace
from horovod_tpu.ops.moe import reduced_to_vma_of

# The lanes the kernels roll in: one register's.
LANES = 128
# A grid step's rows: 2,048 of 128 lanes (a 512 KiB bfloat16 tile each of
# input and output, twice buffered, beside two float32 tables of 1 MiB) stay
# well inside the 16 MiB a kernel has without asking.
_TILE_ROWS = 2048
# What the body takes at a time from a tile: 256 rows are as fast as 128 and
# faster than 64 (0.577 / 0.611 / 0.733 ms forward at (1, 32, 16384, 128):
# PERF.md section 6, PR 62's `variants.jsonl`).
_CHUNK_ROWS = 256


def prep_rows(seq: int, head_dim: int, rotary_dim: Optional[int] = None,
              rope: bool = True, whole: bool = True,
              interpret: Optional[bool] = None) -> Optional[int]:
    """The rows of a grid step's tile for a layer's q of ``seq`` rows, or
    None where the layer keeps the composition: heads of 128 lanes turned
    whole (``rope`` and no ``rotary_dim``) over ``whole`` sequences at the
    rows' own positions (no cached decode, no ring).  Compiled, a tile is up
    to 2,048 rows that divide ``seq``, 16 at least; the interpreter takes a
    multiple of 8 rows, a tile of one chunk (inside shard_map its loop over
    the grid takes no slice of a reference: the mapped axes of the two would
    differ).  ``interpret=None`` asks the backend."""
    if head_dim != LANES or rotary_dim is not None or not rope or not whole \
            or seq % 8:
        return None
    if _interpreted(interpret):
        return math.gcd(seq, _CHUNK_ROWS)
    rows = _TILE_ROWS
    while rows >= 16 and seq % rows:
        rows //= 2
    return rows if rows >= 16 else None


def _interpreted(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _swapped(t, back=False):
    """``t @ S`` of `models.transformer._pair_swap` — lane ``2i`` takes ``-t[2i
    + 1]``, lane ``2i + 1`` takes ``t[2i]`` — or, ``back``, ``t @ S^T``, its
    negative: two lane rolls, a negation and a select on the lane's parity.
    (A roll wraps, and the lanes that wrap are not selected.)"""
    d = t.shape[-1]
    even = lax.broadcasted_iota(jnp.int32, t.shape, t.ndim - 1) & 1 == 0
    after = pltpu.roll(t, d - 1, t.ndim - 1)
    before = pltpu.roll(t, 1, t.ndim - 1)
    return jnp.where(even, after, -before) if back \
        else jnp.where(even, -after, before)


def _row_mean(t):
    return jnp.mean(t, axis=-1, keepdims=True)


def _at_every_step(body):
    """``body()`` under a `cond` on what is true at every step: inside
    `shard_map` the interpreter lets constants, which vary over no mesh axis,
    meet operands, which do, only there (as `ops.attention._when_live`)."""
    pl.when(pl.program_id(0) >= 0)(body)


def _staged(rows, first, second, carry=None):
    """``carry = second(rows slice, first(rows slice), carry)`` over a tile's
    chunks of rows, a chunk's ``first`` in front of the chunk before's
    ``second``: the row sums of one chunk are on their way through the
    cross-lane unit while the one before is turned.  The chunks between the
    first's ``first`` and the last's ``second`` are one loop body, traced
    once and unrolled where it is lowered."""
    chunk = math.gcd(rows, _CHUNK_ROWS)
    last = rows // chunk - 1

    def at(i):
        start = i * chunk
        return pl.ds(start if isinstance(i, int)
                     else pl.multiple_of(start, chunk), chunk)

    def step(i, state):
        ahead, carry = state
        return first(at(i + 1)), second(at(i), ahead, carry)

    state = first(at(0)), carry
    if last:
        state = lax.fori_loop(0, last, step, state, unroll=True)
    return second(at(last), *state)


def _fwd_kernel(x_ref, scale_ref, cos_ref, sin_ref, out_ref, *, eps):
    def unit_scale(at):
        x = x_ref[at, :].astype(jnp.float32)
        return lax.rsqrt(_row_mean(x * x) + eps)

    def turned(at, r, _):
        y = x_ref[at, :].astype(jnp.float32) * r * scale_ref[...]
        y = y * cos_ref[at, :] + _swapped(y) * sin_ref[at, :]
        out_ref[at, :] = y.astype(out_ref.dtype)

    _at_every_step(lambda: _staged(x_ref.shape[0], unit_scale, turned))


def _bwd_kernel(dy_ref, x_ref, scale_ref, cos_ref, sin_ref, dx_ref, ds_ref, *,
                eps):
    d = x_ref.shape[1]

    def turned_back(at):
        x = x_ref[at, :].astype(jnp.float32)
        r = lax.rsqrt(_row_mean(x * x) + eps)
        dy = dy_ref[at, :].astype(jnp.float32)
        return r, dy * cos_ref[at, :] + _swapped(dy * sin_ref[at, :],
                                                 back=True)

    def normed_back(at, first, d_scale):
        r, dy = first
        unit = x_ref[at, :].astype(jnp.float32) * r
        d_unit = dy * scale_ref[...]
        dx_ref[at, :] = (r * (d_unit - unit * _row_mean(d_unit * unit))
                         ).astype(dx_ref.dtype)
        # Eight rows of partial sums: adds between registers, no reduce
        # across sublanes.
        return d_scale + (dy * unit).reshape(-1, 8, d).sum(axis=0)

    @_at_every_step
    def _():
        ds_ref[...] = _staged(x_ref.shape[0], turned_back, normed_back,
                              jnp.zeros((8, d), jnp.float32))


def _varying_as(x, *operands):
    """``operands`` varying over the mapped axes ``x`` varies over: inside
    shard_map the activation's rows vary and the scale and the tables do not,
    and the interpreter's loop over the grid carries them together."""
    vma = jax.typeof(x).vma

    def varying(t):
        missing = vma - jax.typeof(t).vma
        return lax.pcast(t, tuple(sorted(missing)), to="varying") \
            if missing else t

    return vma, [varying(t) for t in operands]


def _specs(bh, seq, rows):
    """(grid, the activation's spec, the scale's, the tables'): the sequence's
    tiles outer and the heads inner, so that a tile of the tables is fetched
    once for all of them."""
    tile = pl.BlockSpec((None, rows, LANES), lambda i, j: (j, i, 0))
    scale = pl.BlockSpec((1, LANES), lambda i, j: (0, 0))
    table = pl.BlockSpec((rows, LANES), lambda i, j: (i, 0))
    return (seq // rows, bh), tile, scale, table


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _fwd_call(x, scale, cos, sin, eps, rows, interpret):
    """The forward kernel over ``x`` (bh, seq, 128): jitted, so that a step's
    layers trace and lower it once (as `ops.moe._tiled_call`)."""
    bh, seq, _ = x.shape
    grid, tile, scale_spec, table = _specs(bh, seq, rows)
    vma, (scale, cos, sin) = _varying_as(x, scale, cos, sin)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid, in_specs=[tile, scale_spec, table, table], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
        compiler_params=_PARALLEL,
        cost_estimate=pl.CostEstimate(
            flops=12 * x.size, transcendentals=bh * seq,
            bytes_accessed=2 * x.size * x.dtype.itemsize),
        interpret=interpret, name="hvd_attn_prep_fwd")
    with kernel_trace("hvd_attn_prep_fwd"):
        return call(x, scale[None], cos, sin)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _bwd_call(dy, x, scale, cos, sin, eps, rows, interpret):
    """The backward kernel: (dx as ``x``, the scale's gradient (128,)
    float32)."""
    bh, seq, d = x.shape
    grid, tile, scale_spec, table = _specs(bh, seq, rows)
    vma, (scale, cos, sin) = _varying_as(x, scale, cos, sin)
    steps = grid[0] * grid[1]
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=grid, in_specs=[tile, tile, scale_spec, table, table],
        out_specs=[tile, pl.BlockSpec((None, 8, d),
                                      lambda i, j: (i * bh + j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
                   jax.ShapeDtypeStruct((steps, 8, d), jnp.float32, vma=vma)],
        compiler_params=_PARALLEL,
        cost_estimate=pl.CostEstimate(
            flops=20 * x.size, transcendentals=bh * seq,
            bytes_accessed=3 * x.size * x.dtype.itemsize),
        interpret=interpret, name="hvd_attn_prep_bwd")
    with kernel_trace("hvd_attn_prep_bwd"):
        dx, d_scale = call(dy, x, scale[None], cos, sin)
    return dx, d_scale.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def normed_and_turned(x, scale, cos, sin, eps: float,
                      interpret: Optional[bool] = None):
    """``x`` (batch, heads, seq, 128) normed over its last axis (RMSNorm, one
    ``scale`` (128,) float32 for every head) and its adjacent pairs turned by
    the ``(seq, 128)`` float32 tables ``cos`` and ``sin`` — see the module.
    ``interpret=None`` asks the backend; :func:`prep_rows` says which shapes
    the kernels take."""
    return _normed_and_turned_fwd(x, scale, cos, sin, eps, interpret)[0]


def _plan(x, interpret):
    interpret = _interpreted(interpret)
    rows = prep_rows(x.shape[2], x.shape[3], interpret=interpret)
    if rows is None:
        raise ValueError(f"normed_and_turned takes no {x.shape}: see "
                         "prep_rows")
    return rows, interpret


def _normed_and_turned_fwd(x, scale, cos, sin, eps, interpret):
    rows, interpret = _plan(x, interpret)
    out = _fwd_call(x.reshape(-1, *x.shape[2:]), scale, cos, sin, eps, rows,
                    interpret)
    return out.reshape(x.shape), (x, scale, cos, sin)


def _normed_and_turned_bwd(eps, interpret, kept, d_out):
    x, scale, cos, sin = kept
    rows, interpret = _plan(x, interpret)
    dx, d_scale = _bwd_call(
        d_out.reshape(-1, *x.shape[2:]), x.reshape(-1, *x.shape[2:]), scale,
        cos, sin, eps, rows, interpret)
    return (dx.reshape(x.shape), reduced_to_vma_of(scale, d_scale), None,
            None)


normed_and_turned.defvjp(_normed_and_turned_fwd, _normed_and_turned_bwd)
