"""The selective state-space recurrence of a Mamba-2 layer as matrix products
over chunks (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060, section 6).

Per head, with a state ``h`` of ``head_dim x state`` and the ``B_t``, ``C_t``
of the head's group:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
    y_t = h_t C_t + D x_t

Token by token that is ``seq`` dependent steps of a few thousand operations
each.  :func:`chunked_scan` computes the same ``y`` from four products a chunk:
within a chunk, ``(C B^T . L) x`` with ``L[i, j] = exp(sum_{j<k<=i} dt_k A)``
the masked decay matrix; each chunk's own end state ``(decay . B)^T x``; the
states ENTERING each chunk from all earlier chunks' end states through the
products of whole-chunk decays (one small matrix over chunks: no loop, so no
``while`` in the compiled program); and ``C h . decay`` for what the entering
state adds.  Everything that decays — the cumulative sums, their exponentials,
the states between chunks — is float32; the operands of the four products are
rounded to ``x``'s dtype and accumulated in float32, as every matmul of the
model is.  As XLA's products it is differentiable by autodiff of that form.

The cost of that form is memory passes, not arithmetic: a token's masked
decay matrix ``L`` is ``heads x chunk`` float32 and the ``mixed`` operand as
many elements in ``x``'s dtype, and every stage reads and writes arrays of
``y``'s size (``heads x head_dim`` float32 a token) — the entering states'
share of ``y``, the skip, the weighted ``dt x`` the end states read — forward
and backward, beside ``heads x chunk x head_dim + 2 heads x state x head_dim +
groups x chunk x state`` multiply-adds.  At the two points the benchmark runs:
16 heads on one group at a chunk of 128 (an eighth of Nemotron-3-Super's 128
heads in 8 groups, its tensor share) 8 KB of ``L`` and 0.41 M multiply-adds a
token beside the 13.7 M of the layer's two projections; 64 whole heads on ONE
group at a chunk of 256 (Granite-4.0-H-Micro) 64 KB of ``L`` — sixteen times a
token's residual stream of 2,048 bfloat16 — and 2.13 M beside 25.8 M.

The four stages run under ``jax.named_scope``s a trace can read, forward and
backward alike (:data:`STAGES`): ``hvd_ssm_scan_decay`` (the cumulative sums,
the masked ``L``, ``to_end`` and their exponentials), ``hvd_ssm_scan_intra``
(``dt x``, ``scores``, ``mixed``, the within-chunk product, the skip ``D x``),
``hvd_ssm_scan_ends`` (each chunk's end state) and ``hvd_ssm_scan_carry`` (the
chunk-by-chunk matrix, ``entering``, ``from_start`` and its sum into ``y``).

Which form.  Where ``L`` is heavy beside what the scan cannot avoid reading
and writing, :func:`chunked_scan` hands the recurrence to a pair of Pallas
kernels (:func:`_scan`: ``hvd_ssm_scan_intra_fwd``, ``hvd_ssm_scan_intra_bwd``
in a device trace, under ``hvd_ssm_scan_intra``; ``hvd_ssm_scan_decay`` keeps
the cumulative sums, XLA's, and the other two stages name nothing).  A grid
step takes a chunk of a block of one group's heads, the chunks in order: it
computes ``scores`` once, and for each head builds ``L`` — masked BEFORE the
exponential, as :func:`_decay_between` masks it — and ``mixed`` in VMEM, adds
to the within-chunk product what the state that entered the chunk gives its
tokens and the skip, writes y, and moves the head's state, float32 in VMEM
from chunk to chunk, on by the chunk.  The same mathematics and the same
roundings: operands of every product in ``x``'s dtype, float32 sums,
everything that decays float32.  The backward is written out: from the
operands as stored, the state that entered each chunk (the one thing the
forward keeps: ``heads x head_dim x state`` float32 a chunk, a 128th of a
token's ``L``) and ``dy`` it rebuilds ``scores``, ``L`` and ``mixed`` on the
chip, the chunks last to first, and returns every cotangent.  x and y cross
with the TOKENS last, (batch, heads * head_dim, seq) — a token's ``dt`` and
decays then scale a head's channels as rows, and it is the layout XLA itself
gives a Mamba-2 mixer's activations while the scan is ``dot``s that contract
over tokens (:func:`held_for_the_scan` keeps it so).

:func:`lowered_plan` answers "kernels or products" from the shapes and the
dtype alone: the kernels where a token's ``L`` (``heads x chunk`` float32) is
at least twice the bytes the scan cannot avoid (x in, y out, B, C, dt) and
Mosaic tiles the shape — Granite's 64 KB against 25 KB; the products
elsewhere — Nemotron's 8 KB against 6.7 KB.  What the threshold rests on is
in PERF.md section 6, PR 65 (`tools/ssm_scan_sweep.py` times the two forms
alone in a program: 9.25 ms against 3.82 a Granite layer, forward with
backward; 2.02 against 1.67 at Nemotron's share, whose builder pins its
custom calls): kernels for the within-chunk stage ALONE lose in Granite's
step, because XLA builds ``L`` inside the product that consumes it and lets
the neighbouring stages' passes over ``y`` ride that product; taking every
pass over ``y`` into the kernels is what pays, by how much ``L`` and those
passes weigh.  The products stay the other answer and the tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.metrics import kernel_trace
from horovod_tpu.ops.attn_prep import _varying_as
from horovod_tpu.ops.moe import reduced_to_vma_of

# The scopes under a caller's own (``hvd_ssm_scan``), ``hvd_ssm_scan_<stage>``.
STAGES = ("decay", "intra", "ends", "carry")

_WIDE = dict(preferred_element_type=jnp.float32)
# One register's lanes: a chunk's tokens lie along whole registers.
LANES = 128
# The kernels take the scan where a token's ``L`` is at least this many times
# the bytes the scan cannot avoid (module docstring, "Which form").
_L_OVER_OPERANDS = 2.0
# Heads a grid step of the kernels walks (`tools/ssm_scan_sweep.py`, a
# Granite layer alone, forward / with backward: 8 1.61 / 3.88 ms, 16 1.57 /
# 3.82, 32 1.58 / 3.68 at twice the VMEM: my chip run, PR 65).
_HEADS_A_STEP = 16


def _decay_between(log_decay_cumsum):
    """``exp(c[i] - c[j])`` for ``i >= j`` and 0 above the diagonal, over the
    last axis of an inclusive cumulative sum ``c`` of log decays (<= 0 each).
    Masked BEFORE the exponential: above the diagonal the difference is
    positive and may overflow."""
    c = log_decay_cumsum
    n = c.shape[-1]
    difference = c[..., :, None] - c[..., None, :]
    lower = jnp.tril(jnp.ones((n, n), bool))
    return jnp.exp(jnp.where(lower, difference, -jnp.inf))


def lowered_plan(heads: int, groups: int, head_dim: int, state: int,
                 chunk: int, dtype) -> dict:
    """What carries :func:`chunked_scan`'s products at these shapes (a
    caller's LOCAL heads and groups), and what one call, forward and
    backward together, adds to a compiled step: ``{"scan": "kernels",
    "heads_a_step": n, "tpu_custom_call": 2}`` or ``{"scan": "products",
    "tpu_custom_call": 0}`` (module docstring, "Which form").  From the shapes
    and the dtype alone."""
    products = {"scan": "products", "tpu_custom_call": 0}
    itemsize = jnp.dtype(dtype).itemsize
    per_group = heads // groups
    decay_matrix = heads * chunk * 4
    operands = (heads * head_dim * (itemsize + 4)      # x in, y out
                + 2 * groups * state * itemsize + heads * 4)
    if decay_matrix < _L_OVER_OPERANDS * operands:
        return products
    # What Mosaic tiles: a chunk's tokens along whole registers' lanes, a
    # head's channels along whole registers' sublanes (16 of two bytes).
    if chunk % LANES or head_dim % (32 // itemsize):
        return products
    step = min(per_group, _HEADS_A_STEP)
    while per_group % step:
        step -= 1
    return {"scan": "kernels", "heads_a_step": step, "tpu_custom_call": 2}


def held_for_the_scan(projected, heads: int, groups: int, head_dim: int,
                      state: int, chunk: int):
    """A mixer's in-projection ``projected`` (batch, seq, channels) as it
    came, or — where :func:`lowered_plan` hands the scan to the kernels —
    with the TOKENS last in memory: the same array to JAX, the layout XLA's
    own assignment gives the mixer's activations while every product of the
    scan is a ``dot`` that contracts over a chunk's tokens.  The kernels read
    x and write y that way; left to itself before a custom call, the
    assignment keeps the projection's output channels-last and turns every
    array it meets in both (PERF.md section 6, PR 65: a step of two Granite
    mixers 112.1 ms so, 100.9 with the projection held)."""
    plan = lowered_plan(heads, groups, head_dim, state, chunk,
                        projected.dtype)
    if plan["scan"] != "kernels":
        return projected
    return with_layout_constraint(projected, Layout(major_to_minor=(0, 2, 1)))


# --- the scan as a pair of Pallas kernels ------------------------------------

def _dot(a, b, a_axis=1, b_axis=0):
    """``a`` and ``b`` (two axes each) contracted over one axis of each,
    summed in float32: (1, 0) ``a b``, (0, 0) ``a^T b``, (1, 1) ``a b^T``."""
    return lax.dot_general(a, b, (((a_axis,), (b_axis,)), ((), ())),
                           **_WIDE)


def _at_every_step(body):
    """``body()`` under a ``cond`` on what holds at every grid step: inside
    ``shard_map`` the interpreter lets a kernel's scratch and constants, which
    vary over no mesh axis, meet its operands, which do, only there (as
    ``ops.delta_rule._at_every_step``)."""
    pl.when(pl.program_id(3) >= 0)(body)


def _first_head(heads_a_step):
    """The grid step's first head among the layer's: a group's blocks one
    behind another, the groups likewise.  (Asked at a kernel's top: the
    interpreter knows the grid's position there only.)"""
    return (pl.program_id(1) * pl.num_programs(2)
            + pl.program_id(2)) * heads_a_step


def _of_head(cols, head):
    """Head ``head``'s lane of ``cols`` (chunk, every head of the layer)."""
    return lax.broadcasted_iota(jnp.int32, cols.shape, 1) == head


def _channels_of(head, head_dim):
    return pl.ds(pl.multiple_of(head * head_dim, head_dim), head_dim)


def _a_row(ref, head):
    return ref[pl.ds(head, 1), :]


def _over(scalar, matrix):
    """A (1, 1) ``scalar`` times ``matrix``, as a scalar (Mosaic broadcasts a
    vector along one axis at a time; ``ops.delta_rule._over_rows``)."""
    return scalar.sum() * matrix


def _chunk_of_a_head(head, first, head_dim, x_ref, dt_ref, rows_ref, cols,
                     scores):
    """What both directions compute of head ``head`` of the grid step's
    block: x (head_dim, chunk) as stored and float32, ``dt`` and the
    cumulative log decay ``w`` as rows (1, chunk), ``w`` at the chunk's end
    (1, 1), ``dt x`` rounded, ``L`` (chunk, chunk) float32 and ``mixed``
    rounded.  ``L[i, j] = exp(w[i] - w[j])`` for ``i >= j``: the head's row
    of ``w`` is a sublane's slice, its column a select and a sum of one term
    and zeros — exact — and the difference is masked BEFORE the exponential,
    as :func:`_decay_between` masks it."""
    chunk = cols.shape[0]
    x = x_ref[_channels_of(head, head_dim), :]
    x32 = x.astype(jnp.float32)
    dt, w = _a_row(dt_ref, head), _a_row(rows_ref, head)
    starting = jnp.where(_of_head(cols, first + head), cols, 0.0).sum(
        axis=1, keepdims=True)                                   # w[i]
    at = functools.partial(lax.broadcasted_iota, jnp.int32, (chunk, chunk))
    decays = jnp.exp(jnp.where(at(0) >= at(1), starting - w, -jnp.inf))
    return (x, x32, dt, w, w[:, chunk - 1:], (dt * x32).astype(x.dtype),
            decays, (scores * decays).astype(x.dtype))


def _scan_fwd_kernel(x_ref, dt_ref, rows_ref, cols_ref, b_ref, c_ref, d_ref,
                     y_ref, entered_ref, state, scores):
    """Grid ``(batch, groups, blocks of a group's heads, chunks)``, the
    chunks in order: the block's heads' states (heads * head_dim, state)
    float32 stay in ``state`` from a chunk to the next.  x and y are a
    chunk's tokens along the lanes and the block's heads' channels along the
    sublanes, so that a token's ``dt`` and decays are ROWS that scale every
    channel of a head.  A step computes ``scores = C B^T`` and walks its
    heads: y's channels are ``(dt x) mixed^T`` (within the chunk), ``(state
    C^T) exp(w)`` (what entered it) and the skip ``D x``; the state takes
    ``exp(w_end - w) (dt x)`` against B and decays by ``exp(w_end)``.
    Writes y and the state that ENTERED the chunk, which the backward
    keeps."""
    heads = rows_ref.shape[0]
    first, head_dim = _first_head(heads), x_ref.shape[0] // heads

    @pl.when(pl.program_id(3) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    @_at_every_step
    def _():
        cols, b, c = cols_ref[...], b_ref[...], c_ref[...]
        scores[...] = _dot(c, b, 1, 1)
        entered_ref[...] = state[...]

        def walk(head, done):
            channels = _channels_of(head, head_dim)
            x, x32, dt, w, end, dt_x, _, mixed = _chunk_of_a_head(
                head, first, head_dim, x_ref, dt_ref, rows_ref, cols,
                scores[...])
            entered = state[channels, :]
            y_ref[channels, :] = (
                _dot(dt_x, mixed, 1, 1)
                + _dot(entered.astype(x.dtype), c, 1, 1) * jnp.exp(w)
                + _a_row(d_ref, head) * x32)
            weighted = (jnp.exp(end - w) * dt_x.astype(jnp.float32)).astype(
                x.dtype)
            state[channels, :] = _over(jnp.exp(end), entered) + _dot(weighted,
                                                                     b)
            return done

        lax.fori_loop(0, heads, walk, None)


def _scan_bwd_kernel(x_ref, dt_ref, rows_ref, cols_ref, b_ref, c_ref, d_ref,
                     entered_ref, dy_ref, dx_ref, d_dt_ref, d_rows_ref,
                     d_cols_ref, db_ref, dc_ref, d_skip_ref, d_state, scores,
                     d_scores, d_b, d_c):
    """The same grid, the chunks last to first: ``d_state``, the cotangent
    of the state a chunk LEAVES, stays in VMEM.  Everything but the kept
    entering state is built again on the chip from the operands as stored;
    ``dy`` is rounded to x's dtype where a product reads it.  Written out,
    with ``g = dy exp(w)`` and ``G`` the state's cotangent: the state that
    entered takes ``g C + exp(w_end) G``; ``dt x`` takes ``dy mixed + (G
    B^T) exp(w_end - w)``; with ``through = (dy^T (dt x)) * L``, ``scores``
    takes the sum of ``through`` over the heads, B and C their shares of it
    and of the states' two products (float32 partial sums a block of heads,
    summed outside); ``w`` takes, as rows, ``dy . (state C^T) exp(w)``, less
    the column sums of ``through * scores`` and what ``exp(w_end - w)``
    carried, all of that again at the chunk's end; as columns, the row sums
    of ``through * scores``."""
    heads = rows_ref.shape[0]
    first, head_dim = _first_head(heads), x_ref.shape[0] // heads
    f32 = jnp.float32

    @pl.when(pl.program_id(3) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    @_at_every_step
    def _():
        cols, b, c = cols_ref[...], b_ref[...], c_ref[...]
        chunk = cols.shape[0]
        scores[...] = _dot(c, b, 1, 1)
        d_scores[...] = jnp.zeros_like(d_scores)
        d_b[...] = jnp.zeros_like(d_b)
        d_c[...] = jnp.zeros_like(d_c)

        def walk(head, d_cols):
            channels = _channels_of(head, head_dim)
            x, x32, dt, w, end, dt_x, decays, mixed = _chunk_of_a_head(
                head, first, head_dim, x_ref, dt_ref, rows_ref, cols,
                scores[...])
            dtype = x.dtype
            dy = dy_ref[channels, :]
            narrow = dy.astype(dtype)
            skip = _a_row(d_ref, head)
            d_skip_ref[pl.ds(head, 1), :] = (dy * x32).sum(axis=0,
                                                           keepdims=True)
            # What entered the chunk, through y.
            entered = entered_ref[channels, :]
            rounded, from_zero = entered.astype(dtype), jnp.exp(w)
            from_state = (dy * from_zero).astype(dtype)
            d_entered = _dot(from_state, c)
            d_c[...] += _dot(from_state, rounded, 0, 0)
            d_w = (dy * _dot(rounded, c, 1, 1)).sum(
                axis=0, keepdims=True) * from_zero
            # Within the chunk.
            d_dt_x = _dot(narrow, mixed)
            through = _dot(narrow, dt_x, 0, 0) * decays
            d_scores[...] += through
            pulled = through * scores[...]
            d_w -= pulled.sum(axis=0, keepdims=True)
            # The state the chunk leaves.
            left = d_state[channels, :]
            narrow_left = left.astype(dtype)
            to_end, carried = jnp.exp(end - w), jnp.exp(end)
            wide_dt_x = dt_x.astype(f32)
            d_weighted = _dot(narrow_left, b, 1, 1)
            d_b[...] += _dot((to_end * wide_dt_x).astype(dtype), narrow_left,
                             0, 0)
            d_dt_x += d_weighted * to_end
            by_end = (d_weighted * wide_dt_x).sum(axis=0,
                                                  keepdims=True) * to_end
            d_end = by_end.sum(axis=1, keepdims=True) + carried * (
                left * entered).sum(axis=1, keepdims=True).sum(
                    axis=0, keepdims=True)
            lane = lax.broadcasted_iota(jnp.int32, w.shape, 1)
            d_rows_ref[pl.ds(head, 1), :] = d_w - by_end + jnp.where(
                lane == chunk - 1, d_end, 0.0)
            d_state[channels, :] = _over(carried, left) + d_entered
            # dt x, rounded, from dt and x.
            dx_ref[channels, :] = (skip * dy + d_dt_x * dt).astype(
                dx_ref.dtype)
            d_dt_ref[pl.ds(head, 1), :] = (d_dt_x * x32).sum(axis=0,
                                                             keepdims=True)
            return d_cols + jnp.where(_of_head(cols, first + head),
                                      pulled.sum(axis=1, keepdims=True), 0.0)

        d_cols_ref[...] = lax.fori_loop(0, heads, walk, jnp.zeros_like(cols))
        through = d_scores[...].astype(b.dtype)
        dc_ref[...] = _dot(through, b) + d_c[...]
        db_ref[...] = _dot(through, c, 0, 0) + d_b[...]


@functools.partial(jax.jit, static_argnames=("backward", "interpret"))
def _scan_call(operands, backward, interpret):
    """``pl.pallas_call`` of the forward kernel over ``operands`` = (x (b,
    heads * head_dim, seq), ``dt`` and the chunks' cumulative log decays as
    rows (b, chunks, blocks, heads a block, chunk), the decays as columns (b,
    seq, heads), B and C (b, groups, seq, state), D along a row (blocks,
    heads a block, chunk)), or of the backward kernel over those, the kept entering states and
    ``dy``, named ``hvd_ssm_scan_intra_fwd`` and ``_bwd``: a chunk's block of
    each operand and result, backward the chunks last to first.  Jitted, so
    that a step's mixers trace and lower each kernel once
    (``ops.moe._tiled_call``)."""
    x, _, rows, cols, b = operands[:5]
    batch, chunks, blocks, heads, chunk = rows.shape
    groups, state = b.shape[1], b.shape[3]
    a_group = blocks // groups
    channels = x.shape[1] // blocks     # of a grid step's heads
    f32 = jnp.float32

    def spec(shape, index):
        """A block ``shape`` at ``index(b, block of heads, group, chunk)``."""
        def at(b, g, j, k):
            return index(b, g * a_group + j, g,
                         chunks - 1 - k if backward else k)
        return pl.BlockSpec(shape, at)

    wide = spec((None, channels, chunk), lambda b, j, g, k: (b, j, k))
    as_rows = spec((None, None, None, heads, chunk),
                   lambda b, j, g, k: (b, k, j, 0, 0))
    as_cols = spec((None, chunk, cols.shape[2]), lambda b, j, g, k: (b, k, 0))
    of_group = spec((None, None, chunk, state),
                    lambda b, j, g, k: (b, g, k, 0))
    a_head = spec((None, heads, chunk), lambda b, j, g, k: (j, 0, 0))
    states = spec((None, None, channels, state),
                  lambda b, j, g, k: (b, k, j, 0))
    given = [wide, as_rows, as_rows, as_cols, of_group, of_group, a_head]
    kept = jax.ShapeDtypeStruct((batch, chunks, x.shape[1], state), f32)
    matrix = pltpu.VMEM((chunk, chunk), f32)
    held = pltpu.VMEM((channels, state), f32)
    if backward:
        kernel, name, passes = _scan_bwd_kernel, "hvd_ssm_scan_intra_bwd", 3
        partial = jax.ShapeDtypeStruct((batch, blocks) + b.shape[2:], f32)
        in_specs = given + [states, wide]
        outputs = (x, operands[1], rows,
                   jax.ShapeDtypeStruct((batch, blocks) + cols.shape[1:],
                                        f32),
                   partial, partial, rows)
        a_block = spec((None, None, chunk, state),
                       lambda b, j, g, k: (b, j, k, 0))
        out_specs = [wide, as_rows, as_rows,
                     spec((None, None, chunk, cols.shape[2]),
                          lambda b, j, g, k: (b, j, k, 0)),
                     a_block, a_block, as_rows]
        scratch = [held, matrix, matrix,
                   pltpu.VMEM((chunk, state), f32),
                   pltpu.VMEM((chunk, state), f32)]
    else:
        kernel, name, passes = _scan_fwd_kernel, "hvd_ssm_scan_intra_fwd", 1
        in_specs, out_specs = given, [wide, states]
        outputs = (jax.ShapeDtypeStruct(x.shape, f32), kept)
        scratch = [held, matrix]
    # Inside shard_map the activations vary over the mapped axes and D, a
    # parameter, does not.
    vma, (skip,) = _varying_as(x, operands[6])
    operands = operands[:6] + (skip,) + operands[7:]
    call = pl.pallas_call(
        kernel, grid=(batch, groups, a_group, chunks), in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                   for t in outputs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * passes * batch * chunks * chunk * (
                blocks * chunk * state
                + x.shape[1] * (chunk + 2 * state)),
            transcendentals=batch * chunks * blocks * heads * chunk * chunk,
            bytes_accessed=sum(t.size * t.dtype.itemsize
                               for t in (*operands, *outputs))),
        interpret=interpret, name=name)
    with kernel_trace(name):
        return call(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan(x, dt, rows, cols, b, c, d, interpret):
    """y, float32 of x's shape (b, heads * head_dim, seq), from the
    operands as :func:`_scan_call` takes them: two kernels that hold a
    chunk's ``L`` and ``mixed`` and the states between chunks on the chip
    (module docstring, "Which form").  The backward keeps the operands and
    the state that entered each chunk, and nothing else."""
    return _scan_fwd(x, dt, rows, cols, b, c, d, interpret)[0]


def _scan_fwd(x, dt, rows, cols, b, c, d, interpret):
    operands = (x, dt, rows, cols, b, c, d)
    y, entered = _scan_call(operands, backward=False, interpret=interpret)
    return y, operands + (entered,)


def _scan_bwd(interpret, kept, dy):
    b = kept[4]
    dx, d_dt, d_rows, d_cols, db, dc, d_skip = _scan_call(
        kept + (dy,), backward=True, interpret=interpret)
    # A block of heads' shares of what the layer's heads share.
    return (dx, d_dt, d_rows, d_cols.sum(axis=1),
            *(t.reshape(b.shape[0], b.shape[1], -1, *b.shape[2:]).sum(
                axis=2).astype(b.dtype) for t in (db, dc)),
            reduced_to_vma_of(kept[6], d_skip.sum(axis=(0, 1))))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _scan_by_kernels(x, dt, A, B, C, D, chunk: int, heads_a_step: int):
    """:func:`chunked_scan` through :func:`_scan`: the cumulative log decays
    are XLA's, a few hundred numbers a token, and every product of the
    recurrence is the kernels'.  x and y cross with the TOKENS last, (b,
    heads * head_dim, seq): the layout XLA gives the mixer's activations
    around a scan of ``dot``s (:func:`held_for_the_scan`), in which a
    token's ``dt`` and decays scale a head's channels as rows."""
    batch, seq, heads, head_dim = x.shape
    groups, state = B.shape[2:]
    chunks, per_group = seq // chunk, heads // groups
    blocks = heads // heads_a_step
    f32 = jnp.float32
    by_block = (batch, chunks, blocks, heads_a_step, chunk)
    with jax.named_scope("hvd_ssm_scan_decay"):
        dtc = dt.astype(f32).reshape(batch, chunks, chunk, heads)
        dt_rows = dtc.transpose(0, 1, 3, 2)                  # (b, c, h, l)
        within = jnp.cumsum(dt_rows * A.astype(f32)[:, None], axis=-1)
        whole = within[..., -1]                              # (b, c, h)
    with jax.named_scope("hvd_ssm_scan_intra"):
        y = _scan(
            x.transpose(0, 2, 3, 1).reshape(batch, heads * head_dim, seq),
            dt_rows.reshape(by_block), within.reshape(by_block),
            within.transpose(0, 1, 3, 2).reshape(batch, seq, heads),
            *(jnp.moveaxis(t, 2, 1) for t in (B, C)),
            jnp.broadcast_to(D.astype(f32).reshape(blocks, heads_a_step, 1),
                             by_block[2:]),
            jax.default_backend() != "tpu")
        y = y.reshape(batch, heads, head_dim, seq).transpose(0, 3, 1, 2)
    return y, whole.reshape(batch, chunks, groups, per_group)


def chunked_scan(x, dt, A, B, C, D, chunk: int):
    """``y`` of the recurrence above for every token.

    ``x`` (batch, seq, heads, head_dim); ``dt`` (batch, seq, heads) float32,
    positive (the softplus already applied); ``A`` (heads,) float32, negative;
    ``B``, ``C`` (batch, seq, groups, state), head ``j`` reading group
    ``j // (heads / groups)``; ``D`` (heads,) float32.  ``seq`` is a multiple
    of ``chunk``.  Returns ``(y, whole)``: ``y`` float32 of ``x``'s shape, and
    the summed ``dt A`` of every chunk and head, float32 (batch, chunks,
    groups, heads per group) — ``exp`` of it is the share of the state
    entering a chunk that leaves it; where that underflows, nothing crosses
    the chunk."""
    batch, seq, heads, head_dim = x.shape
    groups, state = B.shape[2:]
    if seq % chunk or heads % groups:
        raise ValueError(f"chunked_scan: seq {seq} is not a multiple of "
                         f"chunk {chunk}, or {groups} groups do not divide "
                         f"{heads} heads")
    chunks, per_group = seq // chunk, heads // groups
    f32, wide = jnp.float32, dict(preferred_element_type=jnp.float32)
    plan = lowered_plan(heads, groups, head_dim, state, chunk, x.dtype)
    if plan["scan"] == "kernels":
        return _scan_by_kernels(x, dt, A, B, C, D, chunk,
                                plan["heads_a_step"])
    decay, intra, ends_of, carry = (
        functools.partial(jax.named_scope, "hvd_ssm_scan_" + stage)
        for stage in STAGES)
    # (batch, chunks, chunk, groups, heads per group, ...): a head's group is
    # an axis of its own, so that no B or C is repeated per head.
    xc = x.reshape(batch, chunks, chunk, groups, per_group, head_dim)
    dtc = dt.astype(f32).reshape(batch, chunks, chunk, groups, per_group)
    Bc = B.reshape(batch, chunks, chunk, groups, state)
    Cc = C.reshape(batch, chunks, chunk, groups, state)
    with decay():
        log_decay = dtc * A.astype(f32).reshape(groups, per_group)
        # (b, c, g, r, l): inclusive sums along the chunk.
        within = jnp.cumsum(log_decay.transpose(0, 1, 3, 4, 2), axis=-1)
        whole = within[..., -1]                              # (b, c, g, r)
    # Within a chunk: y[i] = sum_{j <= i} (C_i . B_j) L[i, j] dt_j x_j.
    with intra():
        dt_x = (dtc[..., None] * xc.astype(f32)).astype(x.dtype)
        scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, **wide)
        scores = scores[:, :, :, None]             # a group's, for its heads
    with decay():
        between = _decay_between(within)
    with intra():
        mixed = (scores * between).astype(x.dtype)
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mixed, dt_x, **wide)

    # Each chunk's own end state: sum_j exp(whole - within[j]) B_j (x) dt_j x_j.
    with decay():
        to_end = jnp.exp(whole[..., None] - within)          # (b, c, g, r, l)
    with ends_of():
        weighted = (to_end.transpose(0, 1, 4, 2, 3)[..., None]
                    * dt_x.astype(f32)).astype(x.dtype)
        ends = jnp.einsum("bcjgn,bcjgrp->bcgrpn", Bc, weighted, **wide)

    # The state entering chunk c: sum_{c' < c} exp(sum_{c' < k < c} whole_k)
    # ends[c'].  One (chunks, chunks) matrix a head, strictly lower: with
    # P = [0, cumsum(whole)], the sum is P[c] - P[c' + 1].
    with carry():
        across = jnp.cumsum(whole.transpose(0, 2, 3, 1), axis=-1)  # (b,g,r,c)
        carried = _decay_between(
            jnp.pad(across, [(0, 0)] * 3 + [(1, 0)]))[..., :-1, 1:]
        entering = jnp.einsum("bgrcz,bzgrpn->bcgrpn", carried, ends,
                              precision="highest")

        # What the entering state adds to token i: exp(within[i]) C_i . h.
        from_start = jnp.einsum("bcign,bcgrpn->bcigrp", Cc,
                                entering.astype(x.dtype), **wide)
    with decay():
        from_zero = jnp.exp(within).transpose(0, 1, 4, 2, 3)[..., None]
    with carry():
        y = y + from_start * from_zero
    with intra():
        y = y + D.astype(f32).reshape(groups, per_group, 1) * xc.astype(f32)
    return y.reshape(x.shape), whole
