"""The gated delta rule of a linear-attention layer as matrix products over
chunks, in two forms that share the algebra of the solve and the recurrence:
a decay a CHANNEL of the key (Kimi delta attention, KDA: Kimi Linear,
arXiv:2510.26692) and a decay a HEAD over grouped heads (Gated DeltaNet,
arXiv:2412.06464; below, "A decay a head").

Per head, with a state ``S`` of ``d_k x d_v``, a decay ``alpha_t`` a CHANNEL of
the key (``log_alpha_t <= 0``, ``d_k`` of them) and a step ``beta_t``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The transition is no diagonal, so ``ops/ssm.py``'s closed form between chunks
does not hold.  Writing ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T`` with
``u_t = beta_t (v_t - S_{t-1}^T Diag(alpha_t) k_t)``, and ``G_t`` the product
of the decays from the chunk's start to token t, a chunk of ``C`` tokens that
the state ``S`` enters is (the WY / UT transform; rows are tokens):

    A[t, s] = beta_t (k_t . G_t) . (k_s / G_s)   for s < t, else 0
    T = (I + A)^-1                               unit lower triangular
    W = T (beta . K . G),   U0 = T (beta . V)
    U = U0 - W S
    O = (Q . G) S + tril((Q . G) (K / G)^T) U
    S' = Diag(G_C) S + (K . G_C / G)^T U

:func:`chunked_delta_rule` computes ``A``, ``T``, ``W``, ``U0`` and the masked
``Q K^T`` for every chunk at once.  Only ``S`` is a true recurrence, and only
``S`` is carried from chunk to chunk.  Everything outside the carry and the
solve is differentiable by autodiff.

The solve, in two forms.  A decay a channel (:func:`_solved`) is XLA's:
``T`` by :func:`_unit_lower_inverse` (a ``jax.custom_vjp`` of fifteen einsums
over the diagonal sub-blocks' rows and three merges that pad and concatenate
the growing inverse) and two einsums at ``precision="highest"`` against
``beta K G`` and ``beta V`` written out in float32; autodiff transposes the
einsums.  A decay a head (:func:`_head_solve`, a ``jax.custom_vjp``) is a
pair of Pallas kernels, as the carry below is, that hold a chunk's ``T`` on
the chip.  The grid is (batch, key heads, groups of chunks), every axis
parallel; a grid step holds :data:`_SOLVES_A_STEP` value heads' chunks and
works on all of them in every operation, because a chunk's solve is a chain
of dependent steps and a bundle of the chip's instructions holds steps of
many.  ``A`` reaches the forward kernel TRANSPOSED over its two chunk axes,
and the backward kernel writes its cotangent transposed: XLA lays the (C, C)
matrices around the stage out with those two axes swapped, and a kernel's
operand is row-major, so the transposed matrix is the one it hands over
without a copy (0.2 ms a Qwen3-Next layer each way otherwise).  A grid step
of the forward kernel reads the chunks' ``A^T``, a KEY head's k as
stored (ONCE for its value heads: no float32 copy a value head is written,
nor ``beta k G`` nor ``beta v``), a value head's v as stored, ``beta`` and
the decay from the chunk's start; in VMEM it finds ``T``
(:func:`_chunk_inverses`: the rows inside every diagonal sub-block on the VPU
in plain float32, the sub-blocks' coefficients by a lane gather, then the
sub-blocks two at a time by products), ``W = (T . beta G) K`` rounded to q's
dtype and ``U0 = (T . beta) V`` float32 — the vectors scale ``T``'s columns,
so k and v meet the MXU as stored — and writes ``T``, ``W``, ``U0``.  The
backward kernel, on the same grid, reads the kept ``T``, ``dW``, ``dU0`` and
k, v and the two vectors (not ``A``), and writes every cotangent: ``A``'s
``-T^T g T^T`` with ``g = (dW K^T) . beta G + (dU0 V^T) . beta``, k's ``(T .
beta G)^T dW`` summed over the key head's value heads in float32, v's ``(T .
beta)^T dU0``, and the vectors' as column sums of ``T . (dW K^T)`` and ``T .
(dU0 V^T)``.  Nothing of the stage is left to autodiff.  Every product is
what ``precision="highest"`` computes, by hand (:func:`_exact_dot`): a
float32 operand is three bfloat16 terms whose sum it is, a bfloat16 operand
(k, v, ``dW``) is its own one term, and the products of terms i and j with ``i
+ j < 3`` are single passes of the MXU summed in float32 — six for two
float32 operands, three where one is bfloat16, and nothing dropped that
``highest`` keeps.  The channel form is not on these kernels yet for one
reason: ``benchmark/builders/ling_lm.py`` pins its step's count of custom
calls exactly, and a change of the program may not edit the benchmark
(``ROADMAP.md`` S11(c2): its decay a channel scales ``K``'s columns where a
head's scales ``T``'s, which the kernels would take by shape as the carry's
do; then :func:`_solved`, :func:`_unit_lower_inverse` and
:func:`_diagonal_inverses` go).

The carry, in two forms.  A decay a channel (:func:`_carry`, a
``jax.custom_vjp``) is XLA products and two loops a layer, no kernel of ours:
a ``lax.scan`` of two products a step (``U``, then ``S'``; a ``while`` in the
compiled program) leaves the state that entered each chunk, and ``O`` is two
products over all chunks at once from those states.  An iteration of the
loop costs what its operations cost to launch, a microsecond each whatever
they compute, so what need not be in it is not.  Its backward pass has the
same shape: what ``O``'s cotangent gives ``U`` and the state is found for all
chunks at once, a reverse scan of two products a step carries the state's
cotangent, and the operands' cotangents are products over all chunks.  It
keeps the state that entered each chunk and computes ``U`` again.  (The Ling
cell's ``correct`` pins this form's ``while``s and custom calls: ``PERF.md``
section 7.)

A decay a head (:func:`_head_carry`, a ``jax.custom_vjp`` too) is a pair of
Pallas kernels, compiled by Mosaic on a TPU and run by the Pallas interpreter
elsewhere, that hold the state on the chip.  The grid is (batch, key heads,
chunks), the chunks its sequential axis; a key head's value heads' states,
(value heads a key head, d_k, d_v) float32, are VMEM scratch, zero at the
first chunk.  A grid step of the forward kernel reads the chunk's ``W``,
``U0`` and masked ``Q K^T`` a value head, its ``q`` and ``k`` a KEY head and
three vectors of decays (from the chunk's start to each token, from each
token to its end, over the whole chunk), and for each value head computes

    U  = U0 - W S
    O  = (Q . from_start) S + QK U
    S' = carried S + (K . end_decay)^T U

with ``S``, ``U`` and the decayed ``Q`` and ``K`` rounded to ``q``'s dtype
where a product reads them, as the loops round them; it writes ``O`` and the
float32 state that entered the chunk.  The backward kernel walks the chunks
last to first with the state's cotangent in VMEM, computes ``U`` once from
the kept state, and writes every operand's cotangent: ``q``'s and ``k``'s
summed over the key head's value heads in float32 and rounded once, the
decay's from the float32 state.  Nothing is written a value head that is a
key head's, no state is moved by a ``dynamic-update-slice``, and there is no
product over all chunks outside the kernels.  ``carried`` may be one decay a
head, (…, 1), or one a channel, (…, d_k): the kernels take either by shape.
``d_k`` and ``d_v`` differ where a model's do (Olmo-Hybrid: 96 and 192): a
block is its true width, and what Mosaic leaves empty of a 128-lane tile — a
quarter of every q, k, w block and of every v, U, O block and state — is
computed and moved with the rest.

``k_s / G_s`` is never formed: over a chunk of 64 tokens at the gate's bound of
-5 a step it is ``e^320``.  A chunk is cut into sub-blocks of
:data:`SUB_BLOCK` tokens and every ratio ``G_t / G_s`` goes through the decay
at the first token of t's sub-block: ``G_t / G_ref <= 1``, and ``G_ref / G_s``
is at most 1 for an earlier sub-block and at most ``e^75`` inside t's own (15
steps of at most 5), which float32 and bfloat16 hold.  A log-decay under about
-5.8 a step would overflow there: the caller's gate bounds it.  The rows of
a sub-block meet the columns of their own and of earlier sub-blocks alone:
the ratios against later ones, six sub-block pairs of sixteen, would be
masked and are not computed.

Every log of a product of decays is a sum of log-decays (within a sub-block,
to its end, over whole sub-blocks between), never a difference of two
cumulative sums: those reach -320 in a chunk, where float32's spacing is 3e-5.

A decay a head (``log_alpha`` of (batch, seq, value heads)): ``alpha_t`` is a
scalar, ``Diag(alpha_t) = alpha_t I``, and the decays leave the products.  With
``c_t`` the sum of the head's log-decays from the chunk's start through t,

    A[t, s] = beta_t exp(c_t - c_s) (k_t . k_s)              for s < t
    tril((Q . G) (K / G)^T)[t, s] = exp(c_t - c_s) (q_t . k_s)

so ``K K^T`` and ``Q K^T`` are ONE plain product a chunk each, times a (C, C)
matrix of exponentials: no sub-blocks, no reference, no ratio.  And the heads
are grouped: ``q`` and ``k`` have ``key heads``, ``v``, the decay and ``beta``
``value heads``, value head j reading key head ``j // (value heads / key
heads)``; the two products depend on the key head alone and are computed once a
KEY head; the decay matrix, ``beta``, the solve and the recurrence are a value
head's (the decayed ``Q`` and ``K`` the recurrence reads are a value head's
too: the decay is).  Every exponent is a SUM of log-decays, never a difference
of two cumulative sums and never over 0: ``c_t`` a cumulative sum from the
chunk's start, the decay to the chunk's end a cumulative sum from its end, and
the (C, C) exponents ``sum_{s < i <= t} log_alpha_i`` a product of the lower
triangle of ones with the (C, C) matrix that holds ``log_alpha_i`` in row i of
every column ``s < i``, in full precision.  This gate has no bound, and needs
none: float32 holds a sum of C terms to ``C 2^-24`` of the largest partial
sum, so a pair of tokens with nothing but small decays between them has its
exponent to the size of those decays whatever the rest of the chunk holds
(at -20 a step a chunk of 64 sums to -1,280: an absolute 1e-4 there, in an
exponential that is 0 either way), and ``exp`` of a large negative sum
underflows to 0, which is what crosses.  A length that is no multiple of the
chunk is padded with tokens that change nothing (log-decay 0, ``beta`` 0, zero
q, k, v) and cut back.

Four stages, each under a ``jax.named_scope`` of its own beneath the caller's
(``hvd_kda_scan`` or ``hvd_gdn_scan`` in ``models/delta.py``; the stages' names
are the caller's ``scope`` with ``_decays``, ``_chunk``, ``_solve``,
``_carry`` behind), so that a device trace tells them
apart forward and backward; every operation of :func:`chunked_delta_rule` is
under exactly one:

* ``hvd_kda_scan_decays`` — the sums of log-decays, their exponentials and the
  ratios against a sub-block's reference;
* ``hvd_kda_scan_chunk`` — the products inside a chunk: the views of q, k, v
  and beta by chunk, ``K K^T`` and ``Q K^T`` through the sub-blocks
  (``against_earlier``), and the decayed ``Q`` and ``K`` the recurrence reads;
* ``hvd_kda_scan_solve`` — ``T`` (:func:`_unit_lower_inverse`, forward and its
  written-out backward) and ``W``, ``U0``; or :func:`_head_solve`: the two
  kernels, named ``<scope>_solve_fwd`` and ``<scope>_solve_bwd``;
* ``hvd_kda_scan_carry`` — :func:`_carry`: the two ``while``s and their
  bodies and the products over all chunks around them, or
  :func:`_head_carry`: the two kernels, named ``<scope>_carry_fwd`` and
  ``<scope>_carry_bwd``; and the move of ``o`` back to tokens.

:func:`lowered_plan` says what a call contributes to a compiled training
step (its loops and kernels), as ``ops.attention._bwd_plan`` says of flash.

Float32: the summed log-decays, their exponentials, ``T`` (by forward
substitution, exact products: XLA's at ``highest``, or in the head form's
kernels plain float32 on the VPU and sums of bfloat16 terms on the MXU that
drop nothing ``highest`` keeps), ``W`` before it is rounded, ``U0``, every
cotangent of the solve, ``U`` and the state between chunks.  The operands of
the other products are rounded to ``q``'s dtype and accumulated in float32, as
every matmul of the model is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.metrics import kernel_trace

SUB_BLOCK = 16   # tokens whose decay ratios are taken against one reference


def _exact(a, b):
    return jnp.matmul(a, b, precision="highest")


def _diagonal_inverses(diagonal):
    """``(I + d)^-1`` of every strictly lower triangular ``d`` of the last two
    axes by forward substitution, ``row r = e_r - d[r, :r] . rows[:r]`` in
    full precision.  The rows are gathered in a list and stacked once: a row
    written into its block (``.at[..., r, :].add``) copies every block, at a
    sixteenth of a vector register's lanes, fifteen times."""
    sub = diagonal.shape[-1]
    eye = jnp.eye(sub, dtype=diagonal.dtype)
    rows = [jnp.broadcast_to(eye[0], diagonal.shape[:-1])]
    for r in range(1, sub):
        rows.append(eye[r] - jnp.einsum(
            "...j,...jk->...k", diagonal[..., r, :r], jnp.stack(rows, -2),
            precision="highest"))
    return jnp.stack(rows, -2)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` strictly lower triangular over its last two
    axes (float32, a multiple of :data:`SUB_BLOCK` rows or fewer than that), by
    forward substitution: row by row inside each diagonal sub-block, then
    sub-block by sub-block (``[[T, 0], [-T_b a_b T, T_b]]``), every product
    in full precision.  (The Neumann product ``prod_j (I + (-a)^(2^j))`` is
    exact too, and useless: with keys that resemble one another the powers of
    ``a`` reach 1e8 before they cancel.)  Its backward pass is the inverse's
    own, ``-T^T g T^T``, not autodiff through the substitution."""
    size = a.shape[-1]
    sub = min(SUB_BLOCK, size)
    corners = range(0, size, sub)
    inverses = _diagonal_inverses(
        jnp.stack([a[..., c:c + sub, c:c + sub] for c in corners], -3))
    inverse = inverses[..., 0, :, :]
    for block, c in enumerate(corners):
        if block:
            own = inverses[..., block, :, :]
            below = -_exact(_exact(own, a[..., c:c + sub, :c]), inverse)
            inverse = jnp.concatenate([
                jnp.pad(inverse, [(0, 0)] * (a.ndim - 1) + [(0, sub)]),
                jnp.concatenate([below, own], axis=-1)], axis=-2)
    return inverse


def _inverse_fwd(a):
    inverse = _unit_lower_inverse(a)
    return inverse, inverse


def _inverse_bwd(inverse, g):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_exact(_exact(transposed, g), transposed),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


_WIDE = dict(preferred_element_type=jnp.float32)


def _by_step(*operands):                # (b, n, ...) -> (n, b, ...)
    return tuple(jnp.moveaxis(t, 1, 0) for t in operands)


def _entered_states(w, u0, k_end, carried):
    """The state that enters each chunk, (b, n, h, d_k, d_v) float32, from a
    zero state: the one part of the rule that is a recurrence, a ``lax.scan``
    of two products a step (``U`` of the chunk, then the state it leaves)."""
    dtype = w.dtype

    def chunk_step(state, inputs):
        w, u0, k_end, carried = inputs
        u = u0 - jnp.einsum("bhtc,bhcv->bhtv", w, state.astype(dtype),
                            **_WIDE)
        left = carried[..., None] * state + jnp.einsum(
            "bhtc,bhtv->bhcv", k_end, u.astype(dtype), **_WIDE)
        return left, state

    batch, _, heads, _, d_k = w.shape
    start = jnp.zeros((batch, heads, d_k, u0.shape[-1]), jnp.float32)
    varying = tuple(jax.typeof(w).vma)
    if varying:  # inside shard_map the carry varies as the inputs do
        start = lax.pcast(start, varying, to="varying")
    return _by_step(lax.scan(chunk_step, start,
                             _by_step(w, u0, k_end, carried))[1])[0]


def _rounded_states_and_u(w, u0, entered):
    """What the products around the recurrence read of a chunk, for every
    chunk at once: the state that entered it and its ``U``, both rounded."""
    narrow = entered.astype(w.dtype)
    u = u0 - jnp.einsum("bnhtc,bnhcv->bnhtv", w, narrow, **_WIDE)
    return narrow, u.astype(w.dtype)


@jax.custom_vjp
def _carry(w, u0, q_in, qk, k_end, carried):
    """``O`` of every chunk, (b, n, h, C, d_v) float32, from the chunks' own
    operands (module docstring), each (b, n, h, ...), from a zero state.
    ``carried``, the decay over a whole chunk, is (b, n, h, d_k) a channel or
    (b, n, h, 1) a head.

    Only the state is carried from chunk to chunk (:func:`_entered_states`);
    ``O`` is two products over all chunks at once from the states so found.
    The backward pass is written out the same way: what ``O``'s cotangent
    gives ``U`` and the state without knowing the state's own cotangent is
    computed for all chunks at once, a second scan of two products a step
    carries the state's cotangent from the last chunk to the first, and the
    operands' cotangents are products over all chunks after it.  Kept for it:
    the operands and the state that entered each chunk; ``U`` is computed
    again."""
    return _carry_fwd(w, u0, q_in, qk, k_end, carried)[0]


def _carry_fwd(w, u0, q_in, qk, k_end, carried):
    entered = _entered_states(w, u0, k_end, carried)
    narrow, rounded = _rounded_states_and_u(w, u0, entered)
    o = jnp.einsum("bnhtc,bnhcv->bnhtv", q_in, narrow, **_WIDE) \
        + jnp.einsum("bnhts,bnhsv->bnhtv", qk, rounded, **_WIDE)
    return o, (w, u0, q_in, qk, k_end, carried, entered)


def _carry_bwd(kept, d_o):
    w, u0, q_in, qk, k_end, carried, entered = kept
    dtype = w.dtype
    narrow, rounded = _rounded_states_and_u(w, u0, entered)
    d_o = d_o.astype(dtype)
    d_q_in = jnp.einsum("bnhtv,bnhcv->bnhtc", d_o, narrow, **_WIDE)
    d_qk = jnp.einsum("bnhtv,bnhsv->bnhts", d_o, rounded, **_WIDE)
    u_from_o = jnp.einsum("bnhts,bnhtv->bnhsv", qk, d_o, **_WIDE)
    state_from_o = jnp.einsum("bnhtc,bnhtv->bnhcv", q_in, d_o, **_WIDE)

    def chunk_step(d_left, inputs):     # d_left: of the state a chunk leaves
        w, k_end, carried, u_from_o, state_from_o = inputs
        d_u = u_from_o + jnp.einsum("bhtc,bhcv->bhtv", k_end,
                                    d_left.astype(dtype), **_WIDE)
        d_state = carried[..., None] * d_left + state_from_o - jnp.einsum(
            "bhtc,bhtv->bhcv", w, d_u.astype(dtype), **_WIDE)
        return d_state, (d_u, d_left)

    d_u, d_left = _by_step(*lax.scan(
        chunk_step, jnp.zeros_like(entered[:, 0]),
        _by_step(w, k_end, carried, u_from_o, state_from_o), reverse=True)[1])
    d_w = -jnp.einsum("bnhtv,bnhcv->bnhtc", d_u.astype(dtype), narrow,
                      **_WIDE)
    d_k_end = jnp.einsum("bnhtv,bnhcv->bnhtc", rounded, d_left.astype(dtype),
                         **_WIDE)
    d_carried = (d_left * entered).sum(axis=-1)
    if carried.shape[-1] == 1:          # a decay a head: one for all channels
        d_carried = d_carried.sum(axis=-1, keepdims=True)
    return (d_w.astype(dtype), d_u, d_q_in.astype(dtype), d_qk.astype(dtype),
            d_k_end.astype(dtype), d_carried)


_carry.defvjp(_carry_fwd, _carry_bwd)


# --- a decay a head: the recurrence as a pair of Pallas kernels -------------

def _dot(a, b, a_axis=1, b_axis=0):
    """``a`` and ``b`` (two axes each) contracted over one axis of each,
    summed in float32: (1, 0) ``a b``, (0, 0) ``a^T b``, (1, 1) ``a b^T``."""
    return lax.dot_general(a, b, (((a_axis,), (b_axis,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _turned(vector):
    """A (1, n) row as the (n, 1) column or the column as the row, exactly (a
    sum of one term and zeros): tokens and channels lie along the sublanes
    of what a decay multiplies, and a vector of them is stored along the
    lanes.  A vector of one element is either already."""
    n = max(vector.shape)
    if n == 1:
        return vector
    along = vector.shape.index(n)
    diagonal = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where(diagonal, vector, 0.0).sum(axis=along, keepdims=True)


def _over_rows(carried):
    """A value head's decay over a chunk, (1, 1) or a channel's (1, d_k), as
    what multiplies the rows of a (d_k, d_v) state: a single decay as a
    scalar (Mosaic broadcasts a vector along one axis at a time)."""
    return carried.sum() if carried.size == 1 else _turned(carried)


def _at_every_step(body):
    """``body()`` under a ``cond`` on what holds at every grid step: inside
    ``shard_map`` the interpreter lets a kernel's scratch, which varies over
    no mesh axis, meet its operands, which do, only there (as
    ``ops.attention._when_live``)."""
    pl.when(pl.program_id(2) >= 0)(body)


def _chunk_operands(j, w_ref, u0_ref, q, k, start_ref, end_ref, state):
    """What both directions compute of value head ``j`` of the grid step's
    chunk from the float32 ``state`` that entered it: the rounded state and
    ``U``, the decayed ``Q`` and ``K`` (rounded as the products read them),
    and the two decays as columns."""
    dtype = w_ref.dtype
    narrow = state.astype(dtype)
    u = u0_ref[j] - _dot(w_ref[j], narrow)
    start, end = (_turned(ref[j:j + 1, :]) for ref in (start_ref, end_ref))
    return (narrow, u.astype(dtype), (q * start).astype(dtype),
            (k * end).astype(dtype), start, end)


def _carry_fwd_kernel(w_ref, u0_ref, qk_ref, q_ref, k_ref, start_ref, end_ref,
                      carried_ref, o_ref, entered_ref, state):
    """Grid ``(batch, key heads, chunks)``, the chunks in order: a key head's
    value heads' states (per_key, d_k, d_v) float32 stay in ``state`` from a
    chunk to the next.  Writes ``O`` of the chunk and the state that entered
    it."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    @_at_every_step
    def _():
        q, k = (ref[...].astype(jnp.float32) for ref in (q_ref, k_ref))
        for j in range(state.shape[0]):
            entered = state[j]
            entered_ref[j] = entered
            narrow, rounded, q_in, k_end, _, _ = _chunk_operands(
                j, w_ref, u0_ref, q, k, start_ref, end_ref, entered)
            o_ref[j] = _dot(q_in, narrow) + _dot(qk_ref[j], rounded)
            state[j] = _over_rows(carried_ref[j:j + 1, :]) * entered \
                + _dot(k_end, rounded, 0, 0)


def _carry_bwd_kernel(w_ref, u0_ref, qk_ref, q_ref, k_ref, start_ref, end_ref,
                      carried_ref, entered_ref, d_o_ref, d_w_ref, d_u0_ref,
                      d_qk_ref, d_q_ref, d_k_ref, d_start_ref, d_end_ref,
                      d_carried_ref, d_left):
    """The same grid, the chunks last to first: ``d_left``, the cotangent of
    the state a chunk leaves, (per_key, d_k, d_v) float32, stays in VMEM.
    ``U`` is computed once, from the kept state; ``q``'s and ``k``'s
    cotangents are the key head's, summed over its value heads here."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_left[...] = jnp.zeros_like(d_left)

    @_at_every_step
    def _():
        dtype = w_ref.dtype
        q, k = (ref[...].astype(jnp.float32) for ref in (q_ref, k_ref))
        d_q, d_k = jnp.zeros_like(q), jnp.zeros_like(k)
        for j in range(d_left.shape[0]):
            entered, left = entered_ref[j], d_left[j]
            narrow, rounded, q_in, k_end, start, end = _chunk_operands(
                j, w_ref, u0_ref, q, k, start_ref, end_ref, entered)
            d_o, narrow_left = d_o_ref[j].astype(dtype), left.astype(dtype)
            d_u = _dot(qk_ref[j], d_o, 0, 0) + _dot(k_end, narrow_left)
            d_u0_ref[j] = d_u
            d_u = d_u.astype(dtype)
            d_w_ref[j] = (-_dot(d_u, narrow, 1, 1)).astype(dtype)
            d_qk_ref[j] = _dot(d_o, rounded, 1, 1).astype(dtype)
            d_q_in = _dot(d_o, narrow, 1, 1)
            d_k_end = _dot(rounded, narrow_left, 1, 1)
            d_q, d_k = d_q + d_q_in * start, d_k + d_k_end * end
            d_start_ref[j:j + 1, :] = _turned(
                (d_q_in * q).sum(axis=1, keepdims=True))
            d_end_ref[j:j + 1, :] = _turned(
                (d_k_end * k).sum(axis=1, keepdims=True))
            # d_carried from the float32 state: a channel's, or their sum.
            by_channel = (left * entered).sum(axis=1, keepdims=True)
            d_carried_ref[j:j + 1, :] = (
                by_channel.sum(axis=0, keepdims=True)
                if carried_ref.shape[1] == 1 else _turned(by_channel))
            d_left[j] = _over_rows(carried_ref[j:j + 1, :]) * left \
                + _dot(q_in, d_o, 0, 0) - _dot(w_ref[j], d_u, 0, 0)
        d_q_ref[...] = d_q.astype(dtype)
        d_k_ref[...] = d_k.astype(dtype)


def _carry_call(scope, backward, operands, outputs, interpret):
    """``pl.pallas_call`` of the carry's forward or backward kernel, named
    ``<scope>_fwd`` or ``<scope>_bwd``, over the grid (batch, key heads,
    chunks), backward the chunks last to first: a key head's block of each of
    ``operands`` and ``outputs`` (shapes and dtypes), every one
    (b, n, g, ...)."""
    batch, chunks, key_heads, per_key, chunk, d_k = operands[0].shape
    d_v = operands[1].shape[-1]
    # (C, d_k, d_v) and (C, C, d_v) products a value head's chunk takes: the
    # compiler's count of the step's operations cannot see into a kernel.
    kernel, name, products = (
        (_carry_bwd_kernel, f"{scope}_bwd", (7, 2)) if backward
        else (_carry_fwd_kernel, f"{scope}_fwd", (3, 1)))

    def spec(t):
        behind = (0,) * (t.ndim - 3)
        return pl.BlockSpec(
            (None, None, None) + t.shape[3:],
            (lambda b, g, n: (b, chunks - 1 - n, g) + behind) if backward
            else (lambda b, g, n: (b, n, g) + behind))

    vma = jax.typeof(operands[0]).vma   # inside shard_map: as the inputs vary
    macs = chunk * d_v * (products[0] * d_k + products[1] * chunk)
    call = pl.pallas_call(
        kernel, grid=(batch, key_heads, chunks),
        in_specs=[spec(t) for t in operands],
        out_specs=[spec(t) for t in outputs],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                   for t in outputs],
        scratch_shapes=[pltpu.VMEM((per_key, d_k, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * chunks * key_heads * per_key * macs,
            transcendentals=0,
            bytes_accessed=sum(t.size * t.dtype.itemsize
                               for t in (*operands, *outputs))),
        interpret=interpret, name=name)
    with kernel_trace(name):
        return call(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _head_carry(w, u0, qk, q, k, from_start, end_decay, carried, scope,
                interpret):
    """:func:`_carry` of the head form, as two kernels that hold the state on
    the chip (module docstring, "The carry, in two forms"): ``O`` of every
    chunk, (b, n, h, C, d_v) float32, from ``w``, ``u0``, ``qk`` a value head
    (b, n, h, C, .), ``q`` and ``k`` a KEY head (b, n, g, C, d_k) and the
    value heads' decays ``from_start``, ``end_decay`` (b, n, h, C) and
    ``carried`` (b, n, h, 1) float32.  ``scope`` names the kernels
    (``<scope>_fwd``, ``<scope>_bwd``)."""
    return _head_carry_fwd(w, u0, qk, q, k, from_start, end_decay, carried,
                           scope, interpret)[0]


def _by_key_head(q, operands):
    """A value head's arrays (b, n, h, ...) as (b, n, g, per_key, ...)."""
    batch, chunks, key_heads = q.shape[:3]
    return [t.reshape(batch, chunks, key_heads, -1, *t.shape[3:])
            for t in operands]


def _by_value_head(t):
    """A key head's (b, n, g, per_key, ...) as its value heads': (b, n, h,
    ...)."""
    return t.reshape(t.shape[:2] + (-1,) + t.shape[4:])


def _head_carry_fwd(w, u0, qk, q, k, from_start, end_decay, carried, scope,
                    interpret):
    w, u0, qk, from_start, end_decay, carried = _by_key_head(
        q, (w, u0, qk, from_start, end_decay, carried))
    operands = (w, u0, qk, q, k, from_start, end_decay, carried)
    entered = jax.ShapeDtypeStruct(w.shape[:4] + (w.shape[-1], u0.shape[-1]),
                                   jnp.float32)
    o, entered = _carry_call(scope, False, operands, (u0, entered), interpret)
    return _by_value_head(o), operands + (entered,)


def _head_carry_bwd(scope, interpret, kept, d_o):
    operands = kept[:-1]                # a cotangent each, shaped as it is
    d_o, = _by_key_head(operands[3], (d_o,))
    cotangents = _carry_call(scope, True, kept + (d_o,), operands, interpret)
    merged = [_by_value_head(t) for t in cotangents]
    merged[3:5] = cotangents[3:5]       # q's and k's are a key head's
    return tuple(merged)


_head_carry.defvjp(_head_carry_fwd, _head_carry_bwd)


# --- a decay a head: the solve as a pair of Pallas kernels -------------------

def _terms(x):
    """``x`` as bfloat16 arrays whose sum is ``x`` exactly: a bfloat16 array
    is its own, a float32 one three (24 bits of mantissa, eight a term)."""
    if x.dtype == jnp.bfloat16:
        return (x,)
    x, terms = x.astype(jnp.float32), []
    for _ in range(3):
        terms.append(x.astype(jnp.bfloat16))
        x = x - terms[-1].astype(jnp.float32)
    return tuple(terms)


def _exact_dot(a, b, a_axis=1, b_axis=0):
    """``a`` and ``b``, a batch of matrices each (m, ., .) float32 or
    bfloat16, contracted over one axis of each matrix as :func:`_dot` does,
    as ``_exact`` gives it, by hand: each operand split into :func:`_terms`,
    and the products of a term i of one with a term j of the other kept
    where ``i + j < 3`` (what ``precision="highest"`` keeps: six of float32
    by float32, all three of float32 by bfloat16, the one of bfloat16 by
    bfloat16), each a single pass of the MXU, summed in float32 from the
    smallest."""
    left, right = _terms(a), _terms(b)
    dims = (((a_axis + 1,), (b_axis + 1,)), ((0,), (0,)))
    total = None
    for _, i, j in sorted(((i + j, i, j) for i in range(len(left))
                           for j in range(len(right)) if i + j < 3),
                          reverse=True):
        product = lax.dot_general(left[i], right[j], dims,
                                  preferred_element_type=jnp.float32)
        total = product if total is None else total + product
    return total


def _chunk_inverses(turned):
    """``(I + a)^-1`` of every strictly lower ``a`` of a batch (m, C, C)
    float32 in VMEM, handed over as ``a^T``, by the substitution of
    :func:`_unit_lower_inverse`, the batch's matrices side by side in every
    operation (each is a chain of dependent steps, and a bundle holds steps
    of many).

    Inside the diagonal sub-blocks row by row, all of them at once: a
    matrix's sub-blocks lie side by side along the lanes, (sub, C), row r of
    each is ``e_r - a[r, :r] . rows[:r]`` in plain float32 on the VPU, and
    the coefficient ``a[r, j]`` of sub-block b reaches row j of its lanes by
    a lane gather from ``a``'s transposed diagonal.  Then sub-block by
    sub-block, two at a time: with ``T`` the inverse of the diagonal blocks
    of s tokens and ``a_s`` what ``a`` holds between the two halves of each
    block of 2s, the blocks of 2s invert to ``T - T a_s T``."""
    many, chunk, _ = turned.shape
    sub = min(SUB_BLOCK, chunk)
    blocks = chunk // sub
    a = jnp.swapaxes(turned, 1, 2)
    lane = lax.broadcasted_iota(jnp.int32, (sub, chunk), 1)
    row = lax.broadcasted_iota(jnp.int32, (sub, chunk), 0)
    first = lane // sub * sub           # of the lane's sub-block
    diagonal = sum(                 # [j, b sub + r] = a[b sub + r, b sub + j]
        jnp.where(first == b * sub, turned[:, b * sub:(b + 1) * sub, :], 0.0)
        for b in range(blocks))
    rows = jnp.broadcast_to(jnp.where(lane - first == row, 1.0, 0.0),
                            diagonal.shape)
    flat = diagonal.reshape(many * sub, chunk)
    for r in range(1, sub):
        coefficient = jnp.take_along_axis(
            flat, jnp.concatenate([first + r] * many, axis=0),
            axis=1).reshape(diagonal.shape)
        found = (coefficient * rows).sum(axis=1, keepdims=True)
        rows = rows - jnp.where(row == r, found, 0.0)

    def block_of(size, axis):           # of a row, or of a column
        return lax.broadcasted_iota(jnp.int32, (chunk, chunk), axis) // size

    inverse = jnp.where(block_of(sub, 0) == block_of(sub, 1),
                        jnp.concatenate([rows] * blocks, axis=1), 0.0)
    size = sub
    while size < chunk:
        halves = (block_of(size, 0) != block_of(size, 1)) \
            & (block_of(2 * size, 0) == block_of(2 * size, 1))
        inverse = inverse - _exact_dot(
            _exact_dot(inverse, jnp.where(halves, a, 0.0)), inverse)
        size *= 2
    return inverse


def _a_value_head(ref):
    """What a grid step holds of a value head's operand, (chunks, value
    heads a key head, ...), as one batch (chunks * value heads, ...)."""
    return ref[...].reshape((-1,) + ref.shape[2:])


def _a_row_each(ref):
    """A grid step's vectors a value head, (chunks, value heads a key head,
    C), as a row each: (chunks * value heads, 1, C)."""
    return jnp.stack([ref[c, j:j + 1, :] for c in range(ref.shape[0])
                      for j in range(ref.shape[1])])


def _of_value_heads(ref, per_key):
    """A key head's (chunks, C, d_k) once for each of its value heads."""
    k = ref[...]
    return jnp.broadcast_to(k[:, None], (k.shape[0], per_key) + k.shape[1:]
                            ).reshape((-1,) + k.shape[1:])


def _rows_to_value_heads(rows, ref):
    """:func:`_a_row_each`'s batch of rows as the block ``ref`` takes."""
    by_chunk = rows.reshape((-1, ref.shape[1]) + rows.shape[1:])
    return jnp.concatenate([by_chunk[:, j] for j in range(ref.shape[1])],
                           axis=1)


def _solve_fwd_kernel(at_ref, k_ref, v_ref, beta_ref, start_ref, t_ref,
                      w_ref, u0_ref):
    """Grid ``(batch, key heads, groups of chunks)``, no step needing
    another: for each value head of each of the key head's chunks ``T = (I +
    A)^-1`` (:func:`_chunk_inverses`, from ``A^T``), ``W = T (beta K G)`` and
    ``U0 = T (beta V)``.  ``beta`` and the decay scale ``T``'s columns, not
    ``K``'s and ``V``'s rows, so ``k`` and ``v`` meet the MXU as stored."""
    @_at_every_step
    def _():
        solve = _chunk_inverses(_a_value_head(at_ref))
        beta = _a_row_each(beta_ref)
        t_ref[...] = solve.reshape(t_ref.shape)
        w_ref[...] = _exact_dot(
            solve * (beta * _a_row_each(start_ref)),
            _of_value_heads(k_ref, at_ref.shape[1])).astype(
                w_ref.dtype).reshape(w_ref.shape)
        u0_ref[...] = _exact_dot(solve * beta, _a_value_head(v_ref)).reshape(
            u0_ref.shape)


def _solve_bwd_kernel(k_ref, v_ref, beta_ref, start_ref, t_ref, d_w_ref,
                      d_u0_ref, d_at_ref, d_k_ref, d_v_ref, d_beta_ref,
                      d_start_ref):
    """The same grid: every cotangent of the forward kernel's operands from
    the kept ``T`` and the cotangents of ``W`` and ``U0`` (``A`` itself is
    not read).  With ``P = dW K^T`` and ``Q = dU0 V^T``, ``T``'s cotangent
    is ``g = P (beta G) + Q beta`` by columns, ``A``'s ``-T^T g T^T``,
    written transposed, ``-T g^T T``; ``k``'s is ``(T beta G)^T dW`` summed
    over the key head's value heads in float32, ``v``'s ``(T beta)^T dU0``;
    and the two vectors' are column sums of ``T P`` and ``T Q`` element by
    element."""
    @_at_every_step
    def _():
        per_key = t_ref.shape[1]
        solve, d_w, d_u0 = (_a_value_head(ref)
                            for ref in (t_ref, d_w_ref, d_u0_ref))
        beta, start = _a_row_each(beta_ref), _a_row_each(start_ref)
        scale = beta * start            # of T's columns, for W
        by_k = _exact_dot(d_w, _of_value_heads(k_ref, per_key), 1, 1)
        by_v = _exact_dot(d_u0, _a_value_head(v_ref), 1, 1)
        d_scale = (solve * by_k).sum(axis=1, keepdims=True)   # of beta G
        d_beta_ref[...] = _rows_to_value_heads(
            d_scale * start + (solve * by_v).sum(axis=1, keepdims=True),
            d_beta_ref)
        d_start_ref[...] = _rows_to_value_heads(d_scale * beta, d_start_ref)
        d_at_ref[...] = -_exact_dot(_exact_dot(       # (-T^T g T^T)^T
            solve, jnp.swapaxes(by_k * scale + by_v * beta, 1, 2)),
            solve).reshape(d_at_ref.shape)
        d_k = _exact_dot(jnp.swapaxes(solve * scale, 1, 2), d_w)
        d_k_ref[...] = d_k.reshape((-1, per_key) + d_k.shape[1:]).sum(
            axis=1).astype(d_k_ref.dtype)
        d_v_ref[...] = _exact_dot(
            jnp.swapaxes(solve * beta, 1, 2), d_u0).astype(
                d_v_ref.dtype).reshape(d_v_ref.shape)


# Value heads' chunks a grid step of the solve takes where the chunks divide:
# each is a chain of dependent products, and Mosaic fills a step's bundles
# from as many chains as it holds (4, 8, 16 a step: 3.68, 3.32, 3.32 ms
# forward with backward a Qwen3-Next layer alone in a program; 16 is twice
# the code).
#
# `tools/solve_sweep.py` on a v5e, the stage ALONE in a program at a layer of
# each cell, ms, median of 20 calls (my chip run, PR 61; alone, either form
# pays for layouts at its boundary that a step's neighbours share: in the
# Qwen3-Next step XLA's stage is 1.40 forward / 3.95 with backward a layer
# and the kernels are 1.06 / 1.80, with `dW` relaid for them 1.90):
#
#   (tokens, key heads, value heads, d_k, d_v)   `_solved`    the kernels
#   forward            (4096, 16, 32, 128, 128)     2.48         2.24
#   with backward                                   5.78         3.32
#   forward            (8192, 15, 15,  96, 192)     3.13         2.89
#   with backward                                   6.29         4.95
#
# What decided the kernels' shape (my chip runs, PR 61).  Alone in a program:
# one value head's chunk after another, two a grid step, 3.31 forward / 5.08
# with backward a Qwen3-Next layer, where eight a step in every operation
# read 1.72 / 2.53.  In the step, `tokens_per_s_chip` of Qwen3-Next /
# Olmo-Hybrid over the parent's 34,383 / 20,641: `A`, `T` and `A`'s cotangent
# crossing HBM as PAIRS of chunks (.., 64, 128) — a kernel that only copies
# (.., 64, 64) float32 blocks runs at a tenth of the chip's bandwidth — with
# XLA's moves between chunks and pairs around the kernels 36,729 / 21,202;
# single chunks 36,510 / 21,434 (the moves gone, and a 0.2 ms copy a layer
# each way in Qwen3-Next that turns `A` and its cotangent for the kernel);
# single chunks handed over TRANSPOSED, as XLA holds them, 36,997 / 21,376,
# which is what stands.
_SOLVES_A_STEP = 8


@functools.partial(jax.jit, static_argnames=("scope", "backward",
                                             "interpret"))
def _solve_call(operands, scope, backward, interpret):
    """``pl.pallas_call`` of the solve's forward kernel over ``operands`` =
    (A^T, k, v, beta, the decay from the chunk's start), or of its backward
    kernel over (k, v, beta, the decay, T, dW, dU0), named ``<scope>_fwd``
    and ``<scope>_bwd``: the grid (batch, key heads, groups of chunks), every
    axis parallel, a key head's block of each operand and result, every one
    (b, n, g, ...).  Jitted, so that a model's layers of one shape trace and
    lower each kernel once (``ops.moe._tiled_call``)."""
    if backward:
        k, v, _, _, solve = operands[:5]
    else:
        solve, k, v = operands[:3]
    batch, chunks, key_heads, chunk, d_k = k.shape
    per_key, d_v = v.shape[3], v.shape[-1]
    held = max(_SOLVES_A_STEP // per_key, 1)     # chunks a grid step
    while chunks % held:
        held -= 1
    # Single passes of the MXU over (C, C, .) that a value head's chunk takes
    # (module docstring, "The solve, in two forms").
    levels = (chunk // min(SUB_BLOCK, chunk) - 1).bit_length()
    if backward:
        kernel, name, passes = _solve_bwd_kernel, f"{scope}_bwd", (12, 4, 9)
        outputs = (solve,) + operands[:4]   # a cotangent each, shaped as it is
    else:
        kernel, name, passes = (_solve_fwd_kernel, f"{scope}_fwd",
                                (12 * levels, 3, 3))
        outputs = (solve, jax.ShapeDtypeStruct(v.shape[:-1] + (d_k,), k.dtype),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32))

    def spec(t):
        return pl.BlockSpec((None, held, None) + t.shape[3:],
                            lambda b, g, n: (b, n, g) + (0,) * (t.ndim - 3))

    vma = jax.typeof(k).vma             # inside shard_map: as the inputs vary
    macs = chunk * chunk * (passes[0] * chunk + passes[1] * d_k
                            + passes[2] * d_v)
    call = pl.pallas_call(
        kernel, grid=(batch, key_heads, chunks // held),
        in_specs=[spec(t) for t in operands],
        out_specs=[spec(t) for t in outputs],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                   for t in outputs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * chunks * key_heads * per_key * macs,
            transcendentals=0,
            bytes_accessed=sum(t.size * t.dtype.itemsize
                               for t in (*operands, *outputs))),
        interpret=interpret, name=name)
    with kernel_trace(name):
        return call(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _head_solve(a, k, v, beta, from_start, scope, interpret):
    """:func:`_solved` of the head form, as two kernels that hold a chunk's
    ``T`` on the chip (module docstring, "The solve, in two forms"): ``W`` in
    ``k``'s dtype (b, n, h, C, d_k) and ``U0`` float32 (b, n, h, C, d_v) from
    ``a`` (b, n, h, C, C) float32, strictly lower, ``k`` a KEY head
    (b, n, g, C, d_k) and ``v`` (b, n, h, C, d_v) as stored, and ``beta`` and
    ``from_start`` (b, n, h, C) float32.  ``scope`` names the kernels
    (``<scope>_fwd``, ``<scope>_bwd``)."""
    return _head_solve_fwd(a, k, v, beta, from_start, scope, interpret)[0]


def _head_solve_fwd(a, k, v, beta, from_start, scope, interpret):
    turned, *given = _by_key_head(
        k, (jnp.swapaxes(a, -1, -2), v, beta, from_start))
    given = (k, *given)
    solve, w, u0 = _solve_call((turned, *given), scope=scope, backward=False,
                               interpret=interpret)
    return (_by_value_head(w), _by_value_head(u0)), (*given, solve)


def _head_solve_bwd(scope, interpret, kept, cotangents):
    d_turned, d_k, *value_heads = _solve_call(
        kept + tuple(_by_key_head(kept[0], cotangents)), scope=scope,
        backward=True, interpret=interpret)
    return (jnp.swapaxes(_by_value_head(d_turned), -1, -2), d_k,
            *map(_by_value_head, value_heads))


_head_solve.defvjp(_head_solve_fwd, _head_solve_bwd)


def lowered_plan(seq: int, chunk: int, form: str = "head") -> dict:
    """What one call of :func:`chunked_delta_rule` at this length, forward and
    backward together, adds to a compiled step.  ``form="head"`` (a decay a
    head): the two kernels of :func:`_head_solve` and the two of
    :func:`_head_carry`, whatever the length, and no loop.
    ``form="channel"`` (a decay a channel): the recurrence between chunks is
    a ``while`` forward and one backward (the compiler unrolls a loop of one
    step, so a single chunk has none), and no kernel of ours."""
    if form == "head":
        return {"while": 0, "tpu_custom_call": 4}
    if form != "channel":
        raise ValueError(f"lowered_plan: form {form!r} is neither 'head' "
                         "nor 'channel'")
    return {"while": 2 if -(-seq // chunk) > 1 else 0, "tpu_custom_call": 0}


def _solved(a, beta, k, from_start, v, dtype, scope: str):
    """The solve both forms share, from a chunk's ``A`` (strictly lower),
    ``beta``, ``K``, ``G`` and ``V`` (float32, each (b, n, h, C, ...) or
    broadcasting to it): ``W`` in ``dtype`` and ``U0``."""
    exact = dict(precision="highest", **_WIDE)
    with jax.named_scope(f"{scope}_solve"):
        solve = _unit_lower_inverse(a)
        w = jnp.einsum("bnhts,bnhsc->bnhtc", solve, beta * k * from_start,
                       **exact).astype(dtype)
        u0 = jnp.einsum("bnhts,bnhsv->bnhtv", solve, beta * v, **exact)
    return w, u0


def _to_tokens(o, seq: int):            # (b, n, h, C, d_v) -> (b, seq, h, d_v)
    batch, _, heads = o.shape[:3]
    return jnp.moveaxis(o, 3, 2).reshape(batch, -1, heads,
                                         o.shape[-1])[:, :seq]


def _head_decay_rule(q, k, v, log_alpha, beta, chunk: int, scope: str):
    """:func:`chunked_delta_rule` with a decay a head over grouped heads
    (module docstring, "A decay a head")."""
    batch, seq, key_heads, d_k = q.shape
    heads = v.shape[2]
    sub = min(SUB_BLOCK, chunk)
    if heads % key_heads or chunk % sub \
            or log_alpha.shape != (batch, seq, heads):
        raise ValueError(
            f"chunked_delta_rule: {key_heads} key heads do not divide {heads} "
            f"value heads, the chunk {chunk} is no multiple of {sub}, or the "
            f"decay {log_alpha.shape} is not one a value head")
    per_key = heads // key_heads
    f32, dtype = jnp.float32, q.dtype
    tail = -seq % chunk
    chunks = (seq + tail) // chunk

    def by_chunk(t):                    # (b, seq, h, ...) -> (b, n, h, C, ...)
        if tail:
            t = jnp.pad(t, [(0, 0), (0, tail)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(
            t.reshape(batch, chunks, chunk, *t.shape[2:]), 2, 3)

    def of_value_heads(t):              # a key head's, for each of its heads
        return jnp.broadcast_to(
            t[:, :, :, None], (batch, chunks, key_heads, per_key)
            + t.shape[3:]).reshape(batch, chunks, heads, *t.shape[3:])

    with jax.named_scope(f"{scope}_chunk"):
        qc, kc = by_chunk(q), by_chunk(k)
        vc, bc = by_chunk(v), by_chunk(beta.astype(f32))

    with jax.named_scope(f"{scope}_decays"):
        at = jnp.arange(chunk)
        earlier, lower = at[:, None] > at[None, :], at[:, None] >= at[None, :]
        steps = by_chunk(log_alpha.astype(f32))              # (b, n, h, C)
        within = jnp.cumsum(steps, axis=-1)
        whole = within[..., -1]
        later = jnp.concatenate(
            [steps[..., 1:], jnp.zeros_like(steps[..., :1])], -1)
        to_end = lax.cumsum(later, axis=later.ndim - 1, reverse=True)
        # between[t, s] = sum of steps[i] over s < i <= t (0 where s >= t).
        between = jnp.einsum(
            "ti,bnhis->bnhts", lower.astype(f32),
            jnp.where(earlier, steps[..., :, None], 0.0), precision="highest")
        decay = jnp.where(lower, jnp.exp(between), 0.0)      # (b, n, h, C, C)
        # (b, n, h, C): a token's decay, for every channel of its head.
        from_start, end_decay = jnp.exp(within), jnp.exp(to_end)
        carried, decay_min = jnp.exp(whole)[..., None], whole.min()

    with jax.named_scope(f"{scope}_chunk"):
        kk = of_value_heads(jnp.einsum("bngtc,bngsc->bngts", kc, kc, **_WIDE))
        qk = of_value_heads(jnp.einsum("bngtc,bngsc->bngts", qc, kc, **_WIDE))
        a = jnp.where(earlier, bc[..., None] * decay * kk, 0.0)
        qk = (decay * qk).astype(dtype)
    interpret = jax.default_backend() != "tpu"
    with jax.named_scope(f"{scope}_solve"):
        w, u0 = _head_solve(a, kc, vc, bc, from_start, f"{scope}_solve",
                            interpret)
    with jax.named_scope(f"{scope}_carry"):
        o = _head_carry(w, u0, qk, qc, kc, from_start, end_decay, carried,
                        f"{scope}_carry", interpret)
        return _to_tokens(o, seq), decay_min


def chunked_delta_rule(q, k, v, log_alpha, beta, chunk: int,
                       scope: str = "hvd_kda_scan"):
    """``o`` of the recurrence above for every token, from a zero state.

    A decay a channel: ``q``, ``k`` (batch, seq, heads, d_k); ``v`` (batch,
    seq, heads, d_v); ``log_alpha`` (batch, seq, heads, d_k) float32, in
    (-5.8, 0]; ``beta`` (batch, seq, heads) float32.  ``seq`` is a multiple of
    ``chunk``, ``chunk`` of :data:`SUB_BLOCK` where it is longer.

    A decay a head: ``q``, ``k`` (batch, seq, key heads, d_k); ``v`` (batch,
    seq, value heads, d_v); ``log_alpha`` and ``beta`` (batch, seq, value
    heads) float32, ``log_alpha <= 0`` without a bound; any ``seq``.  ``d_k``
    and ``d_v`` are each their own and need be no multiple of a lane tile: the
    kernels' blocks are the true widths (Mosaic compiles 96 and 192, laid out
    in 128 and 256 lanes).  ``beta`` in [0, 2]: the step appears in ``A`` and
    in the right-hand sides alone, and the solve is a forward substitution in
    full precision, which a strictly lower ``A`` twice as large does not
    trouble (``(I + A)^-1`` exists whatever ``A``'s size, and with unit keys
    its entries stay of order one) — checked against the recurrence one step
    a token with steps from 0.1 to past 1.9, values and every gradient, at
    d_k 12 / d_v 24 and at 96 / 192
    (``tests/test_olmohybrid.py::test_rule_at_two_widths_and_steps_past_one_is_the_recurrence``,
    ``::test_steps_past_one_are_as_exact_as_steps_under_it``).  A decay a
    channel was checked on ``beta`` in [0, 1] alone.

    ``scope``: the stages' names start with it.  Returns ``(o,
    chunk_log_decay_min)``: ``o`` float32 (batch, seq, value heads, d_v), and
    the most negative summed log-decay of any chunk, head and channel — where
    ``exp`` of it underflows, nothing crosses that chunk in that channel."""
    if log_alpha.ndim == 3:
        return _head_decay_rule(q, k, v, log_alpha, beta, chunk, scope)
    batch, seq, heads, d_k = q.shape
    sub = min(SUB_BLOCK, chunk)
    if seq % chunk or chunk % sub:
        raise ValueError(f"chunked_delta_rule: seq {seq} is not a multiple "
                         f"of chunk {chunk}, or the chunk of {sub}")
    chunks, blocks = seq // chunk, chunk // sub
    f32, dtype = jnp.float32, q.dtype

    def by_chunk(t):                    # (b, seq, h, ...) -> (b, n, h, C, ...)
        return jnp.moveaxis(
            t.reshape(batch, chunks, chunk, heads, *t.shape[3:]), 3, 2)

    with jax.named_scope(f"{scope}_chunk"):
        qc, kc = by_chunk(q).astype(f32), by_chunk(k).astype(f32)
        vc, bc = by_chunk(v).astype(f32), by_chunk(beta.astype(f32))[..., None]

    with jax.named_scope(f"{scope}_decays"):
        # Sums of log-decays (module docstring): (b, n, h, blocks, sub, d_k)
        # from here on; i, j, m index sub-blocks.
        steps = by_chunk(log_alpha.astype(f32)).reshape(
            batch, chunks, heads, blocks, sub, d_k)
        first = steps[..., :1, :]
        after_first = jnp.cumsum(
            jnp.where(jnp.arange(sub)[:, None] > 0, steps, 0.0), axis=-2)
        later = jnp.concatenate([steps[..., 1:, :], jnp.zeros_like(first)],
                                -2)
        tail = lax.cumsum(later, axis=later.ndim - 2, reverse=True)
        total = after_first[..., -1, :] + first[..., 0, :]   # (b, n, h, i, d)
        m = jnp.arange(blocks)

        def summed(mask):
            """The sub-blocks' totals summed where ``mask[i, m]`` holds."""
            return jnp.einsum("im,bnhmd->bnhid", mask.astype(f32), total,
                              precision="highest")

        before, after = summed(m < m[:, None]), summed(m > m[:, None])
        within = (before[..., None, :] + first + after_first).reshape(
            batch, chunks, heads, chunk, d_k)
        to_end = (tail + after[..., None, :]).reshape(within.shape)
        whole = total.sum(axis=-2)                           # (b, n, h, d_k)

        # Every G_t / G_s through the first token of t's sub-block i:
        # G_t / G_ref is at most 1; G_ref / G_s at most 1 for s in an earlier
        # sub-block j and at most e^75 inside i itself.
        to_ref = jnp.exp(after_first).reshape(within.shape)
        # Rows of sub-block i meet the columns of sub-blocks 0..i alone:
        # from a column of sub-block j < i to the end of j, over the whole
        # sub-blocks between, and i's first token; inside i back from the
        # column to i's first token.
        from_ref = []
        for i in range(blocks):
            pieces = [-after_first[..., i, :, :]]
            if i:
                to_first = [first[..., i, 0, :]]     # from the end of i - 1,
                for j in range(i - 1, 0, -1):        # then of j - 1: over j
                    to_first.append(to_first[-1] + total[..., j, :])
                pieces.insert(0, (
                    tail[..., :i, :, :]
                    + jnp.stack(to_first[::-1], -2)[..., None, :]).reshape(
                        batch, chunks, heads, i * sub, d_k))
            from_ref.append(jnp.exp(jnp.concatenate(pieces, axis=-2)))
        decayed, end_decay = jnp.exp(within), jnp.exp(to_end)
        carried, decay_min = jnp.exp(whole), whole.min()

    def against_earlier(rows):
        """``rows[t] . G_t`` against every ``k_s / G_s``: (b, n, h, C, C),
        zero right of the diagonal sub-blocks."""
        rows = (rows * to_ref).astype(dtype)
        return jnp.concatenate([jnp.pad(
            jnp.einsum("bnhtc,bnhsc->bnhts",
                       rows[..., i * sub:(i + 1) * sub, :], k_col[i], **_WIDE),
            [(0, 0)] * 4 + [(0, chunk - (i + 1) * sub)])
            for i in range(blocks)], axis=-2)

    with jax.named_scope(f"{scope}_chunk"):
        k_col = [(kc[..., :(i + 1) * sub, :] * from_ref[i]).astype(dtype)
                 for i in range(blocks)]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        a = jnp.where(lower & ~jnp.eye(chunk, dtype=bool),
                      bc * against_earlier(kc), 0.0)
        qk = jnp.where(lower, against_earlier(qc), 0.0).astype(dtype)
        q_in = (qc * decayed).astype(dtype)
        k_end = (kc * end_decay).astype(dtype)
    w, u0 = _solved(a, bc, kc, decayed, vc, dtype, scope)
    with jax.named_scope(f"{scope}_carry"):
        return _to_tokens(_carry(w, u0, q_in, qk, k_end, carried),
                          seq), decay_min
