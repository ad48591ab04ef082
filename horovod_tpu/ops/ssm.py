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
model is.  It is differentiable by autodiff of that form.

The cost is memory passes, not arithmetic, for any caller: a token's masked
decay matrix ``L`` is ``heads x chunk`` float32 and the ``mixed`` operand as
many elements in ``x``'s dtype, written and read again forward and backward,
beside ``heads x chunk x head_dim + 2 heads x state x head_dim + groups x chunk
x state`` multiply-adds.  At the two points the benchmark runs: 16 heads on one
group at a chunk of 128 (an eighth of Nemotron-3-Super's 128 heads in 8
groups, its tensor share) 8 KB of ``L`` and 0.41 M multiply-adds a token
beside the 13.7 M of the layer's two projections; 64 whole heads on ONE group
at a chunk of 256 (Granite-4.0-H-Micro) 64 KB of ``L`` — sixteen times a
token's residual stream of 2,048 bfloat16 — and 2.13 M beside 25.8 M.

The four stages run under ``jax.named_scope``s a trace can read, forward and
backward alike (:data:`STAGES`): ``hvd_ssm_scan_decay`` (the cumulative sums,
the masked ``L``, ``to_end`` and their exponentials), ``hvd_ssm_scan_intra``
(``dt x``, ``scores``, ``mixed``, the within-chunk product, the skip ``D x``),
``hvd_ssm_scan_ends`` (each chunk's end state) and ``hvd_ssm_scan_carry`` (the
chunk-by-chunk matrix, ``entering``, ``from_start`` and its sum into ``y``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The scopes under a caller's own (``hvd_ssm_scan``), ``hvd_ssm_scan_<stage>``.
STAGES = ("decay", "intra", "ends", "carry")


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
