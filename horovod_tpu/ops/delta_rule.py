"""The gated delta rule of a Kimi-delta linear-attention layer (KDA, Kimi
Linear, arXiv:2510.26692) as matrix products over chunks.

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
``Q K^T`` for every chunk at once, and then carries ``S`` through the chunks in
a ``lax.scan`` of three products a step (a ``while`` in the compiled program,
forward and backward: the recurrence is a true one).  It is differentiable by
autodiff of that form.

``k_s / G_s`` is never formed: over a chunk of 64 tokens at the gate's bound of
-5 a step it is ``e^320``.  A chunk is cut into sub-blocks of
:data:`SUB_BLOCK` tokens and every ratio ``G_t / G_s`` goes through the decay
at the first token of t's sub-block: ``G_t / G_ref <= 1``, and ``G_ref / G_s``
is at most 1 for an earlier sub-block and at most ``e^75`` inside t's own (15
steps of at most 5), which float32 and bfloat16 hold.  A log-decay under about
-5.8 a step would overflow there: the caller's gate bounds it.

Every log of a product of decays is a sum of log-decays (within a sub-block,
to its end, over whole sub-blocks between), never a difference of two
cumulative sums: those reach -320 in a chunk, where float32's spacing is 3e-5.

Four stages, each under a ``jax.named_scope`` of its own beneath the caller's
(``hvd_kda_scan`` in ``models/delta.py``), so that a device trace tells them
apart forward and backward; every operation of :func:`chunked_delta_rule` is
under exactly one:

* ``hvd_kda_scan_decays`` — the sums of log-decays, their exponentials and the
  ratios against a sub-block's reference;
* ``hvd_kda_scan_chunk`` — the products inside a chunk: the views of q, k, v
  and beta by chunk, ``K K^T`` and ``Q K^T`` through the sub-blocks
  (``against_earlier``), and the decayed ``Q`` and ``K`` the recurrence reads;
* ``hvd_kda_scan_solve`` — ``T`` (:func:`_unit_lower_inverse`, forward and its
  written-out backward) and ``W``, ``U0``;
* ``hvd_kda_scan_carry`` — the ``lax.scan`` between chunks (the ``while``,
  its body, and the moves of its operands and of ``o``).

The statements stand in the order they were traced in before the stages had
names, so a stage's scope opens more than once: the lowered program is the
same to the byte.

Float32: the summed log-decays, their exponentials, ``T`` (by forward
substitution, exact products), ``W``, ``U0``, ``U`` and the state between
chunks.  The operands of
the other products are rounded to ``q``'s dtype and accumulated in float32, as
every matmul of the model is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SUB_BLOCK = 16   # tokens whose decay ratios are taken against one reference


def _exact(a, b):
    return jnp.matmul(a, b, precision="highest")


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
    diagonal = jnp.stack([a[..., c:c + sub, c:c + sub] for c in corners], -3)
    inverses = jnp.broadcast_to(jnp.eye(sub, dtype=a.dtype), diagonal.shape)
    for row in range(1, sub):
        inverses = inverses.at[..., row, :].add(-jnp.einsum(
            "...j,...jk->...k", diagonal[..., row, :row],
            inverses[..., :row, :], precision="highest"))
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


def chunked_delta_rule(q, k, v, log_alpha, beta, chunk: int):
    """``o`` of the recurrence above for every token, from a zero state.

    ``q``, ``k`` (batch, seq, heads, d_k); ``v`` (batch, seq, heads, d_v);
    ``log_alpha`` (batch, seq, heads, d_k) float32, in (-5.8, 0];
    ``beta`` (batch, seq, heads) float32.  ``seq`` is a multiple of ``chunk``,
    ``chunk`` of :data:`SUB_BLOCK` where it is longer.  Returns ``(o,
    chunk_log_decay_min)``: ``o`` float32 (batch, seq, heads, d_v), and the
    most negative summed log-decay of any chunk, head and channel — where
    ``exp`` of it underflows, nothing crosses that chunk in that channel."""
    batch, seq, heads, d_k = q.shape
    sub = min(SUB_BLOCK, chunk)
    if seq % chunk or chunk % sub:
        raise ValueError(f"chunked_delta_rule: seq {seq} is not a multiple "
                         f"of chunk {chunk}, or the chunk of {sub}")
    chunks, blocks = seq // chunk, chunk // sub
    f32, dtype = jnp.float32, q.dtype
    wide = dict(preferred_element_type=f32)
    exact = dict(precision="highest", preferred_element_type=f32)

    def by_chunk(t):                    # (b, seq, h, ...) -> (b, n, h, C, ...)
        return jnp.moveaxis(
            t.reshape(batch, chunks, chunk, heads, *t.shape[3:]), 3, 2)

    with jax.named_scope("hvd_kda_scan_chunk"):
        qc, kc = by_chunk(q).astype(f32), by_chunk(k).astype(f32)
        vc, bc = by_chunk(v).astype(f32), by_chunk(beta.astype(f32))[..., None]

    with jax.named_scope("hvd_kda_scan_decays"):
        # Sums of log-decays (module docstring): (b, n, h, blocks, sub, d_k)
        # from here on; i, j, m index sub-blocks.
        steps = by_chunk(log_alpha.astype(f32)).reshape(
            batch, chunks, heads, blocks, sub, d_k)
        first = steps[..., :1, :]
        after_first = jnp.cumsum(steps.at[..., 0, :].set(0.0), axis=-2)
        later = jnp.concatenate([steps[..., 1:, :], jnp.zeros_like(first)],
                                -2)
        tail = lax.cumsum(later, axis=later.ndim - 2, reverse=True)
        total = after_first[..., -1, :] + first[..., 0, :]   # (b, n, h, i, d)
        i, j = jnp.arange(blocks)[:, None], jnp.arange(blocks)[None, :]
        m = jnp.arange(blocks)

        def summed(indices, mask):
            """The sub-blocks' totals summed where ``mask[..., m]`` holds."""
            return jnp.einsum(f"{indices}m,bnhmd->bnh{indices}d",
                              mask.astype(f32), total, precision="highest")

        before, after = summed("i", j < i), summed("i", j > i)
        between = summed("ij", (m > j[..., None]) & (m < i[..., None]))
        within = (before[..., None, :] + first + after_first).reshape(
            batch, chunks, heads, chunk, d_k)
        to_end = (tail + after[..., None, :]).reshape(within.shape)
        whole = total.sum(axis=-2)                           # (b, n, h, d_k)

        # Every G_t / G_s through the first token of t's sub-block i:
        # G_t / G_ref is at most 1; G_ref / G_s at most 1 for s in an earlier
        # sub-block j and at most e^75 inside i itself.
        to_ref = jnp.exp(after_first).reshape(within.shape)
        from_ref = jnp.exp(jnp.where(
            (j < i)[..., None, None],
            tail[..., None, :, :, :] + between[..., None, :]
            + first[..., :, None, :, :],
            jnp.where((j == i)[..., None, None],
                      -after_first[..., None, :, :, :], -jnp.inf)))

    def against_earlier(rows):
        """``rows[t] . G_t`` against every ``k_s / G_s``: (b, n, h, C, C)."""
        rows = (rows * to_ref).astype(dtype).reshape(
            batch, chunks, heads, blocks, sub, d_k)
        return jnp.einsum("bnhitc,bnhisc->bnhits", rows, k_col,
                          **wide).reshape(batch, chunks, heads, chunk, chunk)

    with jax.named_scope("hvd_kda_scan_chunk"):
        k_col = (kc[..., None, :, :] * from_ref.reshape(
            batch, chunks, heads, blocks, chunk, d_k)).astype(dtype)
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        a = jnp.where(lower & ~jnp.eye(chunk, dtype=bool),
                      bc * against_earlier(kc), 0.0)
        qk = jnp.where(lower, against_earlier(qc), 0.0).astype(dtype)
    with jax.named_scope("hvd_kda_scan_solve"):
        solve = _unit_lower_inverse(a)
    with jax.named_scope("hvd_kda_scan_decays"):
        decayed = jnp.exp(within)
    with jax.named_scope("hvd_kda_scan_solve"):
        w = jnp.einsum("bnhts,bnhsc->bnhtc", solve, bc * kc * decayed,
                       **exact)
        u0 = jnp.einsum("bnhts,bnhsv->bnhtv", solve, bc * vc, **exact)
    with jax.named_scope("hvd_kda_scan_chunk"):
        q_in = (qc * decayed).astype(dtype)
    with jax.named_scope("hvd_kda_scan_decays"):
        end_decay = jnp.exp(to_end)
    with jax.named_scope("hvd_kda_scan_chunk"):
        k_end = (kc * end_decay).astype(dtype)

    def chunk_step(state, inputs):
        w, u0, q_in, qk, k_end, carried = inputs
        narrow = state.astype(dtype)
        u = u0 - jnp.einsum("bhtc,bhcv->bhtv", w, narrow, **wide)
        rounded = u.astype(dtype)
        o = jnp.einsum("bhtc,bhcv->bhtv", q_in, narrow, **wide) \
            + jnp.einsum("bhts,bhsv->bhtv", qk, rounded, **wide)
        state = carried[..., None] * state + jnp.einsum(
            "bhtc,bhtv->bhcv", k_end, rounded, **wide)
        return state, o

    with jax.named_scope("hvd_kda_scan_carry"):
        start = jnp.zeros((batch, heads, d_k, v.shape[-1]), f32)
        varying = tuple(jax.typeof(k).vma)
        if varying:  # inside shard_map the carry varies as the inputs do
            start = lax.pcast(start, varying, to="varying")
    with jax.named_scope("hvd_kda_scan_solve"):
        w = w.astype(dtype)
    with jax.named_scope("hvd_kda_scan_decays"):
        carried = jnp.exp(whole)
    with jax.named_scope("hvd_kda_scan_carry"):
        by_step = [jnp.moveaxis(t, 1, 0)
                   for t in (w, u0, q_in, qk, k_end, carried)]
        _, o = lax.scan(chunk_step, start, tuple(by_step))
        # (n, b, h, C, d_v) -> (b, seq, h, d_v)
        o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(batch, seq, heads, -1)
    with jax.named_scope("hvd_kda_scan_decays"):
        return o, whole.min()
