"""The gated delta rule in its chunked (WY) form, in ``jax.numpy``.

Per head, with a state ``S`` of ``[d_k, d_v]``, keys of unit length,
a decay ``alpha_t = exp(g_t)`` in (0, 1] and a step ``beta_t`` in
[0, 2] (Yang et al., Gated Delta Networks, arXiv:2412.06464)::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Token by token that is ``T`` dependent rank-one updates. Here the
sequence is cut into chunks of ``C`` tokens; inside a chunk the
updates are written as one triangular system. With ``c_i`` the
cumulative log-decay up to token ``i`` of the chunk and ``G_ij =
exp(c_i - c_j)`` for ``i >= j``::

    A      = I + strict_lower(diag(beta) (K K^T * G))
    W      = A^-1 (beta * exp(c) * K)        U = A^-1 (beta * V)
    V_new  = U - W S_0                       (the chunk's rank-C update)
    O      = (exp(c) * Q) S_0 + lower(Q K^T * G) V_new
    S_C    = exp(c_C) S_0 + (exp(c_C - c) * K)^T V_new

Everything that does not read the state (``A^-1``, ``W``, ``U``, the
masked ``Q K^T``) is computed for all chunks at once, as batched
products; the state is carried across chunks in float32 by
``lax.scan``, whose body is recomputed in the backward pass
(``jax.checkpoint``), so the backward holds the carried states and no
other residual of the scan. ``A`` is unit lower triangular and is
solved, not multiplied out: the powers of its strict part grow like
binomials where keys repeat under ``beta`` near 2, while the solve
stays as well conditioned as the recurrence.

The solve is this module's own (``unit_lower_inverse``): forward
substitution row by row, 64 dependent steps, each one multiply-and-sum
over all 960 chunks of a call at once, with its backward rule written
out (``-strict_lower(X^T G X^T)``) so that JAX does not differentiate
through the steps. ``jax.scipy``'s ``solve_triangular`` is the same
substitution on the chip, but XLA:TPU's generic inverter of diagonal
blocks runs it one block after another: 2.9 ms a call at the model's
shapes against 0.37 ms (PERF.md section 6, PR 36, which also read the
blocked forms there: diagonal blocks of 16 or 32 rows substituted and
merged by products cost more on the chip than they save, and lose a
factor of four of accuracy where keys repeat).

Decays only ever appear as ``exp`` of a difference ``c_i - c_j`` with
``i >= j`` (masked before the ``exp``), so a decay near 0 underflows
to 0 and never overflows. What the chip's trace leaves after the
inverse is the two scans over chunks (forward: three fusions and a
copy a trip; backward: eighteen fusions and two copies a trip) and
the batched products around them; a fused kernel for those is read
against ``benchmark/flops/olmo_hybrid.py:delta_rule_flops``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
HIGHEST = jax.lax.Precision.HIGHEST


def _chunks(x, n: int, c: int):
    """``[B, n*c, H, ...] -> [B, H, n, c, ...]``."""
    b, _, h = x.shape[:3]
    x = x.reshape((b, n, c, h) + x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


@jax.custom_vjp
def unit_lower_inverse(l):
    """``(I + l)^-1`` for strictly lower triangular ``l`` of
    ``[..., n, n]`` float32 (what lies on or above the diagonal is
    not read), by forward substitution, ``X[r] = e_r - l[r, :r]
    X[:r]``: ``n`` dependent steps, each one multiply-and-sum over
    every block of the batch at once. The blocks lie on the minor axis
    meanwhile (``[n, n, N]``): there a step is whole vector
    operations, whatever ``n``. Its own backward rule
    (``-strict_lower(X^T G X^T)``, two products at the highest
    precision) keeps JAX from differentiating through the steps."""
    with jax.named_scope("delta.inverse"):
        shape, n = l.shape, l.shape[-1]
        lt = jnp.moveaxis(jnp.tril(l, -1).reshape(-1, n, n), 0, -1)
        eye = jnp.eye(n, dtype=l.dtype)

        def step(r, x):
            # rows r and below of x are still 0: the sum is over :r
            row = eye[r][:, None] - jnp.sum(lt[r][:, None, :] * x, axis=0)
            return x.at[r].set(row)

        x = jax.lax.fori_loop(0, n, step, jnp.zeros_like(lt))
        return jnp.moveaxis(x, -1, 0).reshape(shape)


def _inverse_fwd(l):
    x = unit_lower_inverse(l)
    return x, x


def _inverse_bwd(x, g):
    with jax.named_scope("delta.inverse"):
        xt = jnp.swapaxes(x, -1, -2)
        bar = jnp.matmul(jnp.matmul(xt, g, precision=HIGHEST), xt,
                         precision=HIGHEST)
        return (-jnp.tril(bar, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """``q``, ``k``: ``[B, T, H, d_k]`` (``k`` of unit length, ``q``
    already scaled); ``v``: ``[B, T, H, d_v]``; ``g``: ``[B, T, H]``
    log-decay (<= 0) and ``beta``: ``[B, T, H]``, both float32.
    Returns ``o``: ``[B, T, H, d_v]`` float32, from a zero state.

    Products take their operands in ``q``'s dtype (bfloat16 in the
    model, float32 in the tests) and accumulate in float32; decays,
    the triangular solve and the carried state are float32."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    dt = q.dtype
    f32 = jnp.float32
    pad = (-T) % chunk
    if pad:
        # a padded token has beta 0 and decay 1: it leaves the state
        # as it is, and its output is cut off below
        widen = lambda x: jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = (widen(x) for x in (q, k, v, g, beta))
    n = (T + pad) // chunk
    qc, kc, vc = (_chunks(x, n, chunk) for x in (q, k, v))
    gc = _chunks(g.astype(f32), n, chunk)          # [B, H, n, C]
    bc = _chunks(beta.astype(f32), n, chunk)
    c = jnp.cumsum(gc, axis=-1)

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                          preferred_element_type=f32)

    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    strict = idx[:, None] > idx[None, :]
    diff = c[..., :, None] - c[..., None, :]
    gam = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = kc.astype(f32) * bc[..., None]
    a = jnp.where(strict, mm("bhnid,bhnjd->bhnij", kb, kc) * gam, 0.0)
    inv = unit_lower_inverse(a)
    ec = jnp.exp(c)[..., None]
    w = mm("bhnij,bhnjd->bhnid", inv, kb * ec)
    u = mm("bhnij,bhnjd->bhnid", inv, vc.astype(f32) * bc[..., None])
    attn = jnp.where(lower, mm("bhnid,bhnjd->bhnij", qc, kc) * gam, 0.0)
    qg = qc.astype(f32) * ec
    kdec = kc.astype(f32) * jnp.exp(c[..., -1:] - c)[..., None]
    last = jnp.exp(c[..., -1])                      # [B, H, n]

    def body(state, xs):
        w_i, u_i, attn_i, qg_i, kdec_i, last_i = xs
        v_new = u_i - mm("bhcd,bhde->bhce", w_i, state)
        o_i = mm("bhcd,bhde->bhce", qg_i, state) \
            + mm("bhij,bhje->bhie", attn_i, v_new)
        state = state * last_i[..., None, None] \
            + mm("bhcd,bhce->bhde", kdec_i, v_new)
        return state, o_i

    # what the scan reads a chunk at a time, in the operands' dtype
    per_chunk = tuple(jnp.moveaxis(x.astype(dt), 2, 0)
                      for x in (w, u, attn, qg, kdec)) \
        + (jnp.moveaxis(last, 2, 0),)
    _, o = jax.lax.scan(jax.checkpoint(body),
                        jnp.zeros((B, H, dk, dv), f32), per_chunk)
    # [n, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, n * chunk, H, dv)
    return o[:, :T]
