"""Kimi Delta Attention (KDA): the gated delta rule with a decay per
channel, in chunked form.

NEW capability over the reference (it has no linear attention). The
recurrence of one head, state ``S`` of (K, V), from zero at the start of
every row, with q and k already L2-normalised::

    S'  = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t / sqrt(K)

``alpha_t`` (K,) is a decay a key channel (``log_alpha <= 0``), ``beta_t``
a scalar a head. :func:`kda_scan` computes it a chunk of C positions at
a time (the WY form of the delta rule, Yang et al. 2024, with Kimi
Linear's per-channel decay). With ``G`` the running sum of ``log_alpha``
inside a chunk (float32, ``G_0 = 0``)::

    A_kk[i, j] = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)     j < i
    A_qk[i, j] =        sum_c q_ic k_jc exp(G_ic - G_jc)     j <= i
    [W | U]    = (I + A_kk)^-1 [diag(beta)(k o e^G) | diag(beta) v]
    V'         = U - W S          (S: the state the chunk starts from)
    O          = ((q o e^G) S + A_qk V') / sqrt(K)
    S         <- Diag(e^{G_C}) S + (k o e^{G_C - G})^T V'

``(I + A_kk)^-1`` is built by products (:func:`_unit_lower_inverse`):
the inverses of the diagonal blocks of 1, 2, 4, ... positions are merged
pairwise up to C, two (C, C) products a doubling at ``highest`` precision,
so the MXU does it in log2(C) dependent steps where a triangular solve
takes C. Applying it is a product for W and one for U, and
:func:`_wy_solve` gives its own backward (``dR = X^T dY`` for each,
``dA_kk = -(dRk W^T + dRv U^T)`` below the diagonal), which neither
solves nor inverts again. The states are carried
from chunk to chunk by a ``lax.scan`` over T / C steps. Plain
``jax.numpy``, the rest differentiated by JAX; one form, no kernel.
``gluon.nn.KimiDeltaAttention`` is the Block.

No exponential is ever taken of a positive number: ``exp(G_i - G_j)`` for
``j <= i`` is at most 1, but ``exp(G_i) exp(-G_j)`` would overflow
``exp(-G_j)`` once a chunk's decays pass 88 in float32. So ``A`` is built
a block of ``SUB`` positions at a time: on the diagonal blocks the decay
of each (i, j, channel) exactly, masked to ``-inf`` before the
exponential; off them the two-sided split ``exp(G_i - G_r) exp(G_r -
G_j)`` about the last position ``r`` of the block of ``j``, both factors
at most 1 for ``i > r >= j``.
"""

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

SCOPE = 'mx.kda'    # the chunked core; the short convolutions are mx.ssm_conv
SUB = 16            # positions of a diagonal block whose decays are exact


def _decayed_products(x, k, g, strict):
    """``M[i, j] = sum_c x_ic k_jc exp(g_ic - g_jc)`` for ``j < i``
    (``strict``) or ``j <= i``, 0 above; x, k: (..., C, K), g: (..., C, K)
    float32, non-increasing along C. Returns (..., C, C)."""
    length, width = x.shape[-2:]
    sub = min(SUB, length)
    blocks = length // sub
    lead = x.shape[:-2]
    xb = x.reshape(*lead, blocks, sub, width)
    kb = k.reshape(*lead, blocks, sub, width)
    gb = g.reshape(*lead, blocks, sub, width)
    # the diagonal blocks: every (i, j, channel) decay of a block
    rows = jnp.arange(sub)
    keep = rows[:, None] > rows[None, :] if strict \
        else rows[:, None] >= rows[None, :]
    span = gb[..., :, None, :] - gb[..., None, :, :]
    decay = jnp.exp(jnp.where(keep[:, :, None], span, -jnp.inf))
    diag = (xb[..., :, None, :] * kb[..., None, :, :]
            * decay.astype(x.dtype)).sum(-1)          # (..., blocks, i, j)
    eye = jnp.eye(blocks, dtype=x.dtype)
    out = (diag[..., :, :, None, :] * eye[:, None, :, None]).reshape(
        *lead, length, length)
    if blocks == 1:
        return out
    # off them: about r, the last position of the block of j
    ref = gb[..., -1, :]                               # (..., blocks, K)
    later = jnp.arange(length)[None, :] >= (jnp.arange(blocks)[:, None] + 1) \
        * sub                                          # (blocks, C): i > r
    to_row = jnp.exp(jnp.where(later[..., None], g[..., None, :, :]
                               - ref[..., :, None, :], -jnp.inf))
    from_col = jnp.exp(ref[..., :, None, :] - gb)      # (..., blocks, sub, K)
    off = jnp.einsum('...bic,...bjc->...ibj',
                     x[..., None, :, :] * to_row.astype(x.dtype),
                     kb * from_col.astype(x.dtype))
    return out + off.reshape(*lead, length, length)


def _product(a, b):
    """a @ b as float32 work: at the TPU's default a float32 product is
    one bfloat16 pass."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _unit_lower_inverse(n):
    """``(I + N)^-1`` of the strictly lower part of ``n`` (..., C, C).

    The inverse of a block lower triangular matrix of two blocks is
    ``[[X11, 0], [-X22 N21 X11, X22]]``. With ``X`` the inverses of the
    diagonal blocks of ``size`` positions side by side, the blocks of
    twice that size are ``X - X O X``, ``O`` the part of ``N`` below each
    pair's diagonal blocks: C is reached in ceil(log2 C) doublings (the
    first needs no product). Every product is of inverses of blocks
    and of ``N`` itself, never of powers of ``N``, whose entries grow
    like binomial coefficients where neighbouring keys are alike."""
    length = n.shape[-1]
    rows = jnp.arange(length)

    def below(size):
        """Where a pair of blocks of ``size`` holds ``N21``."""
        pair, block = rows // (2 * size), rows // size
        return (pair[:, None] == pair[None, :]) \
            & (block[:, None] > block[None, :])

    x = jnp.eye(length, dtype=n.dtype) - jnp.where(below(1), n, 0)
    size = 2
    while size < length:
        x = x - _product(_product(x, jnp.where(below(size), n, 0)), x)
        size *= 2
    return x


@jax.custom_vjp
def _wy_solve(n, rk, rv):
    """``(I + N)^-1 Rk`` and ``(I + N)^-1 Rv`` for ``N`` the strictly
    lower part of ``n`` (..., C, C), by the inverse's products; Rk
    (..., C, K), Rv (..., C, V)."""
    return _wy_solve_fwd(n, rk, rv)[0]


def _wy_solve_fwd(n, rk, rv):
    x = _unit_lower_inverse(n)
    w, u = _product(x, rk), _product(x, rv)
    return (w, u), (x, w, u)


def _wy_solve_bwd(res, cot):
    x, w, u = res
    dw, du = cot
    xt = jnp.swapaxes(x, -1, -2)
    drk, drv = _product(xt, dw), _product(xt, du)
    dn = _product(drk, jnp.swapaxes(w, -1, -2)) \
        + _product(drv, jnp.swapaxes(u, -1, -2))
    return -jnp.tril(dn, -1), drk, drv


_wy_solve.defvjp(_wy_solve_fwd, _wy_solve_bwd)


@jax.checkpoint
def _chunk_local(q, k, v, g, beta):
    """What a chunk gives without the state it starts from.

    q, k: (..., C, K); v: (..., C, V); g: (..., C, K) float32, the running
    sum of the log decays inside the chunk; beta: (..., C). Returns (W, U,
    A_qk, q o e^G, k o e^{G_C - G}, e^{G_C}). The (SUB, SUB, K) decays and
    the inverse are made again in the backward pass rather than kept."""
    dtype = q.dtype
    a_kk = beta[..., :, None] * _decayed_products(k, k, g, strict=True)
    a_qk = _decayed_products(q, k, g, strict=False)
    with jax.named_scope('wy_inverse'):
        w, u = _wy_solve(a_kk, beta[..., None] * k * jnp.exp(g).astype(dtype),
                         beta[..., None] * v)
    last = g[..., -1:, :]
    return (w, u, a_qk, q * jnp.exp(g).astype(dtype),
            k * jnp.exp(last - g).astype(dtype), jnp.exp(last[..., 0, :]))


@register('kda_scan', f32_only=True)
def kda_scan(q, k, v, log_alpha, beta, chunk_size=64):
    """The gated delta rule with a decay per key channel, chunked.

    q, k: (B, T, H, K), L2-normalised along K. v: (B, T, H, V).
    log_alpha: (B, T, H, K), <= 0, the log of each channel's decay (taken
    in float32). beta: (B, T, H), the write strength in [0, 1]. Returns
    (B, T, H, V): ``S_t^T q_t / sqrt(K)``. The state is zero at the start
    of every row; T that is no multiple of ``chunk_size`` is padded on the
    right inside (a padded position has ``log_alpha = 0`` and ``beta =
    0``: it neither decays nor writes).
    """
    with jax.named_scope(SCOPE):
        batch, t, heads, width = q.shape
        dtype = q.dtype
        length = chunk_size
        if length > SUB and length % SUB:
            raise ValueError(f'chunk_size {length} is no multiple of {SUB}')
        pad = -t % length
        chunks = (t + pad) // length

        def chunked(arr):
            """(B, T, H, ...) -> (B, H, chunks, C, ...), padded."""
            if pad:
                arr = jnp.pad(arr, ((0, 0), (0, pad))
                              + ((0, 0),) * (arr.ndim - 2))
            arr = arr.reshape(batch, chunks, length, *arr.shape[2:])
            return jnp.moveaxis(arr, 3, 1)

        g = jnp.cumsum(chunked(log_alpha.astype(jnp.float32)), axis=-2)
        w, u, a_qk, qg, kd, whole = _chunk_local(
            chunked(q), chunked(k), chunked(v), g,
            chunked(beta.astype(dtype)))

        def carry(state, chunk):
            """The state a chunk starts from -> the next one's; emits
            that state and the chunk's V' (B, H, C, V)."""
            w_c, u_c, kd_c, whole_c = chunk
            new = u_c - w_c @ state
            return (whole_c.astype(dtype)[..., None] * state
                    + jnp.swapaxes(kd_c, -1, -2) @ new), (state, new)

        by_chunk = lambda arr: jnp.moveaxis(arr, 2, 0)
        _, (before, new) = lax.scan(
            carry, jnp.zeros((batch, heads, width, v.shape[-1]), dtype),
            tuple(map(by_chunk, (w, u, kd, whole))))
        before, new = jnp.moveaxis(before, 0, 2), jnp.moveaxis(new, 0, 2)
        o = (qg @ before + a_qk @ new) * (width ** -0.5)
        o = jnp.moveaxis(o, 1, 3).reshape(batch, chunks * length, heads, -1)
        return o[:, :t].astype(dtype)
