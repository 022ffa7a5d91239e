"""State-space layers (Mamba-2): the causal depthwise convolution before
the scan, and the selective scan itself in its chunked (SSD) form; and
the gated short convolution of LFM2, which shares the convolution's taps.

NEW capability over the reference (it has no state-space layer). The
recurrence of one head, state ``S`` of (P, N), from zero at the start of
every row::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

:func:`ssm_scan` computes it a chunk of positions at a time (Dao & Gu
2024, "Transformers are SSMs", the state-space dual form): inside a chunk
``y = (L o C B^T)(dt x)`` with ``L[i, j] = exp(sum_{j < s <= i} dt_s A)``
for ``j <= i``, a chunk's own end state ``B^T (decay to the end o dt x)``,
the states carried from chunk to chunk by a ``lax.scan`` over T / chunk
steps, and what the carried state adds, ``C S_prev`` decayed from the
chunk's start. Four matrix products a chunk in place of T dependent
steps. Plain ``jax.numpy``, differentiated by JAX; there is one form and
no kernel. ``gluon.nn.Mamba2Mixer`` is the Block.

A decay is the exponential of a difference of the cumulative sum of
``dt A`` (float32, always <= 0 where it is used). The differences above
the diagonal are positive and may be large: they are masked to ``-inf``
*before* the exponential, so neither the forward nor the backward ever
meets ``inf * 0``.
"""

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

CONV_SCOPE = 'mx.ssm_conv'      # the depthwise causal convolution + silu
SCAN_SCOPE = 'mx.ssm_scan'      # the chunked selective scan
SHORT_CONV_SCOPE = 'mx.short_conv'  # LFM2's two gates and the taps


def causal_taps(x, weight):
    """The causal depthwise convolution along the positions, no bias.

    x: (B, T, C). weight: (C, K), tap K - 1 on the position itself and
    tap 0 on the one K - 1 before it (a ``Conv1d(C, C, K, groups=C,
    padding=K - 1)`` cut to its first T outputs). Positions before a
    row's start read as zero. Returns (B, T, C).
    """
    taps = weight.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, k:k + t] * weight[:, k] for k in range(taps))


@register('ssm_conv', f32_only=True)
def ssm_conv(x, weight, bias=None):
    """silu of a causal depthwise convolution along the positions
    (:func:`causal_taps`); bias: (C,) or None. Returns (B, T, C)."""
    with jax.named_scope(CONV_SCOPE):
        out = causal_taps(x, weight)
        if bias is not None:
            out = out + bias
        return jax.nn.silu(out).astype(x.dtype)


@register('short_conv', f32_only=True)
def short_conv(b, c, x, weight):
    """LFM2's gated short convolution: ``c * conv(b * x)``, the
    convolution :func:`causal_taps` with no bias and **no activation**.

    b, c, x: (B, T, C), the input gate, the output gate and the values.
    weight: (C, K). Both gates lie inside the scope, so that its device
    time is the whole of the mixer but its two projections. Returns
    (B, T, C).
    """
    with jax.named_scope(SHORT_CONV_SCOPE):
        return (c * causal_taps(b * x, weight)).astype(x.dtype)


@jax.checkpoint
def _chunk_local(dx, cum, b, c):
    """What a chunk gives without its neighbours: its outputs from its
    own inputs, and the state it adds by its end.

    dx: (B, c, G, R, L, P), ``dt x`` with the heads as G groups of R;
    cum: (B, c, G, R, L) float32, the running sum of ``dt A`` inside the
    chunk; b, c: (B, c, G, L, N). Returns (y (B, c, G, R, L, P), states
    (B, c, G, R, P, N)). The (L, L) arrays, a head each, are made again
    in the backward pass rather than kept: 4 H L bytes a token each."""
    length = dx.shape[-2]
    lower = jnp.tril(jnp.ones((length, length), bool))
    # cum_i - cum_j, (B, c, G, R, i, j): <= 0 on and below the diagonal
    span = cum[..., :, None] - cum[..., None, :]
    decay = jnp.exp(jnp.where(lower, span, -jnp.inf)).astype(dx.dtype)
    scores = jnp.einsum('bcgin,bcgjn->bcgij', c, b)
    y = jnp.einsum('bcgrij,bcgrjp->bcgrip', scores[:, :, :, None] * decay,
                   dx)
    to_end = jnp.exp(cum[..., -1:] - cum).astype(dx.dtype)
    states = jnp.einsum('bcgjn,bcgrjp->bcgrpn', b, to_end[..., None] * dx)
    return y, states


@register('ssm_scan', f32_only=True)
def ssm_scan(x, dt, a, b, c, d=None, chunk_size=128):
    """The Mamba-2 selective scan, chunked.

    x: (B, T, H, P). dt: (B, T, H), the step sizes, positive. a: (H,),
    negative (``-exp(A_log)``). b, c: (B, T, G, N), head h reads group
    ``h // (H / G)``. d: (H,) or None, the skip ``D x``. Returns
    (B, T, H, P). The state is zero at the start of every row; T that is
    no multiple of ``chunk_size`` is padded on the right inside (a padded
    position has ``dt = 0``: it neither decays nor feeds the state).
    """
    with jax.named_scope(SCAN_SCOPE):
        batch, t, heads, p = x.shape
        groups = b.shape[2]
        per = heads // groups
        length = chunk_size
        pad = -t % length
        chunks = (t + pad) // length

        def chunked(arr):
            """(B, T, heads or groups, ...) -> (B, c, L, the rest), the
            positions padded to whole chunks."""
            if pad:
                arr = jnp.pad(arr, ((0, 0), (0, pad))
                              + ((0, 0),) * (arr.ndim - 2))
            return arr.reshape(batch, chunks, length, *arr.shape[2:])

        # positions last but one, so that a chunk's products are matrix
        # products over (L, L), (L, P) and (L, N) with the heads in front
        cum = jnp.cumsum(chunked(
            dt.astype(jnp.float32) * a.astype(jnp.float32)).reshape(
            batch, chunks, length, groups, per).transpose(0, 1, 3, 4, 2),
            axis=-1)
        dx = chunked(x * dt.astype(x.dtype)[..., None]).reshape(
            batch, chunks, length, groups, per, p).transpose(0, 1, 3, 4, 2, 5)
        bc = chunked(b).transpose(0, 1, 3, 2, 4)
        cc = chunked(c).transpose(0, 1, 3, 2, 4)
        y, states = _chunk_local(dx, cum, bc, cc)

        # the state a chunk starts from: the one before it decayed over
        # that whole chunk, plus what that chunk added
        whole = jnp.exp(cum[..., -1]).astype(x.dtype)      # (B, c, G, R)

        def carry(state, chunk):
            decay, added = chunk
            return state * decay[..., None, None] + added, state

        _, before = lax.scan(
            carry, jnp.zeros_like(states[:, 0]),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(states, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)            # (B, c, G, R, P, N)
        y = y + jnp.einsum('bcgin,bcgrpn->bcgrip', cc, before) \
            * jnp.exp(cum).astype(x.dtype)[..., None]
        y = y.transpose(0, 1, 4, 2, 3, 5).reshape(
            batch, chunks * length, heads, p)[:, :t]
        if d is not None:
            y = y + x * d[:, None].astype(x.dtype)
        return y.astype(x.dtype)
