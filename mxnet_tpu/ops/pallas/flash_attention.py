"""Flash attention as a Pallas TPU kernel.

Functional parity target: the reference's fused attention ops
(``_contrib_interleaved_matmul_selfatt_qk``/``valatt`` and encdec variants,
src/operator/contrib/transformer.cc:650-826) compute QK^T → softmax → AV as
separate cuBLAS batched matmuls with an O(T·S) attention matrix in HBM.

TPU re-design: one blockwise kernel with online softmax — the attention
matrix never materializes in HBM; each (query-block × key-block) tile lives
in VMEM, scores accumulate on the MXU in fp32 with running row max/sum
(the Flash-Attention-2 recurrence). Layout puts head_dim on the lane axis
(128) and the query block on sublanes, matching the MXU tiling table in
/opt/skills/guides/pallas_guide.md.

The backward pass recomputes attention blockwise under ``jax.checkpoint``
semantics via a custom VJP (recompute beats storing the O(T·S) matrix on
HBM-bandwidth-bound TPUs).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _on_tpu():
    return jax.devices()[0].platform == 'tpu'


def _under_mesh():
    """True inside an ``mx.sharding`` mesh context, the Trainer's own test
    for switching its Pallas update off. GSPMD cannot partition an opaque
    ``pallas_call`` (Mosaic refuses at lowering: "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    the dispatch gates of this package take their XLA branch there.
    Kernels called from inside a ``shard_map`` (ring attention) are per
    device already and ask only :func:`_on_tpu`."""
    from ...sharding.context import current
    return current() is not None


# ------------------------------------------------------------------ kernel

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref=None, l_ref=None, *,
                      block_k, sm_scale, causal, q_offset):
    """One (batch·head, q-block) program: stream key blocks, online softmax.

    q_ref: (1, block_q, d); k_ref/v_ref: (1, S, d); o_ref: (1, block_q, d).
    With m_ref/l_ref supplied, o is left UNNORMALIZED and the running
    row max/denominator are written out — the ring-attention form where
    blocks from other devices still need merging.
    """
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, d)
    block_q, d = q.shape
    s_len = k_ref.shape[1]
    qi = pl.program_id(1)

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    num_kb = s_len // block_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        if causal:
            rows = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                           # (bq, bk)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # skip key blocks entirely above the diagonal of this q block
        last = (q_offset + (qi + 1) * block_q + block_k - 1) // block_k
        num_iters = jnp.minimum(num_kb, last)
        m, l, acc = jax.lax.fori_loop(0, num_iters, body, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))

    if m_ref is None:
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    else:
        o_ref[0] = acc.astype(o_ref.dtype)
        m_ref[0, 0] = m[:, 0]
        l_ref[0, 0] = l[:, 0]


def _flash_call(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                q_offset, return_stats):
    """Shared pallas_call scaffolding for both kernel variants.

    q: (BH, T, d), k/v: (BH, S, d). Block sizes must divide T/S exactly
    (callers guarantee via _choose_block). ``return_stats`` selects the
    3-output form: unnormalized acc + row max + row denominator.
    """
    bh, t, d = q.shape
    s = k.shape[1]
    assert t % block_q == 0 and s % block_k == 0

    grid = (bh, t // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, sm_scale=sm_scale,
        causal=causal, q_offset=q_offset)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
    ]
    if return_stats:
        # stats ride as (bh, 1, t) blocked (1, 1, block_q): the Mosaic
        # lowering requires the last two block dims to divide (8, 128)
        # or equal the array dims — a 2-D (1, block_q) block over
        # (bh, t) violates that on real TPU (sublane dim 1 vs bh)
        out_specs = [
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ]
        acc, m, l = pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=interpret,
            name='mx_flash_attention_stats')(q, k, v)
        return acc, m[:, 0], l[:, 0]
    out_specs = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, t, d), q.dtype)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        name='mx_flash_attention')(q, k, v)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    """Normalized single-device form; bottom-right causal when T < S."""
    return _flash_call(q, k, v, sm_scale, causal, block_q, block_k,
                       interpret, q_offset=k.shape[1] - q.shape[1],
                       return_stats=False)


def _stats_xla(q, k, v, sm_scale, causal):
    """Pure-XLA twin of the stats kernel — the differentiation path
    (recompute backward, mirroring _flash3_bwd's choice) and the
    off-TPU fallback. Diagonal-block causal: q_pos >= k_pos."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum('bqd,bkd->bqk', qf, kf) * sm_scale
    if causal:
        t, src = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, src), bool))
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum('bqk,bkd->bqd', p, vf)
    return acc, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_stats(q, k, v, sm_scale, causal=False, interpret=False):
    """Blockwise attention that returns (acc, m, l): UNNORMALIZED output
    plus the online-softmax row statistics, so the caller can merge
    results across devices (ring attention over the sp axis,
    parallel/ring_attention.py). q: (BH, T, d); k/v: (BH, S, d).
    Causal here is the DIAGONAL-block form: positions align 1:1 (T == S,
    same shard), mask is q_pos >= k_pos.

    Differentiable: backward recomputes through the pure-XLA twin
    (_stats_xla), the same recompute-over-store trade as _flash3."""
    if _on_tpu() and not interpret:
        bq = _choose_block(q.shape[1], 128)
        bk = _choose_block(k.shape[1], 128)
        if bq >= 32 and bk >= 32:
            return tuple(_flash_call(q, k, v, sm_scale, causal, bq, bk,
                                     False, q_offset=0, return_stats=True))
        return _stats_xla(q, k, v, sm_scale, causal)
    if interpret:
        bq = _choose_block(q.shape[1], 128)
        bk = _choose_block(k.shape[1], 128)
        return tuple(_flash_call(q, k, v, sm_scale, causal, bq, bk,
                                 True, q_offset=0, return_stats=True))
    return _stats_xla(q, k, v, sm_scale, causal)


def _stats_fwd(q, k, v, sm_scale, causal, interpret):
    return flash_attention_stats(q, k, v, sm_scale, causal, interpret), \
        (q, k, v)


def _stats_bwd(sm_scale, causal, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _stats_xla(q_, k_, v_, sm_scale,
                                                   causal), q, k, v)
    return vjp(g)


flash_attention_stats.defvjp(_stats_fwd, _stats_bwd)


def _reference_attention(q, k, v, sm_scale, causal):
    """XLA fallback/backward: plain fused-by-XLA attention, fp32 softmax."""
    s = jnp.einsum('bqd,bkd->bqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        t, src = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, src), bool), k=src - t)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bqk,bkd->bqd', p, v.astype(jnp.float32)).astype(
        q.dtype)


def _choose_block(n, preferred):
    b = min(preferred, n)
    while n % b:
        b -= 1
    return b


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash3(q, k, v, sm_scale, causal, block_q, block_k):
    # block_q == 0 → XLA path (off-TPU, or shapes the kernel tiles badly);
    # CI exercises the Pallas kernel via flash_attention(interpret=True)
    if _on_tpu() and block_q:
        return _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret=False)
    return _reference_attention(q, k, v, sm_scale, causal)


def _flash3_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    return _flash3(q, k, v, sm_scale, causal, block_q, block_k), (q, k, v)


def _flash3_bwd(sm_scale, causal, block_q, block_k, res, g):
    """Backward by blockless recompute in XLA (jax.checkpoint semantics:
    trade FLOPs for HBM; the O(T·S) matrix lives only inside the fused
    backward computation)."""
    q, k, v = res
    f32 = jnp.float32
    qf, kf, vf, gf = (x.astype(f32) for x in (q, k, v, g))
    s = jnp.einsum('bqd,bkd->bqk', qf, kf) * sm_scale
    if causal:
        t, src = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, src), bool), k=src - t)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum('bqk,bqd->bkd', p, gf)
    dp = jnp.einsum('bqd,bkd->bqk', gf, vf)
    delta = jnp.sum(p * dp, axis=-1, keepdims=True)
    ds = p * (dp - delta) * sm_scale
    dq = jnp.einsum('bqk,bkd->bqd', ds, kf)
    dk = jnp.einsum('bqk,bqd->bkd', ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=128,
                    block_k=128, interpret=False):
    """Blockwise fused attention.

    Args:
      q: (..., T, d) queries — any number of leading batch/head dims.
      k, v: (..., S, d) keys/values with matching leading dims.
      sm_scale: score scale; default 1/sqrt(d).
      causal: lower-triangular masking (decoder self-attention).
      interpret: run the Pallas kernel in interpreter mode (CPU testing).

    Returns (..., T, d) in the input dtype; softmax/accumulation in fp32.
    """
    q_shape = q.shape
    d = q_shape[-1]
    t, s = q.shape[-2], k.shape[-2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    qr = q.reshape((-1, t, d))
    kr = k.reshape((-1, s, d))
    vr = v.reshape((-1, s, d))
    if causal and t > s:
        # bottom-right causal with more queries than keys leaves fully
        # masked rows; keep forward/backward consistent via the XLA path
        # (the kernel's online softmax would emit zeros there)
        return _reference_attention(qr, kr, vr, sm_scale,
                                    causal).reshape(q_shape)
    if interpret:
        bq = _choose_block(t, block_q)
        bk = _choose_block(s, block_k)
        out = _flash_fwd(qr, kr, vr, sm_scale, causal, bq, bk,
                         interpret=True)
        return out.reshape(q_shape)
    bq = bk = 0                 # 0: the XLA path (off-TPU, under a mesh)
    if _on_tpu() and not _under_mesh():
        bq = block_q if t % block_q == 0 else _choose_block(t, block_q)
        bk = block_k if s % block_k == 0 else _choose_block(s, block_k)
        if bq < 32 or bk < 32:
            # awkward sequence lengths (prime factors < MXU tile) would
            # degrade to scalar-ish tiles; XLA's fused attention is faster
            bq = bk = 0
    out = _flash3(qr, kr, vr, sm_scale, causal, bq, bk)
    return out.reshape(q_shape)
