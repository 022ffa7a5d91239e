"""Paged flash-attention decode Pallas TPU kernel (vLLM-style).

The llama paged-decode branch historically gathered each row's logical KV
out of the global page pool (``pool[pages].reshape(B, L, kv, dh)``) — a
full materialization of B·L·kv·dh values through HBM *per layer per
token*, which the roofline auditor duly flags. This kernel instead walks
the int32 block table inside the kernel: the table and per-row offsets
ride in as scalar-prefetch operands (``PrefetchScalarGridSpec``), and the
k/v BlockSpec index_maps read ``pages[b, i]`` directly, so the DMA engine
fetches exactly the pages a row owns — no gather, no L-sized scratch,
and the block table stays a traced VALUE (re-pointing a slot at
different pages never recompiles; the pool keeps its donation alias).

Grid is (B, kv_heads, pages_per_seq) with the page dimension innermost;
a (G, dh) fp32 accumulator (G = q_heads / kv_heads query group) carries
FlashAttention-2 online-softmax state across pages in VMEM scratch.
GQA is the layout: all G queries of a group share the page block the
moment it lands, so K/V bytes are read once per group, not once per
query head — exactly the bandwidth argument for GQA, enforced by
construction.

Masked lanes use the p=0 trick (probabilities zeroed AFTER exp, not by
-inf scores alone): a dead row whose table is all garbage pages yields
l = 0 and a zero output instead of NaN — matching "dead rows compute
garbage nobody reads" in the gather path, but with defined garbage.

Off-TPU the registered op (ops/contrib.py: ``paged_attention_decode``)
falls back to the original gather math, kept operation-for-operation
identical so decode tokens are unchanged on CPU tier-1.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu, _under_mesh

_NEG_INF = -1e30


def _decode_kernel(pages_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, sm_scale, page_size):
    i = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, dh)
    k = k_ref[0, :, 0].astype(jnp.float32)         # (psz, dh)
    v = v_ref[0, :, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # (G, psz)

    b = pl.program_id(0)
    pos = i * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = pos <= off_ref[b]                      # (1, psz)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]        # (G, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    # exp AFTER the max subtraction, zeroed on masked lanes: an
    # all-masked page contributes nothing instead of exp(0)=1 garbage
    p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
    m_ref[...] = m_cur
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == np_ - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_decode_pallas(q, k_pool, v_pool, pages, offset,
                                  sm_scale, interpret=False):
    """q: (B, kv, G, dh); pools: (P, psz, kv, dh); pages: (B, NP) int32;
    offset: (B,) int32 absolute position of each row's current token.
    Returns (B, kv, G, dh) in q.dtype."""
    B, kv, G, dh = q.shape
    psz = k_pool.shape[1]
    NP = pages.shape[1]

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               page_size=psz)
    # index_maps see the scalar-prefetch refs after the grid indices;
    # the k/v maps are where the block table is actually walked
    kv_spec = pl.BlockSpec(
        (1, psz, 1, dh),
        lambda b, h, i, pages_ref, off_ref: (pages_ref[b, i], 0, h, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, kv, NP),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh),
                         lambda b, h, i, pages_ref, off_ref: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, dh),
            lambda b, h, i, pages_ref, off_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, G, dh), q.dtype),
        interpret=interpret,
    )(pages.astype(jnp.int32), offset.astype(jnp.int32), q, k_pool,
      v_pool)


def use_pallas(q, k_pool):
    """TPU, no mesh context, and a lane-tileable head dim; everything
    else takes the gather fallback in ops/contrib.py."""
    dh = q.shape[-1]
    return (_on_tpu() and not _under_mesh() and dh % 128 == 0
            and k_pool.dtype == q.dtype)
