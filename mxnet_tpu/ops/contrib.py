"""Contrib ops: transformer attention kernels, detection helpers, fused
optimizer utilities.

Reference: ``src/operator/contrib/`` (31.5 kLoC). The headline items for a
transformer stack are the interleaved-matmul self-attention ops
(src/operator/contrib/transformer.cc:650-826) — re-designed here as einsum
compositions that XLA maps onto the MXU, plus a whole fused
``multi_head_attention`` (the form the reference never had; on TPU one fused
softmax(QK^T)V is both simpler and faster). A Pallas flash-attention path
plugs in underneath for long sequences.
"""

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


# ---------------------------------------------------- interleaved attention
# Reference layout: qkv (seq, batch, num_heads * 3 * head_dim) interleaved.
@register('interleaved_matmul_selfatt_qk')
def interleaved_matmul_selfatt_qk(queries_keys_values, heads):
    """Reference: src/operator/contrib/transformer.cc:650 — Q·K^T from
    interleaved QKV projections. Output: (batch*heads, seq, seq)."""
    s, b, e = queries_keys_values.shape
    hd = e // (3 * heads)
    x = queries_keys_values.reshape(s, b, heads, 3, hd)
    q = x[:, :, :, 0]  # (s, b, h, d)
    k = x[:, :, :, 1]
    q = q * (hd ** -0.5)
    scores = jnp.einsum('sbhd,tbhd->bhst', q, k)
    return scores.reshape(b * heads, s, s)


@register('interleaved_matmul_selfatt_valatt')
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads):
    """Reference: transformer.cc:710 — attention · V back to interleaved
    layout. attention: (batch*heads, seq, seq)."""
    s, b, e = queries_keys_values.shape
    hd = e // (3 * heads)
    x = queries_keys_values.reshape(s, b, heads, 3, hd)
    v = x[:, :, :, 2]  # (s, b, h, d)
    att = attention.reshape(b, heads, s, s)
    out = jnp.einsum('bhst,tbhd->sbhd', att, v)
    return out.reshape(s, b, heads * hd)


@register('interleaved_matmul_encdec_qk')
def interleaved_matmul_encdec_qk(queries, keys_values, heads):
    """Reference: transformer.cc:770 — cross-attention Q·K^T."""
    sq, b, e = queries.shape
    sk = keys_values.shape[0]
    hd = e // heads
    q = queries.reshape(sq, b, heads, hd) * (hd ** -0.5)
    kv = keys_values.reshape(sk, b, heads, 2, hd)
    k = kv[:, :, :, 0]
    scores = jnp.einsum('sbhd,tbhd->bhst', q, k)
    return scores.reshape(b * heads, sq, sk)


@register('interleaved_matmul_encdec_valatt')
def interleaved_matmul_encdec_valatt(keys_values, attention, heads):
    sk, b, e = keys_values.shape
    hd = e // (2 * heads)
    kv = keys_values.reshape(sk, b, heads, 2, hd)
    v = kv[:, :, :, 1]
    sq = attention.shape[1]
    att = attention.reshape(b, heads, sq, sk)
    out = jnp.einsum('bhst,tbhd->sbhd', att, v)
    return out.reshape(sq, b, heads * hd)


def _attention_pallas_cost(eqn):
    """Analytical cost for the flash-attention kernels
    (mx.analysis.costs), by the operands each takes. Forward
    (q, k, v): QK^T over d and PV over v's width, 2·BH·T·S·(d + dv)
    flops, which is 4·B·H·T·S·d at one width. Backward
    (q, k, v, do, lse, delta): the five products of a tile, the scores
    again, dP and dV over dv, dK and dQ over d, 2·BH·T·S·(3d + 2dv).
    The sum is the same whether the heads are a leading axis or packed
    along the last one (it is linear in the widths). Causal kernels skip
    ~half the blocks; this prices the dense upper bound since masking
    isn't visible in the eqn. Non-pallas equations return None so the
    primitive table handles the XLA fallback."""
    if eqn.primitive.name != 'pallas_call':
        return None
    q, k, v = (x.aval for x in eqn.invars[:3])
    t, d = q.shape[-2], q.shape[-1]
    s, dv = k.shape[-2], v.shape[-1]
    bh = 1
    for n in q.shape[:-2]:
        bh *= n
    backward = len(eqn.invars) == 6
    return 2 * bh * t * s * (3 * d + 2 * dv if backward else d + dv)


@register('flash_attention', f32_only=True, fused_kernel=True,
          cost=_attention_pallas_cost)
def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=None,
                    block_k=None):
    """Blockwise fused attention (Pallas on TPU, forward and backward;
    XLA fallback elsewhere).

    q: (..., T, d); k: (..., S, d); v: (..., S, dv). ``block_q`` and
    ``block_k`` bound the tile; by default it is chosen from the shape.
    New TPU-native capability — the reference's closest assets are the
    interleaved matmul kernels above (transformer.cc:650-826), which
    materialize the full score matrix.
    """
    from .pallas.flash_attention import flash_attention as _fa
    return _fa(q, k, v, sm_scale=sm_scale, causal=causal,
               block_q=block_q, block_k=block_k)


def _paged_attention_cost(eqn):
    """Analytical cost for the paged decode kernel: QK^T + PV over every
    table-mapped position, 4·B·H·L·dh flops with L = pages_per_seq ·
    page_size (dense upper bound; the per-row <= offset mask isn't
    visible in the eqn). Operand order of the pallas_call is
    (pages, offset, q, k_pool, v_pool)."""
    if eqn.primitive.name != 'pallas_call':
        return None
    b, kv, g, dh = eqn.outvars[0].aval.shape
    np_ = eqn.invars[0].aval.shape[1]
    psz = eqn.invars[3].aval.shape[1]
    return 4 * b * kv * g * np_ * psz * dh


@register('paged_attention_decode', f32_only=True, fused_kernel=True,
          cost=_paged_attention_cost)
def paged_attention_decode(q, k_pool, v_pool, pages, offset,
                           sm_scale=None):
    """One decode step of attention over a paged KV pool (vLLM-style).

    q: (B, H, dh) — this step's queries, RoPE applied; k_pool/v_pool:
    (num_pages, page_size, kv_heads, dh) global pools (already holding
    this step's K/V, scattered by the caller); pages: (B, pages_per_seq)
    int32 block table; offset: (B,) int32 absolute position of row b's
    current token (row b attends logical positions <= offset[b]).

    On TPU the int32 block table is walked INSIDE the kernel
    (ops/pallas/paged_attention.py) — no gather, no (B, L) KV
    materialization. Elsewhere this is the original gather math from
    the llama paged branch, operation-for-operation, so decode tokens
    are identical on CPU tier-1.
    """
    B, H, dh = q.shape
    kv = k_pool.shape[2]
    scale = (dh ** -0.5) if sm_scale is None else sm_scale
    from .pallas import paged_attention as _pa
    if _pa.use_pallas(q, k_pool):
        # GQA grouping: q heads [j*G, (j+1)*G) share kv head j
        qg = q.reshape(B, kv, H // kv, dh)
        out = _pa.paged_attention_decode_pallas(
            qg, k_pool, v_pool, pages, offset, scale)
        return out.reshape(B, H, dh)
    psz = k_pool.shape[1]
    L = pages.shape[1] * psz
    kf = k_pool[pages].reshape(B, L, kv, dh)
    vf = v_pool[pages].reshape(B, L, kv, dh)
    rep = H // kv
    kf = jnp.repeat(kf, rep, 2) if rep > 1 else kf
    vf = jnp.repeat(vf, rep, 2) if rep > 1 else vf
    scores = jnp.einsum('bshd,blhd->bhsl', q[:, None].astype(jnp.float32),
                        kf.astype(jnp.float32)) * scale
    mask = jnp.arange(L)[None, :] <= offset[:, None]          # (B, L)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum('bhsl,blhd->bshd', probs,
                     vf.astype(jnp.float32)).astype(q.dtype)
    return out[:, 0]


@register('multi_head_attention', fused_kernel=True,
          cost=_attention_pallas_cost)
def multi_head_attention(q, k, v, num_heads, mask=None, dropout_p=0.0,
                         causal=False, key=None, sm_scale=None):
    """Fused scaled-dot-product attention (batch, seq, embed) — the TPU-first
    replacement for the interleaved-matmul pipeline. Unmasked/causal cases
    take the Pallas flash path (ops/pallas/flash_attention.py); explicit
    masks use jax.nn.dot_product_attention, which XLA fuses.

    ``v`` may have another head width than ``q`` and ``k``
    (``v.shape[-1] / num_heads``; latent attention: 192 for the scores,
    128 for the values): the flash path and the dropout path take each
    at its own width; for jax.nn.dot_product_attention the narrower side
    is zero-padded to the wider, which changes neither a score nor a kept
    output column. The output is (batch, seq, num_heads x v's head
    width). ``sm_scale`` is the score scale; default 1/sqrt(q's head
    width)."""
    # one scope round every branch: a device operation of a profile is
    # put down to attention whichever implementation ran
    with jax.named_scope('mx.attention'):
        return _attention(q, k, v, num_heads, mask, dropout_p, causal, key,
                          sm_scale)


def _attention(q, k, v, num_heads, mask, dropout_p, causal, key,
               sm_scale=None):
    if mask is None and dropout_p == 0.0:
        # the kernels take the heads packed along the last axis, as q, k
        # and v come; only the XLA branch behind the same gate splits them
        from .pallas.flash_attention import flash_attention_packed
        return flash_attention_packed(q, k, v, num_heads,
                                      sm_scale=sm_scale, causal=causal)
    b, sq, e = q.shape
    hd = e // num_heads
    vd = v.shape[-1] // num_heads
    qh = q.reshape(b, sq, num_heads, hd)
    kh = k.reshape(b, k.shape[1], num_heads, hd)
    vh = v.reshape(b, v.shape[1], num_heads, vd)
    out = _attention_heads(qh, kh, vh, mask, dropout_p, causal, key,
                           sm_scale)
    return out.reshape(b, sq, num_heads * vd)


def _attention_heads(qh, kh, vh, mask, dropout_p, causal, key, sm_scale):
    """The masked and the dropout branch:
    (B, T, H, d) x (B, S, H, d) x (B, S, H, dv) -> (B, T, H, dv)."""
    sq, sk = qh.shape[1], kh.shape[1]
    if causal:
        # explicit bottom-right-aligned causal mask so this branch agrees
        # with the flash path when T != S (decode with KV cache)
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)[None, None]
        mask = tri if mask is None else jnp.logical_and(mask, tri)
    if dropout_p > 0.0:
        if key is None:
            raise ValueError(
                'multi_head_attention with dropout_p > 0 needs key= (a '
                'jax PRNG key); pass one or apply nn.Dropout outside')
        hd_scale = qh.shape[-1] ** -0.5 if sm_scale is None else sm_scale
        s = jnp.einsum('bqhd,bkhd->bhqk', qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) * hd_scale
        if mask is not None:
            s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        return jnp.einsum('bhqk,bkhd->bqhd', p,
                          vh.astype(jnp.float32)).astype(qh.dtype)
    hd, vd = qh.shape[-1], vh.shape[-1]
    if vd != hd:
        if sm_scale is None:
            sm_scale = hd ** -0.5       # of the width before the padding
        wide = max(hd, vd)
        pad = lambda a: a if a.shape[-1] == wide else jnp.pad(
            a, ((0, 0),) * 3 + ((0, wide - a.shape[-1]),))
        qh, kh, vh = pad(qh), pad(kh), pad(vh)
    out = jax.nn.dot_product_attention(qh, kh, vh, mask=mask,
                                       scale=sm_scale)
    return out if vd == hd else out[..., :vd]


# ----------------------------------------------------------- detection utils
@register('box_iou', differentiable=False)
def box_iou(lhs, rhs, format='corner'):
    """Reference: src/operator/contrib/bounding_box.cc _contrib_box_iou."""
    if format == 'center':
        def corner(b):
            cx, cy, w, h = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
            return jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2,
                              cy + h / 2], axis=-1)
        lhs, rhs = corner(lhs), corner(rhs)
    l = lhs[..., :, None, :]
    r = rhs[..., None, :, :]
    tl = jnp.maximum(l[..., :2], r[..., :2])
    br = jnp.minimum(l[..., 2:], r[..., 2:])
    wh = jnp.clip(br - tl, 0, None)
    inter = wh[..., 0] * wh[..., 1]
    area_l = (l[..., 2] - l[..., 0]) * (l[..., 3] - l[..., 1])
    area_r = (r[..., 2] - r[..., 0]) * (r[..., 3] - r[..., 1])
    return inter / jnp.maximum(area_l + area_r - inter, 1e-12)


@register('box_nms', differentiable=False)
def box_nms(data, overlap_thresh=0.5, valid_thresh=0, topk=-1, coord_start=2,
            score_index=1, id_index=-1, background_id=-1, force_suppress=False,
            in_format='corner', out_format='corner'):
    """Reference: src/operator/contrib/bounding_box.cc box_nms. Static-shape
    NMS via iterative suppression with lax.fori_loop (TPU-friendly: no
    dynamic shapes — suppressed boxes get score -1, as in the reference)."""
    boxes = data[..., coord_start:coord_start + 4]
    scores = data[..., score_index]
    ids = data[..., id_index] if id_index >= 0 else None
    n = data.shape[-2]

    order = jnp.argsort(-scores, axis=-1)
    boxes_s = jnp.take_along_axis(boxes, order[..., None], axis=-2)
    scores_s = jnp.take_along_axis(scores, order, axis=-1)
    iou = box_iou(boxes_s, boxes_s, format=in_format)
    ids_s = None
    if ids is not None:
        ids_s = jnp.take_along_axis(ids, order, axis=-1)
        if not force_suppress:
            same = ids_s[..., :, None] == ids_s[..., None, :]
            iou = jnp.where(same, iou, 0.0)

    valid = scores_s > valid_thresh
    if ids_s is not None and background_id >= 0:
        valid = valid & (ids_s != background_id)
    if topk > 0:
        # only the top-k scored candidates enter NMS (reference semantics)
        valid = valid & (jnp.arange(n) < topk)

    def body(i, keep):
        sup = (iou[..., i, :] > overlap_thresh) & keep[..., i][..., None] & \
            (jnp.arange(n) > i)
        return keep & ~sup

    keep = lax.fori_loop(0, n, body, valid)
    out_scores = jnp.where(keep, scores_s, -1.0)
    out = jnp.take_along_axis(data, order[..., None], axis=-2)
    out = out.at[..., score_index].set(out_scores)
    if out_format != in_format:
        c = out[..., coord_start:coord_start + 4]
        if out_format == 'center':
            conv = jnp.stack([(c[..., 0] + c[..., 2]) / 2,
                              (c[..., 1] + c[..., 3]) / 2,
                              c[..., 2] - c[..., 0],
                              c[..., 3] - c[..., 1]], axis=-1)
        else:
            conv = jnp.stack([c[..., 0] - c[..., 2] / 2,
                              c[..., 1] - c[..., 3] / 2,
                              c[..., 0] + c[..., 2] / 2,
                              c[..., 1] + c[..., 3] / 2], axis=-1)
        out = out.at[..., coord_start:coord_start + 4].set(conv)
    return out


@register('roi_align')
def roi_align(data, rois, pooled_size, spatial_scale, sample_ratio=2):
    """Reference: src/operator/contrib/roi_align.cc. Bilinear sampling via
    map_coordinates-style gathers (XLA gather, differentiable)."""
    ph, pw = (pooled_size if isinstance(pooled_size, (tuple, list))
              else (pooled_size, pooled_size))
    n, c, h, w = data.shape

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = roi[1] * spatial_scale, roi[2] * spatial_scale, \
            roi[3] * spatial_scale, roi[4] * spatial_scale
        rw = jnp.maximum(x2 - x1, 1.0)
        rh = jnp.maximum(y2 - y1, 1.0)
        bin_w, bin_h = rw / pw, rh / ph
        s = max(sample_ratio, 1)
        ys = y1 + (jnp.arange(ph)[:, None] + (jnp.arange(s)[None, :] + 0.5)
                   / s) * bin_h
        xs = x1 + (jnp.arange(pw)[:, None] + (jnp.arange(s)[None, :] + 0.5)
                   / s) * bin_w
        ys = ys.reshape(-1)
        xs = xs.reshape(-1)
        yy, xx = jnp.meshgrid(ys, xs, indexing='ij')
        img = data[batch_idx]

        y0 = jnp.clip(jnp.floor(yy), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xx), 0, w - 1)
        y1i = jnp.clip(y0 + 1, 0, h - 1)
        x1i = jnp.clip(x0 + 1, 0, w - 1)
        wy = yy - y0
        wx = xx - x0
        y0 = y0.astype(jnp.int32); x0 = x0.astype(jnp.int32)
        y1i = y1i.astype(jnp.int32); x1i = x1i.astype(jnp.int32)
        v = (img[:, y0, x0] * (1 - wy) * (1 - wx) +
             img[:, y1i, x0] * wy * (1 - wx) +
             img[:, y0, x1i] * (1 - wy) * wx +
             img[:, y1i, x1i] * wy * wx)  # (c, ph*s, pw*s)
        v = v.reshape(c, ph, s, pw, s)
        return v.mean(axis=(2, 4))

    return jax.vmap(one_roi)(rois)


@register('all_finite', differentiable=False)
def all_finite(*arrays, init_output=True):
    """Reference: src/operator/contrib/all_finite.cc — AMP overflow check."""
    ok = jnp.array(True)
    for a in arrays:
        ok = ok & jnp.all(jnp.isfinite(a))
    return ok


@register('index_copy')
def index_copy(old, index, new_tensor):
    return old.at[index.astype(jnp.int32)].set(new_tensor)


@register('index_add')
def index_add(old, index, new_tensor):
    return old.at[index.astype(jnp.int32)].add(new_tensor)


@register('getnnz', differentiable=False)
def getnnz(data, axis=None):
    return jnp.count_nonzero(data, axis=axis)


@register('count_sketch')
def count_sketch(data, h, s, out_dim):
    """Reference: src/operator/contrib/count_sketch.cc."""
    idx = h.astype(jnp.int32)
    signed = data * s
    out = jnp.zeros(data.shape[:-1] + (out_dim,), dtype=data.dtype)
    return out.at[..., idx].add(signed)


@register('bipartite_matching', differentiable=False, n_out=2)
def bipartite_matching(data, threshold=0.5, is_ascend=False, topk=-1):
    """Greedy bipartite matching (reference
    src/operator/contrib/bounding_box.cc _contrib_bipartite_matching).

    data: (..., N, M) pairwise scores. Returns (row→col match, col→row
    match), -1 for unmatched. The greedy loop over min(N, M) rounds is a
    ``lax.scan`` masking out matched rows/cols each round — fixed trip
    count, so XLA compiles it to one fused loop.
    """
    scores = data.astype(jnp.float32)
    N, M = scores.shape[-2], scores.shape[-1]
    batch = scores.shape[:-2]
    s = scores.reshape((-1, N, M))
    sign = 1.0 if is_ascend else -1.0
    key_ = sign * s  # minimize key_
    BIG = jnp.float32(3.4e38)
    rounds = min(N, M) if topk < 0 else min(topk, min(N, M))
    ok = (s > threshold) if not is_ascend else (s < threshold)

    def body(carry, _):
        kmat, rmatch, cmatch = carry
        flat = kmat.reshape(kmat.shape[0], -1)
        idx = jnp.argmin(flat, axis=1)
        r, c = idx // M, idx % M
        valid = jnp.take_along_axis(flat, idx[:, None], 1)[:, 0] < BIG
        b = jnp.arange(kmat.shape[0])
        good = valid & ok[b, r, c]
        rmatch = rmatch.at[b, r].set(jnp.where(good, c, rmatch[b, r]))
        cmatch = cmatch.at[b, c].set(jnp.where(good, r, cmatch[b, c]))
        kmat = kmat.at[b, r, :].set(jnp.where(valid[:, None], BIG,
                                              kmat[b, r, :]))
        kmat = kmat.at[b, :, c].set(jnp.where(valid[:, None], BIG,
                                              kmat[b, :, c]))
        return (kmat, rmatch, cmatch), None

    rmatch0 = jnp.full((s.shape[0], N), -1.0)
    cmatch0 = jnp.full((s.shape[0], M), -1.0)
    (_, rmatch, cmatch), _ = lax.scan(body, (key_, rmatch0, cmatch0),
                                      None, length=rounds)
    return (rmatch.reshape(batch + (N,)), cmatch.reshape(batch + (M,)))


@register('sparse_embedding', aliases=('SparseEmbedding',))
def sparse_embedding(data, weight, input_dim=None, output_dim=None,
                     dtype=None, sparse_grad=False):
    """Reference: src/operator/tensor/indexing_op.cc _contrib_SparseEmbedding.
    On TPU the row-sparse gradient path is an XLA scatter-add over the dense
    table (same dispatch the dense embedding uses), so this is an alias."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register('group_adagrad_update', n_out=2)
def group_adagrad_update(weight, grad, history, lr=0.01, rescale_grad=1.0,
                         clip_gradient=-1.0, epsilon=1e-5):
    """Reference: src/operator/contrib/optimizer_op.cc
    _contrib_group_adagrad_update (per-row accumulated squared-norm
    AdaGrad, the row_sparse-friendly variant). Returns (weight, history).
    """
    g = grad * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    axes = tuple(range(1, g.ndim))
    hist = history + jnp.mean(g * g, axis=axes, keepdims=True) \
        if g.ndim > 1 else history + g * g
    w = weight - lr * g / (jnp.sqrt(hist) + epsilon)
    return w, hist


# ------------------------------------------------------- SSD multibox family

@register('multibox_prior', differentiable=False)
def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                   steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
    """Anchor-box generation (reference
    src/operator/contrib/multibox_prior.cc). data: (N, C, H, W) feature
    map; output (1, H*W*A, 4) corner boxes, A = len(sizes)+len(ratios)-1.
    Pure index arithmetic — XLA constant-folds it into the graph."""
    h, w = data.shape[-2], data.shape[-1]
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w
    cy = (jnp.arange(h, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(w, dtype=jnp.float32) + offsets[1]) * step_x
    cyg, cxg = jnp.meshgrid(cy, cx, indexing='ij')          # (H, W)

    ws, hs = [], []
    for s in sizes:                       # first ratio with every size
        r = ratios[0] ** 0.5
        ws.append(s * r)
        hs.append(s / r)
    for r in ratios[1:]:                  # first size with remaining ratios
        rr = r ** 0.5
        ws.append(sizes[0] * rr)
        hs.append(sizes[0] / rr)
    ws = jnp.asarray(ws, jnp.float32) / 2                    # (A,)
    hs = jnp.asarray(hs, jnp.float32) / 2

    cxg = cxg[..., None]                                     # (H, W, 1)
    cyg = cyg[..., None]
    boxes = jnp.stack([cxg - ws, cyg - hs, cxg + ws, cyg + hs], axis=-1)
    boxes = boxes.reshape(1, -1, 4)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return boxes


def _corner_to_center(b):
    w = b[..., 2] - b[..., 0]
    h = b[..., 3] - b[..., 1]
    return (b[..., 0] + w / 2, b[..., 1] + h / 2, w, h)


@register('multibox_target', differentiable=False, n_out=3)
def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """SSD training-target encoder (reference
    src/operator/contrib/multibox_target.cc). anchor: (1, A, 4) corners;
    label: (N, M, 5) [cls, xmin, ymin, xmax, ymax], cls<0 = padding.
    Returns (loc_target (N, A*4), loc_mask (N, A*4), cls_target (N, A)) —
    cls_target 0 is background, gt class ids shifted by +1.

    Matching is the reference's two-stage rule: each gt grabs its best
    anchor, then every anchor with best-gt IOU > threshold joins; all
    vectorized (argmax + where), no data-dependent loops.
    """
    A = anchor.shape[1]
    anchors = anchor[0]                                     # (A, 4)
    cls_id = label[..., 0]                                  # (N, M)
    gt = label[..., 1:5]                                    # (N, M, 4)
    valid = cls_id >= 0                                     # (N, M)

    iou = box_iou(anchors[None], gt)                        # (N, A, M)
    iou = jnp.where(valid[:, None, :], iou, 0.0)

    best_gt = jnp.argmax(iou, axis=2)                       # (N, A)
    best_gt_iou = jnp.max(iou, axis=2)                      # (N, A)
    # stage 1: force-match each valid gt's best anchor. Padding rows
    # (cls<0) scatter to index A, which is out of range and therefore
    # dropped — they must not clobber real matches at anchor 0.
    best_anchor = jnp.argmax(iou, axis=1)                   # (N, M)
    N, M = cls_id.shape
    safe_anchor = jnp.where(valid, best_anchor, A)
    bidx = jnp.arange(N)[:, None].repeat(M, 1)
    forced = jnp.zeros((N, A), bool)
    forced = forced.at[bidx, safe_anchor].max(True, mode='drop')
    forced_gt = jnp.zeros((N, A), jnp.int32)
    forced_gt = forced_gt.at[bidx, safe_anchor].set(
        jnp.arange(M, dtype=jnp.int32)[None, :].repeat(N, 0), mode='drop')
    # stage 2: threshold matches
    matched = forced | (best_gt_iou > overlap_threshold)
    gt_idx = jnp.where(forced, forced_gt, best_gt)          # (N, A)

    mg = jnp.take_along_axis(gt, gt_idx[..., None], axis=1)  # (N, A, 4)
    acx, acy, aw, ah = _corner_to_center(anchors[None])
    gcx, gcy, gw, gh = _corner_to_center(mg)
    tx = (gcx - acx) / jnp.maximum(aw, 1e-8) / variances[0]
    ty = (gcy - acy) / jnp.maximum(ah, 1e-8) / variances[1]
    tw = jnp.log(jnp.maximum(gw / jnp.maximum(aw, 1e-8), 1e-8)) / variances[2]
    th = jnp.log(jnp.maximum(gh / jnp.maximum(ah, 1e-8), 1e-8)) / variances[3]
    loc = jnp.stack([tx, ty, tw, th], axis=-1)              # (N, A, 4)
    loc_target = jnp.where(matched[..., None], loc, 0.0).reshape(N, A * 4)
    loc_mask = jnp.where(matched[..., None],
                         jnp.ones_like(loc), 0.0).reshape(N, A * 4)

    mcls = jnp.take_along_axis(cls_id, gt_idx, axis=1)      # (N, A)
    cls_target = jnp.where(matched, mcls + 1, 0.0)

    if negative_mining_ratio > 0:
        # hard-negative mining (reference multibox_target.cc): rank
        # unmatched anchors by their max foreground confidence; keep the
        # hardest ratio×num_pos as background, set the rest to
        # ignore_label. cls_pred: (N, C+1, A), class 0 = background.
        probs = jax.nn.softmax(cls_pred, axis=1)
        neg_conf = jnp.max(probs[:, 1:, :], axis=1)         # (N, A)
        neg_conf = jnp.where(matched, -jnp.inf, neg_conf)
        num_pos = jnp.sum(matched, axis=1, keepdims=True)   # (N, 1)
        quota = negative_mining_ratio * num_pos
        rank = jnp.argsort(jnp.argsort(-neg_conf, axis=1), axis=1)
        keep_neg = (rank < quota) & ~matched
        cls_target = jnp.where(matched | keep_neg, cls_target,
                               ignore_label)
    return loc_target, loc_mask, cls_target


@register('multibox_detection', differentiable=False)
def multibox_detection(cls_prob, loc_pred, anchor, clip=True, threshold=0.01,
                       background_id=0, nms_threshold=0.5,
                       force_suppress=False,
                       variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """SSD decode + per-class NMS (reference
    src/operator/contrib/multibox_detection.cc). cls_prob: (N, C, A);
    loc_pred: (N, A*4); anchor: (1, A, 4). Output (N, A, 6):
    [cls_id, score, xmin, ymin, xmax, ymax], suppressed rows cls_id=-1.
    """
    N, C, A = cls_prob.shape
    acx, acy, aw, ah = _corner_to_center(anchor[0][None])   # (1, A)
    loc = loc_pred.reshape(N, A, 4)
    cx = loc[..., 0] * variances[0] * aw + acx
    cy = loc[..., 1] * variances[1] * ah + acy
    w = jnp.exp(loc[..., 2] * variances[2]) * aw / 2
    h = jnp.exp(loc[..., 3] * variances[3]) * ah / 2
    boxes = jnp.stack([cx - w, cy - h, cx + w, cy + h], axis=-1)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)

    fg = jnp.delete(cls_prob, background_id, axis=1,
                    assume_unique_indices=True)
    scores = jnp.max(fg, axis=1)
    ids = jnp.argmax(fg, axis=1)      # 0-based foreground class id, as in
    keep = scores > threshold         # the reference's output convention
    data = jnp.concatenate([
        jnp.where(keep, ids.astype(jnp.float32), -1.0)[..., None],
        jnp.where(keep, scores, -1.0)[..., None], boxes], axis=-1)
    out = box_nms(data, overlap_thresh=nms_threshold, valid_thresh=0.0,
                  topk=nms_topk, coord_start=2, score_index=1, id_index=0,
                  force_suppress=force_suppress)
    # reference convention: invalid/suppressed rows carry class id -1
    return out.at[..., 0].set(jnp.where(out[..., 1] < 0, -1.0, out[..., 0]))


@register('proposal', differentiable=False, aliases=('Proposal',))
def proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16,
             output_score=False, iou_loss=False):
    """Faster-RCNN RPN proposals (reference
    src/operator/contrib/proposal.cc). cls_prob: (N, 2A, H, W);
    bbox_pred: (N, 4A, H, W); im_info: (N, 3) [height, width, scale].
    Static-shape TPU design: instead of the reference's dynamic pre/post-NMS
    top-k copies, scores are sorted once and NMS runs over the fixed
    rpn_post_nms_top_n best anchors; output (N, post_nms_top_n, 5)
    [batch_idx, x1, y1, x2, y2].
    """
    N, A2, H, W = cls_prob.shape
    A = A2 // 2
    if A != len(scales) * len(ratios):
        raise ValueError(
            f'cls_prob implies {A} anchors/cell but scales×ratios gives '
            f'{len(scales) * len(ratios)}')
    base = float(feature_stride)
    # base anchors centered at (stride-1)/2, cuda-impl convention
    ctr = (base - 1) / 2
    ws, hs = [], []
    for r in ratios:
        size = base * base / r
        w0 = jnp.round(jnp.sqrt(size))
        h0 = jnp.round(w0 * r)
        for s in scales:
            ws.append(w0 * s)
            hs.append(h0 * s)
    ws = jnp.asarray(ws, jnp.float32)
    hs = jnp.asarray(hs, jnp.float32)
    base_anchors = jnp.stack([ctr - (ws - 1) / 2, ctr - (hs - 1) / 2,
                              ctr + (ws - 1) / 2, ctr + (hs - 1) / 2], -1)

    sx = jnp.arange(W, dtype=jnp.float32) * base
    sy = jnp.arange(H, dtype=jnp.float32) * base
    syg, sxg = jnp.meshgrid(sy, sx, indexing='ij')
    shifts = jnp.stack([sxg, syg, sxg, syg], axis=-1)        # (H, W, 4)
    anchors = (shifts[:, :, None, :] + base_anchors[None, None]
               ).reshape(-1, 4)                              # (H*W*A, 4)

    scores = cls_prob[:, A:].transpose(0, 2, 3, 1).reshape(N, -1)
    deltas = bbox_pred.transpose(0, 2, 3, 1).reshape(N, -1, 4)

    aw = anchors[:, 2] - anchors[:, 0] + 1
    ah = anchors[:, 3] - anchors[:, 1] + 1
    acx = anchors[:, 0] + 0.5 * (aw - 1)
    acy = anchors[:, 1] + 0.5 * (ah - 1)
    cx = deltas[..., 0] * aw + acx
    cy = deltas[..., 1] * ah + acy
    pw = jnp.exp(deltas[..., 2]) * aw
    ph = jnp.exp(deltas[..., 3]) * ah
    props = jnp.stack([cx - 0.5 * (pw - 1), cy - 0.5 * (ph - 1),
                       cx + 0.5 * (pw - 1), cy + 0.5 * (ph - 1)], -1)
    imh = im_info[:, 0][:, None]
    imw = im_info[:, 1][:, None]
    props = jnp.stack([jnp.clip(props[..., 0], 0, imw - 1),
                       jnp.clip(props[..., 1], 0, imh - 1),
                       jnp.clip(props[..., 2], 0, imw - 1),
                       jnp.clip(props[..., 3], 0, imh - 1)], -1)
    min_size = rpn_min_size * im_info[:, 2][:, None]
    pw = props[..., 2] - props[..., 0] + 1
    ph = props[..., 3] - props[..., 1] + 1
    scores = jnp.where((pw >= min_size) & (ph >= min_size), scores, -1.0)

    k = min(rpn_post_nms_top_n, scores.shape[1])
    top_scores, top_idx = jax.lax.top_k(scores, k)
    top_props = jnp.take_along_axis(props, top_idx[..., None], axis=1)
    data = jnp.concatenate([jnp.zeros_like(top_scores)[..., None],
                            top_scores[..., None], top_props], axis=-1)
    kept = box_nms(data, overlap_thresh=threshold, valid_thresh=0.0,
                   coord_start=2, score_index=1, id_index=-1,
                   force_suppress=True)
    batch_idx = jnp.arange(N, dtype=jnp.float32)[:, None, None]
    rois = jnp.concatenate(
        [jnp.broadcast_to(batch_idx, (N, k, 1)), kept[..., 2:6]], axis=-1)
    if output_score:
        return rois, kept[..., 1:2]
    return rois


# ------------------------------------------------ sliding-window attention

def _sldwin_mask(seq, w, w_left, w_right):
    i = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    return (j >= i - w_left) & (j <= i + w_right)


@register('sldwin_atten_mask_like', differentiable=False)
def sldwin_atten_mask_like(score, dilation, valid_length, w,
                           symmetric=True):
    """Reference: src/operator/contrib/transformer.cc
    _contrib_sldwin_atten_mask_like (GluonNLP sliding-window attention).
    Returns the 0/1 mask shaped like ``score`` (B, H, S, S) for a window
    of w tokens each side (w left only when not symmetric), intersected
    with the valid-length mask."""
    B, H, S, _ = score.shape
    wl, wr = w, (w if symmetric else 0)
    band = _sldwin_mask(S, w, wl, wr)[None, None]
    valid = jnp.arange(S)[None, :] < valid_length[:, None]   # (B, S)
    vmask = valid[:, None, :, None] & valid[:, None, None, :]
    return jnp.broadcast_to(band & vmask,
                            score.shape).astype(score.dtype)


@register('sldwin_atten_score')
def sldwin_atten_score(query, key, dilation, w, symmetric=True):
    """Banded QK^T: only positions within the window contribute
    (reference _contrib_sldwin_atten_score). query/key: (B, S, H, D);
    returns (B, H, S, S) scores with out-of-band entries at -1e30 so a
    following softmax zeroes them. Dense-banded on TPU: XLA fuses the
    mask into the matmul epilogue; the band never materializes in HBM
    under jit."""
    s = jnp.einsum('bqhd,bkhd->bhqk', query, key)
    S = query.shape[1]
    band = _sldwin_mask(S, w, w, w if symmetric else 0)[None, None]
    return jnp.where(band, s, -1e30)


@register('sldwin_atten_context')
def sldwin_atten_context(score, value, dilation, w, symmetric=True):
    """Probability-weighted value gather for the banded scores
    (reference _contrib_sldwin_atten_context). score: (B, H, S, S) —
    typically softmax(sldwin_atten_score * scale); value: (B, S, H, D)."""
    return jnp.einsum('bhqk,bkhd->bqhd', score, value)


# ------------------------------------------ round-2 op-ledger additions
# (VERDICT r1 item 5: remaining contrib registrations)

@register('quadratic')
def quadratic(data, a=0.0, b=0.0, c=0.0):
    """f(x) = a x^2 + b x + c (reference contrib/quadratic_op.cc — the
    tutorial op; kept for parity with scripts that probe it)."""
    return a * data * data + b * data + c


@register('gradient_multiplier')
def gradient_multiplier(data, scalar=1.0):
    """Identity forward, grad scaled by `scalar` in backward (reference
    contrib/gradient_multiplier_op.cc — gradient-reversal trick)."""
    import jax

    @jax.custom_vjp
    def _gm(x):
        return x

    def _fwd(x):
        return x, None

    def _bwd(_, g):
        return (g * scalar,)

    _gm.defvjp(_fwd, _bwd)
    return _gm(data)


@register('div_sqrt_dim')
def div_sqrt_dim(data):
    """x / sqrt(last_dim) (reference contrib/transformer.cc
    _contrib_div_sqrt_dim — attention score scaling)."""
    return data / jnp.sqrt(jnp.float32(data.shape[-1])).astype(data.dtype)


@register('edge_id', differentiable=False)
def edge_id(data, u, v):
    """CSR edge-id lookup: for each (u_i, v_i) return the data value of
    edge u->v or -1 (reference contrib/dgl_graph.cc _contrib_edge_id).
    Dense-adjacency form on TPU (CSR indexing is host-hostile)."""
    return data[u.astype(jnp.int32), v.astype(jnp.int32)]


@register('index_array', differentiable=False)
def index_array(data, axes=None):
    """Map each element position to its N-d index (reference
    contrib/index_array.cc): output (d1..dn, len(axes) or n)."""
    shape = data.shape
    n = len(shape)
    axes = tuple(range(n)) if axes is None else tuple(axes)
    grids = jnp.meshgrid(*[jnp.arange(s, dtype=jnp.int64) for s in shape],
                         indexing='ij') if n else []
    return jnp.stack([grids[a] for a in axes], axis=-1) if n else \
        jnp.zeros((0,), jnp.int64)


@register('round_ste')
def round_ste(data):
    """Round with straight-through gradient (reference
    contrib/stes_op.cc — QAT building block)."""
    import jax

    @jax.custom_vjp
    def _r(x):
        return jnp.round(x)

    def _fwd(x):
        return jnp.round(x), None

    def _bwd(_, g):
        return (g,)

    _r.defvjp(_fwd, _bwd)
    return _r(data)


@register('sign_ste')
def sign_ste(data):
    """Sign with straight-through gradient (reference contrib/stes_op.cc)."""
    import jax

    @jax.custom_vjp
    def _s(x):
        return jnp.sign(x)

    def _fwd(x):
        return jnp.sign(x), None

    def _bwd(_, g):
        return (g,)

    _s.defvjp(_fwd, _bwd)
    return _s(data)


@register('calibrate_entropy', differentiable=False, n_out=2)
def calibrate_entropy(hist, hist_edges, num_quantized_bins=255):
    """KL-optimal int8 threshold from a histogram (reference
    quantization/calibrate.cc _contrib_calibrate_entropy). Reuses the
    framework's calibration machinery (quantization.py)."""
    import numpy as _onp
    from ..quantization import _HistogramCollector
    c = _HistogramCollector.__new__(_HistogramCollector)
    c.hist = _onp.asarray(hist)
    c.edges = _onp.asarray(hist_edges)
    c.num_bins = int(c.hist.shape[0])
    c.min = float(c.edges[0])
    c.max = float(c.edges[-1])
    lo, hi = c.entropy(num_quantized_bins=int(num_quantized_bins))
    return (jnp.asarray(hi, jnp.float32),
            jnp.asarray(0.0, jnp.float32))   # divergence: opaque detail


@register('box_encode', n_out=2)
def box_encode(samples, matches, anchors, refs, means=(0., 0., 0., 0.),
               stds=(0.1, 0.1, 0.2, 0.2)):
    """Anchor-relative box regression targets (reference
    contrib/bounding_box.cc _contrib_box_encode; SSD/Faster-RCNN
    training). corner boxes -> normalized (dx, dy, dw, dh) targets +
    foreground masks."""
    m = matches.astype(jnp.int32)
    ref = jnp.take_along_axis(refs, m[..., None], axis=1)
    ax, ay, ax2, ay2 = [anchors[..., i] for i in range(4)]
    gx, gy, gx2, gy2 = [ref[..., i] for i in range(4)]
    aw, ah = ax2 - ax, ay2 - ay
    acx, acy = ax + aw / 2, ay + ah / 2
    gw, gh = gx2 - gx, gy2 - gy
    gcx, gcy = gx + gw / 2, gy + gh / 2
    t = jnp.stack([
        ((gcx - acx) / jnp.maximum(aw, 1e-12) - means[0]) / stds[0],
        ((gcy - acy) / jnp.maximum(ah, 1e-12) - means[1]) / stds[1],
        (jnp.log(jnp.maximum(gw, 1e-12) / jnp.maximum(aw, 1e-12))
         - means[2]) / stds[2],
        (jnp.log(jnp.maximum(gh, 1e-12) / jnp.maximum(ah, 1e-12))
         - means[3]) / stds[3]], axis=-1)
    mask = (samples > 0.5).astype(t.dtype)[..., None]
    return t * mask, jnp.broadcast_to(mask, t.shape)


@register('box_decode')
def box_decode(data, anchors, std0=0.1, std1=0.1, std2=0.2, std3=0.2,
               clip=-1.0, format='corner'):
    """Invert box_encode (reference _contrib_box_decode)."""
    if format == 'corner':
        ax, ay, ax2, ay2 = [anchors[..., i] for i in range(4)]
        aw, ah = ax2 - ax, ay2 - ay
        acx, acy = ax + aw / 2, ay + ah / 2
    else:
        acx, acy, aw, ah = [anchors[..., i] for i in range(4)]
    dx = data[..., 0] * std0 * aw + acx
    dy = data[..., 1] * std1 * ah + acy
    dw = data[..., 2] * std2
    dh = data[..., 3] * std3
    if clip > 0:
        dw = jnp.minimum(dw, clip)
        dh = jnp.minimum(dh, clip)
    w, h = jnp.exp(dw) * aw / 2, jnp.exp(dh) * ah / 2
    return jnp.stack([dx - w, dy - h, dx + w, dy + h], axis=-1)


@register('batch_norm_with_relu', n_out=3)
def batch_norm_with_relu(data, gamma, beta, moving_mean, moving_var,
                         eps=1e-3, momentum=0.9, axis=1):
    """BN + ReLU in one op (reference contrib/batch_norm_relu.cc —
    an MKLDNN fusion; XLA fuses the relu into the normalize epilogue
    anyway, the registration exists for graph parity). Inference form."""
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    mm = moving_mean.reshape(shape)
    mv = moving_var.reshape(shape)
    out = (data - mm) * (gamma.reshape(shape)
                         / jnp.sqrt(mv + eps)) + beta.reshape(shape)
    return jnp.maximum(out, 0), moving_mean, moving_var


@register('roi_pooling', differentiable=True)
def roi_pooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0):
    """Max-pool ROI features (reference src/operator/roi_pooling.cc).
    Static-shape TPU form: each ROI bin max-reduces a masked window —
    no dynamic slicing, everything batchable under vmap."""
    import jax
    ph, pw = (pooled_size, pooled_size) if isinstance(pooled_size, int) \
        else pooled_size
    N, C, H, W = data.shape

    ys = jnp.arange(H, dtype=jnp.float32)
    xs = jnp.arange(W, dtype=jnp.float32)

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = [jnp.round(roi[i + 1] * spatial_scale)
                          for i in range(4)]
        rh = jnp.maximum(y2 - y1 + 1, 1.0)
        rw = jnp.maximum(x2 - x1 + 1, 1.0)
        bh, bw = rh / ph, rw / pw
        feat = data[b]                       # (C, H, W)

        def bin_val(py, px):
            ys0 = y1 + py * bh
            ys1 = y1 + (py + 1) * bh
            xs0 = x1 + px * bw
            xs1 = x1 + (px + 1) * bw
            my = (ys >= jnp.floor(ys0)) & (ys < jnp.ceil(ys1))
            mx = (xs >= jnp.floor(xs0)) & (xs < jnp.ceil(xs1))
            mask = my[:, None] & mx[None, :]
            return jnp.where(mask[None], feat, -jnp.inf).max((-2, -1))

        grid = jnp.stack([jnp.stack([bin_val(py, px)
                                     for px in range(pw)], -1)
                          for py in range(ph)], -2)
        return jnp.where(jnp.isfinite(grid), grid, 0.0)

    return jax.vmap(one_roi)(rois)


@register('identity_attach_kl_sparse_reg')
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1,
                                  penalty=0.001, momentum=0.9):
    """Identity forward (reference identity_attach_KL_sparse_reg.cc —
    the KL sparsity penalty attaches to the backward as a regularizer).
    The penalty gradient is folded in via custom VJP."""
    import jax

    @jax.custom_vjp
    def _id(x):
        return x

    def _fwd(x):
        rho_hat = jnp.mean(jax.nn.sigmoid(x))
        return x, (x, rho_hat)

    def _bwd(res, g):
        x, rho = res
        rho = jnp.clip(rho, 1e-6, 1 - 1e-6)
        t = sparseness_target
        dpen = penalty * (-t / rho + (1 - t) / (1 - rho))
        s = jax.nn.sigmoid(x)
        return (g + dpen * s * (1 - s) / x.size,)

    _id.defvjp(_fwd, _bwd)
    return _id(data)


@register('hawkesll', n_out=2)
def hawkesll(mu, alpha, beta, state, lags, marks, valid_length, max_time):
    """Marked multivariate Hawkes-process log-likelihood, exponential
    kernels, diagonal excitation (reference contrib/hawkes_ll.cc).

    LL = sum_i log lam_{m_i}(t_i) - sum_k [ mu_k T
         + alpha_k (N_k + r0_k - r_k(T)) ]
    with lam_k(t) = mu_k + alpha_k beta_k r_k(t) and r_k the decaying
    event excitation (the compensator's closed form uses
    sum_{i in k} e^{-beta_k (T - t_i)} = r_k(T)). One lax.scan over the
    padded event axis — no per-event host loop.

    mu: (N,K) background rates; alpha/beta: (K,); state: (N,K) carried
    excitation from a previous interval; lags/marks: (N,T);
    valid_length/max_time: (N,). Returns (ll (N,), new_state (N,K)).
    """
    import jax
    from jax import lax
    N, K = mu.shape
    T = lags.shape[1]
    marks_i = marks.astype(jnp.int32)
    rows = jnp.arange(N)

    def step(carry, t):
        r, elapsed, ll = carry
        valid = (t < valid_length).astype(mu.dtype)
        # padded entries past valid_length must be full no-ops: mask the
        # decay too, not just the ll/bump terms
        dt = lags[:, t] * valid
        r = r * jnp.exp(-beta[None, :] * dt[:, None])
        m = marks_i[:, t]
        lam = mu[rows, m] + alpha[m] * beta[m] * r[rows, m]
        ll = ll + valid * jnp.log(jnp.maximum(lam, 1e-30))
        bump = jax.nn.one_hot(m, K, dtype=mu.dtype) * valid[:, None]
        return (r + bump, elapsed + dt * valid, ll), None

    (r_end, t_end, ll), _ = lax.scan(
        step, (state, jnp.zeros((N,), mu.dtype),
               jnp.zeros((N,), mu.dtype)), jnp.arange(T))
    # decay the end-of-events excitation out to max_time
    rem = jnp.maximum(max_time - t_end, 0.0)
    r_T = r_end * jnp.exp(-beta[None, :] * rem[:, None])
    counts = jnp.sum(
        jax.nn.one_hot(marks_i, K, dtype=mu.dtype)
        * (jnp.arange(T)[None, :, None]
           < valid_length[:, None, None]).astype(mu.dtype), axis=1)
    comp = (max_time[:, None] * mu
            + alpha[None, :] * (counts + state - r_T)).sum(-1)
    return ll - comp, r_T


@register('onnx_nms', differentiable=False, dynamic_shape=True)
def onnx_nms(boxes, scores, max_output_boxes_per_class=0,
             iou_threshold=0.0, score_threshold=None):
    """ONNX ``NonMaxSuppression`` semantics (opset 10+): greedy per-class
    NMS returning selected (batch, class, box) index triples, dynamic
    output count — executes eagerly (the importer's round-trip path for
    exported box_nms graphs). IoU is corner-order invariant, so corner
    boxes work directly."""
    import numpy as onp
    b = onp.asarray(boxes, 'float32')          # (B, N, 4)
    s = onp.asarray(scores, 'float32')         # (B, C, N)
    max_out = int(onp.asarray(max_output_boxes_per_class).reshape(()))
    if max_out == 0:
        # spec: max_output_boxes_per_class defaults to 0 = NO output
        return jnp.zeros((0, 3), jnp.int64)
    iou_t = float(onp.asarray(iou_threshold).reshape(()))
    sc_t = None if score_threshold is None else \
        float(onp.asarray(score_threshold).reshape(()))
    sel = []
    x1 = onp.minimum(b[..., 0], b[..., 2])
    y1 = onp.minimum(b[..., 1], b[..., 3])
    x2 = onp.maximum(b[..., 0], b[..., 2])
    y2 = onp.maximum(b[..., 1], b[..., 3])
    area = (x2 - x1) * (y2 - y1)
    for bi in range(s.shape[0]):
        for ci in range(s.shape[1]):
            order = onp.argsort(-s[bi, ci], kind='stable')
            if sc_t is not None:
                order = order[s[bi, ci, order] > sc_t]
            kept = []
            for idx in order:
                if max_out and len(kept) >= max_out:
                    break
                ok = True
                for j in kept:
                    ix1 = max(x1[bi, idx], x1[bi, j])
                    iy1 = max(y1[bi, idx], y1[bi, j])
                    ix2 = min(x2[bi, idx], x2[bi, j])
                    iy2 = min(y2[bi, idx], y2[bi, j])
                    inter = max(ix2 - ix1, 0.0) * max(iy2 - iy1, 0.0)
                    union = area[bi, idx] + area[bi, j] - inter
                    if union > 0 and inter / union > iou_t:
                        ok = False
                        break
                if ok:
                    kept.append(int(idx))
            sel += [[bi, ci, k] for k in kept]
    out = onp.asarray(sel, 'int64').reshape(-1, 3)
    return jnp.asarray(out)
