"""Flash attention as Pallas TPU kernels, forward and backward.

Functional parity target: the reference's fused attention ops
(``_contrib_interleaved_matmul_selfatt_qk``/``valatt`` and encdec variants,
src/operator/contrib/transformer.cc:650-826) compute QK^T → softmax → AV as
separate cuBLAS batched matmuls with an O(T·S) attention matrix in HBM.

TPU re-design: blockwise kernels with online softmax — the attention
matrix never materializes in HBM, forward or backward; each (query-block ×
key-block) tile lives in VMEM.

**Layout: heads stay packed along the lane axis.** The kernels take q, k,
v as the projections leave them, (batch, seq, heads x width), and a grid
step holds a *group* of heads whose widths together fill whole 128-lane
tiles (two 64-wide heads; two heads of 192 and 128). A (batch·head, seq,
64) array moves through the DMA engine at a seventh of the HBM's rate
(117 GB/s measured on the v5e, PERF.md §6 PR 34: every row is half a
tile) and costs four XLA transposes a call besides; packed, the blocks
are lane-dense and nothing is transposed. Inside the kernel a head is
picked by zeroing the other heads' lanes of one operand (the contraction
then runs over the group's lanes, which the MXU's 128-deep tile holds
anyway) or, where the width is a multiple of 128, by a static lane slice.

* ``mx_flash_attention`` (forward): one (batch, head group, query block)
  program streams the key blocks of its K/V, which stay in VMEM for the
  whole group, with running row max/sum (the Flash-Attention-2
  recurrence), and writes ``o`` and each row's logsumexp
  ``lse = m + log l`` as f32[batch, groups, heads a group, T].
* ``mx_flash_attention_bwd`` (backward, one kernel): residuals are
  ``(q, k, v, o, lse)``; ``delta = sum(o * do, -1)`` is one XLA reduction.
  One (batch, head group, key block) program loops over the query blocks,
  rebuilds each head's tile ``p^T = exp(k q^T - lse)`` keys by queries (so
  the row statistics broadcast along sublanes as they are stored, and no
  tile is ever transposed), accumulates dk and dv of its key block and
  adds ``k^T ds^T`` into a float32 dq^T that stays in VMEM across the key
  blocks: five products and one ``exp`` a tile, nothing (T, S)-shaped in
  HBM.
* Under ``causal`` blocks wholly above the diagonal are not visited and
  only blocks the diagonal crosses pay for the iota mask.

Precision: what the XLA branch gives on the TPU, and no lower. Operands
of every product are rounded to one bfloat16 MXU pass with float32
accumulation (JAX's default precision for float32 there; Mosaic gives
float32 operands the same one pass, measured); nothing is stored below
the input dtype, and the softmax statistics, ``exp``, ``delta``, the
accumulators, ``o``, dq, dk, dv are float32. In interpret mode (the
CPU's tests) the operands stay as they are, as the CPU's default
precision has them.

The value head may be narrower or wider than the query/key head
(latent attention: 192 and 128); every kernel takes v at its own width.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_NN = (((1,), (0,)), ((), ()))       # a @ b

# block rule (measured on the v5e, PERF.md §6 PR 34): the largest divisor
# of the sequence that is a multiple of 128 (or the whole sequence) up to
# these; a grid step costs about 0.35 us whatever it holds, so the tile
# has to carry enough products to cover it
_BLOCK_Q, _BLOCK_K = 512, 512
# what a kernel is let take of the v5e's 128 MiB of VMEM, twice the
# default. Not more, and why is NOT known: with 96 MiB declared the sparse
# decoder's step was no longer `correct` though each kernel's own results
# were (the gradients of every leaf below the second attention layer from
# the top drifted over three steps, whatever the block size); with 32 MiB
# every leaf reads as under XLA's backward (chip runs of PR 34, PERF.md
# §6 and §7; ROADMAP A3 has the probe to rerun after any change to these
# kernels, to jax or to libtpu). tests/test_chip_compile.py and
# chip_smoke.py hold the number. A shape whose resident K/V (forward) or
# q/do/dq (backward) do not fit the budget, the limit less room for what
# the sum in `_plan` leaves out, goes to XLA.
_VMEM_LIMIT = 32 * 2 ** 20
_VMEM_BUDGET = 28 * 2 ** 20


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


def _mxu(x, dtype):
    """An MXU operand: rounded to ``dtype`` (one bf16 pass on the chip),
    left alone where ``dtype`` is None (interpret mode)."""
    return x if dtype is None else x.astype(dtype)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _rows(i, block, total):
    """Block ``i`` of an axis of ``total``; the whole axis, statically,
    where one block holds it (no multiple of 128 is asked of it then)."""
    if block == total:
        return slice(None)
    return pl.ds(pl.multiple_of(i * block, block), block)


def _head_of_lane(width, group):
    """(1, group x width) int32: which head of the group a lane is."""
    return jax.lax.broadcasted_iota(
        jnp.int32, (1, group * width), 1) // width


def _one_head(x, h, width, group):
    """Head ``h`` of an operand whose lanes hold ``group`` heads of
    ``width``, and the operand it is contracted with over those lanes:
    a lane slice where it is tile-aligned, else the other heads' lanes
    zeroed (the contraction then runs over the whole group)."""
    if group == 1:
        return x
    if width % 128 == 0:
        return x[:, h * width:(h + 1) * width]
    return jnp.where(_head_of_lane(width, group) == h, x, 0)


def _its_partner(x, h, width, group):
    """What :func:`_one_head`'s result is contracted with."""
    if group > 1 and width % 128 == 0:
        return x[:, h * width:(h + 1) * width]
    return x


def _merge_heads(parts, width, group):
    """The group's lanes from one result a head, each a product with
    :func:`_its_partner`: side by side where a head's result is its own
    lanes, else head ``h``'s lanes picked out of part ``h``."""
    if group == 1 or width % 128 == 0:
        return jnp.concatenate(parts, axis=1)
    out = parts[0]
    for h in range(1, group):
        out = jnp.where(_head_of_lane(width, group) == h, parts[h], out)
    return out


# ---------------------------------------------------------- forward kernel

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *stat_refs, group,
                      block_k, sm_scale, causal, q_offset, mxu_dtype,
                      normalize):
    """One (batch, head group, q-block) program: stream key blocks, online
    softmax, one head of the group after the other.

    q_ref: (1, block_q, g·d); k_ref: (1, S, g·d); v_ref: (1, S, g·dv);
    o_ref: (1, block_q, g·dv); statistics (1, 1, g, block_q).
    ``normalize`` writes o = acc / l and the one statistic lse; without
    it o is left UNNORMALIZED and the running row max and denominator
    are written out — the ring-attention form where blocks from other
    devices still need merging.
    """
    block_q = q_ref.shape[1]
    s_len = k_ref.shape[1]
    d, dv = q_ref.shape[2] // group, v_ref.shape[2] // group
    num_kb = s_len // block_k
    row0 = q_offset + pl.program_id(2) * block_q
    # the scale goes into q before the rounding, (bq, d) multiplies
    # instead of (bq, bk); the backward rounds the same product
    q = q_ref[0].astype(jnp.float32) * sm_scale
    qs = [_mxu(_one_head(q, h, d, group), mxu_dtype) for h in range(group)]

    def step(masked):
        def body(j, carry):
            keys = _rows(j, block_k, s_len)
            k = _mxu(k_ref[0, keys, :], mxu_dtype)
            v = _mxu(v_ref[0, keys, :], mxu_dtype)
            heads = []
            for h, (m, l, acc) in enumerate(carry):
                s = _dot(qs[h], _its_partner(k, h, d, group), _NT)
                if masked:                                   # (bq, bk)
                    rows = row0 + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    cols = j * block_k + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1)
                    s = jnp.where(rows >= cols, s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                v_h = _its_partner(v, h, dv, group)
                pv = _dot(_mxu(p, mxu_dtype), v_h, _NN)
                heads.append(
                    (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                     alpha * acc + pv))
            return heads
        return body

    # a head's accumulator holds its own lanes where they are whole
    # tiles, else the group's (the other heads' lanes are dropped last)
    lanes = dv if group == 1 or dv % 128 == 0 else group * dv
    carry = [(jnp.full((block_q, 1), _NEG_INF, jnp.float32),
              jnp.zeros((block_q, 1), jnp.float32),
              jnp.zeros((block_q, lanes), jnp.float32))] * group
    if causal:
        # key blocks wholly below the diagonal of this q block need no
        # mask; those the diagonal crosses do; the rest are not visited
        below = jnp.minimum(num_kb, (row0 + 1) // block_k)
        last = jnp.minimum(num_kb,
                           (row0 + block_q + block_k - 1) // block_k)
        carry = jax.lax.fori_loop(0, below, step(False), carry)
        heads = jax.lax.fori_loop(below, last, step(True), carry)
    else:
        heads = jax.lax.fori_loop(0, num_kb, step(False), carry)

    outs = []
    for h, (m, l, acc) in enumerate(heads):
        if normalize:
            l = jnp.maximum(l, 1e-30)
            outs.append(acc / l)
            stat_refs[0][0, 0, h] = (m + jnp.log(l))[:, 0]
        else:
            outs.append(acc)
            stat_refs[0][0, 0, h] = m[:, 0]
            stat_refs[1][0, 0, h] = l[:, 0]
    o_ref[0] = _merge_heads(outs, dv, group).astype(o_ref.dtype)


# Both calls are jitted on their own: a model's layers then share one
# trace and one lowering of each kernel where every call site had paid
# for its own (3 s of a warm set-up with twelve layers, my chip runs,
# PR 34).
@functools.partial(jax.jit, static_argnames=(
    'heads', 'group', 'sm_scale', 'causal', 'block_q', 'block_k',
    'interpret', 'q_offset', 'return_stats'))
def _flash_call(q, k, v, heads, group, sm_scale, causal, block_q, block_k,
                interpret, q_offset, return_stats):
    """Shared pallas_call scaffolding for both forward variants.

    q: (B, T, H·d), k: (B, S, H·d), v: (B, S, H·dv), heads packed along
    the last axis; ``group`` heads a grid step. Block sizes must divide
    T/S exactly (callers guarantee via _choose_seq_block). ``return_stats``
    selects (unnormalized acc, row max, row denominator); else (o, lse).
    Every statistic is f32[B, H/group, group, T], which is (B, H, T).
    """
    b, t, width = q.shape
    s, v_width = v.shape[1], v.shape[2]
    assert t % block_q == 0 and s % block_k == 0 and heads % group == 0
    gd, gdv = width // heads * group, v_width // heads * group
    kernel = functools.partial(
        _flash_fwd_kernel, group=group, block_k=block_k, sm_scale=sm_scale,
        causal=causal, q_offset=q_offset,
        mxu_dtype=None if interpret else jnp.bfloat16,
        normalize=not return_stats)
    # the statistics' block is (group, block_q) of (group, T): the Mosaic
    # lowering requires the last two block dims to divide (8, 128) or
    # equal the array dims, so the heads of a group get an axis of
    # their own
    stat_spec = pl.BlockSpec((1, 1, group, block_q),
                             lambda b, g, i: (b, g, 0, i))
    stat_shape = jax.ShapeDtypeStruct((b, heads // group, group, t),
                                      jnp.float32)
    n_stats = 2 if return_stats else 1
    return pl.pallas_call(
        kernel, grid=(b, heads // group, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, gd), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, s, gd), lambda b, g, i: (b, 0, g)),
            pl.BlockSpec((1, s, gdv), lambda b, g, i: (b, 0, g)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, gdv),
                                lambda b, g, i: (b, i, g))]
        + [stat_spec] * n_stats,
        out_shape=[jax.ShapeDtypeStruct(
            (b, t, v_width), jnp.float32 if return_stats else q.dtype)]
        + [stat_shape] * n_stats,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='mx_flash_attention_stats' if return_stats
        else 'mx_flash_attention')(q, k, v)


# --------------------------------------------------------- backward kernel

def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *, group, block_q,
                      sm_scale, causal, q_offset, mxu_dtype):
    """One (batch, head group, key block) program: loop over the query
    blocks, one head of the group after the other.

    q_ref: (1, T, g·d), do_ref: (1, T, g·dv), lse_ref/delta_ref:
    (1, 1, g, T) and dq_ref: (1, T, g·d) stay in VMEM across the key
    blocks of a group; k_ref/dk_ref: (1, block_k, g·d); v_ref/dv_ref:
    (1, block_k, g·dv); dq_acc: (g·d, T) float32 scratch, dq's transpose.
    Tiles are keys by queries.
    """
    j, num_kb = pl.program_id(2), pl.num_programs(2)
    block_k = k_ref.shape[1]
    t = q_ref.shape[1]
    d, dv = k_ref.shape[2] // group, v_ref.shape[2] // group
    num_qb = t // block_q
    k = k_ref[0]
    v = v_ref[0]
    ks = [_mxu(_one_head(k, h, d, group), mxu_dtype) for h in range(group)]
    vs = [_mxu(_one_head(v, h, dv, group), mxu_dtype)
          for h in range(group)]
    # dq^T = k^T ds^T: the small operand is transposed, once a program,
    # and a head's rows of it are sublanes, cut where they lie
    k_t = _mxu(k.astype(jnp.float32).T, mxu_dtype)           # (g·d, bk)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked):
        def body(i, carry):
            rows = _rows(i, block_q, t)
            q = _mxu(q_ref[0, rows, :].astype(jnp.float32) * sm_scale,
                     mxu_dtype)                  # the forward's operand
            do = _mxu(do_ref[0, rows, :], mxu_dtype)
            heads = []
            for h, (dk, dv_acc) in enumerate(carry):
                s = _dot(ks[h], _its_partner(q, h, d, group), _NT)
                if masked:                                   # (bk, bq)
                    cols = j * block_k + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    qpos = q_offset + i * block_q + \
                        jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                    s = jnp.where(qpos >= cols, s, _NEG_INF)
                p = jnp.exp(s - lse_ref[0, 0, h:h + 1, rows])
                do_h = _its_partner(do, h, dv, group)
                dv_acc = dv_acc + _dot(_mxu(p, mxu_dtype), do_h, _NN)
                dp = _dot(vs[h], do_h, _NT)                  # (bk, bq)
                ds = _mxu(p * (dp - delta_ref[0, 0, h:h + 1, rows]),
                          mxu_dtype)                         # (bk, bq)
                # q carries the scale, so dk needs none; dq takes it
                # where it is written out
                dk = dk + _dot(ds, _its_partner(q, h, d, group), _NN)
                head = slice(h * d, (h + 1) * d)
                dq_acc[head, rows] += _dot(k_t[head], ds, _NN)
                heads.append((dk, dv_acc))
            return heads
        return body

    # as in the forward: a head's own lanes where they are whole tiles
    lanes = lambda w: w if group == 1 or w % 128 == 0 else group * w
    carry = [(jnp.zeros((block_k, lanes(d)), jnp.float32),
              jnp.zeros((block_k, lanes(dv)), jnp.float32))] * group
    if causal:
        # q blocks wholly above this key block's diagonal are not
        # visited; those the diagonal crosses are masked; the rest not
        first = jnp.maximum(j * block_k - q_offset, 0) // block_q
        below = jnp.maximum((j + 1) * block_k - 1 - q_offset, 0)
        below = jnp.minimum(num_qb, (below + block_q - 1) // block_q)
        carry = jax.lax.fori_loop(first, below, step(True), carry)
        heads = jax.lax.fori_loop(below, num_qb, step(False), carry)
    else:
        heads = jax.lax.fori_loop(0, num_qb, step(False), carry)
    dk_ref[0] = _merge_heads([dk for dk, _ in heads], d,
                             group).astype(dk_ref.dtype)
    dv_ref[0] = _merge_heads([dv_acc for _, dv_acc in heads], dv,
                             group).astype(dv_ref.dtype)

    @pl.when(j == num_kb - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * sm_scale).T.astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    'heads', 'group', 'sm_scale', 'causal', 'block_q', 'block_k',
    'interpret'))
def _flash_bwd(q, k, v, o, lse, do, heads, group, sm_scale, causal,
               block_q, block_k, interpret):
    """dq, dk, dv of the normalized form from the saved row statistics;
    every array packed as :func:`_flash_call` takes it."""
    b, t, width = q.shape
    s, v_width = v.shape[1], v.shape[2]
    assert t % block_q == 0 and s % block_k == 0
    gd, gdv = width // heads * group, v_width // heads * group
    mxu_dtype = None if interpret else jnp.bfloat16
    # ds = p (dp - delta) is a difference of two means of do·v over the
    # keys, and the two cancel a part common to v's rows only if delta
    # holds do as the kernel's dp does: rounded as the MXU gets it. A
    # probe that tier-1 holds: v = 3 +- 0.1, dq and dk 6.8 % off the
    # float32 gradient with do left float32 here, 4.5 % so, as XLA's
    # recompute reads at the same one pass.
    delta = jnp.sum((o.astype(jnp.float32)
                     * _mxu(do, mxu_dtype).astype(jnp.float32))
                    .reshape(b, t, heads, -1), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(lse.shape)
    kernel = functools.partial(
        _flash_bwd_kernel, group=group, block_q=block_q, sm_scale=sm_scale,
        causal=causal, q_offset=s - t, mxu_dtype=mxu_dtype)
    whole = lambda w: pl.BlockSpec((1, t, w), lambda b, g, j: (b, 0, g))
    block = lambda w: pl.BlockSpec((1, block_k, w),
                                   lambda b, g, j: (b, j, g))
    stat = pl.BlockSpec((1, 1, group, t), lambda b, g, j: (b, g, 0, 0))
    return pl.pallas_call(
        kernel, grid=(b, heads // group, s // block_k),
        in_specs=[whole(gd), block(gd), block(gdv), whole(gdv), stat, stat],
        out_specs=[whole(gd), block(gd), block(gdv)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((gd, t), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name='mx_flash_attention_bwd')(q, k, v, do, lse, delta)


# -------------------------------------------------- ring attention's form

def _stats_xla(q, k, v, sm_scale, causal):
    """Pure-XLA twin of the stats kernel — the differentiation path
    (recompute backward) and the off-TPU fallback. Diagonal-block causal:
    q_pos >= k_pos."""
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
    (_stats_xla); the merged statistics of a ring are no one block's, so
    the backward kernel of the normalized form does not apply."""
    if interpret or _on_tpu():
        choose = _choose_block if interpret else _choose_seq_block
        bq, bk = choose(q.shape[1], 128), choose(k.shape[1], 128)
        if interpret or (bq >= 32 and bk >= 32):
            acc, m, l = _flash_call(q, k, v, 1, 1, sm_scale, causal, bq, bk,
                                    interpret, q_offset=0,
                                    return_stats=True)
            return acc, m[:, 0, 0], l[:, 0, 0]
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


# ------------------------------------------------------ the XLA branch

def _reference_attention(q, k, v, sm_scale, causal):
    """XLA fallback: plain fused-by-XLA attention, fp32 softmax."""
    s = jnp.einsum('bqd,bkd->bqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        t, src = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, src), bool), k=src - t)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bqk,bkd->bqd', p, v.astype(jnp.float32)).astype(
        q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _xla_attention(q, k, v, sm_scale, causal):
    """(BH, T, d) attention by XLA, off the TPU, under a mesh, and for
    shapes the kernels tile badly."""
    return _reference_attention(q, k, v, sm_scale, causal)


def _xla_attention_fwd(q, k, v, sm_scale, causal):
    return _xla_attention(q, k, v, sm_scale, causal), (q, k, v)


def _xla_attention_bwd(sm_scale, causal, res, g):
    """Backward by blockless recompute (jax.checkpoint semantics: the
    O(T·S) matrix is not a residual, it lives inside the backward
    computation)."""
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


_xla_attention.defvjp(_xla_attention_fwd, _xla_attention_bwd)


# ---------------------------------------------------- the kernels' VJP

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_packed(q, k, v, heads, sm_scale, causal, plan):
    """Attention of (B, T, H·d) x (B, S, H·d) x (B, S, H·dv) by the two
    kernels; ``plan`` is :func:`_plan`'s (group, block_q, block_k,
    interpret)."""
    return _flash_packed_fwd(q, k, v, heads, sm_scale, causal, plan)[0]


def _flash_packed_fwd(q, k, v, heads, sm_scale, causal, plan):
    group, block_q, block_k, interpret = plan
    o, lse = _flash_call(q, k, v, heads, group, sm_scale, causal, block_q,
                         block_k, interpret,
                         q_offset=k.shape[1] - q.shape[1],
                         return_stats=False)
    return o, (q, k, v, o, lse)


def _flash_packed_bwd(heads, sm_scale, causal, plan, res, g):
    group, block_q, block_k, interpret = plan
    return _flash_bwd(*res, g, heads, group, sm_scale, causal, block_q,
                      block_k, interpret)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


# ------------------------------------------------------------- the gate

def _choose_block(n, preferred):
    """The largest divisor of ``n`` up to ``preferred`` (int8_matmul's
    rule too, and interpret mode's)."""
    b = min(preferred, n)
    while n % b:
        b -= 1
    return b


def _choose_seq_block(n, preferred):
    """The largest block of a sequence of ``n`` up to ``preferred`` that
    Mosaic can tile here: a divisor that is a multiple of 128 (the
    statistics' lane axis is cut by it), or the whole of ``n``. 0 where
    there is none."""
    if n <= preferred:
        return n
    for b in range(preferred - preferred % 128, 0, -128):
        if n % b == 0:
            return b
    return 0


def _choose_group(heads, d, dv):
    """Heads a grid step: the fewest whose widths together fill whole
    128-lane tiles; all of them where no divisor of ``heads`` does (the
    interpreter takes that; on the chip :func:`_plan` sends it to XLA)."""
    for g in range(1, heads + 1):
        if heads % g == 0 and g * d % 128 == 0 and g * dv % 128 == 0:
            return g
    return heads


def _plan(t, s, d, dv, heads, causal, itemsize, block_q, block_k,
          interpret):
    """(group, block_q, block_k, interpret) for the kernels, or None for
    XLA: off the TPU, under a mesh, head groups that do not fill whole
    128-lane tiles (head-major operands under 128 wide: half-empty tiles
    move at a seventh of the HBM's rate and lost to XLA on the chip,
    PERF.md §6 PR 34), awkward sequence lengths (prime factors under the
    MXU tile would degrade to scalar-ish tiles), more causal queries
    than keys (rows with no key: the online softmax would emit zeros
    there), or a sequence so long that a head group's resident blocks
    (K/V in the forward; q, do and dq in the backward) pass the VMEM
    budget."""
    if causal and t > s:
        return None
    if interpret:
        return (_choose_group(heads, d, dv),
                _choose_block(t, block_q or _BLOCK_Q),
                _choose_block(s, block_k or _BLOCK_K), True)
    if not _on_tpu() or _under_mesh():
        return None
    group = _choose_group(heads, d, dv)
    if group * d % 128 or group * dv % 128:
        return None
    bq = _choose_seq_block(t, block_q or _BLOCK_Q)
    bk = _choose_seq_block(s, block_k or _BLOCK_K)
    if bq < 32 or bk < 32 or group * max(d, dv) > 1024:
        return None
    gd, gdv = group * d, group * dv
    tiles = 6 * 4 * bq * bk                     # s, p, dp, ds and casts
    # inputs and outputs are double-buffered
    fwd = 2 * itemsize * (bq * gd + s * (gd + gdv) + bq * gdv) + tiles
    bwd = 2 * itemsize * (2 * t * gd + t * gdv + 2 * bk * (gd + gdv)) \
        + 4 * t * gd + tiles
    if max(fwd, bwd) > _VMEM_BUDGET:
        return None
    return group, bq, bk, False


def flash_attention_packed(q, k, v, num_heads, sm_scale=None, causal=False,
                           block_q=None, block_k=None, interpret=False):
    """Multi-head attention of (B, T, H·d) x (B, S, H·d) x (B, S, H·dv)
    -> (B, T, H·dv), heads packed along the last axis as the projections
    leave them. The kernels take the arrays as they are; what the gate
    refuses is split into heads for XLA's attention."""
    b, t, _ = q.shape
    s = k.shape[1]
    d, dv = q.shape[-1] // num_heads, v.shape[-1] // num_heads
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    plan = _plan(t, s, d, dv, num_heads, causal, q.dtype.itemsize, block_q,
                 block_k, interpret)
    if plan is not None:
        return _flash_packed(q, k, v, num_heads, sm_scale, causal, plan)
    qh, kh, vh = (x.reshape(b, n, num_heads, w)
                  for x, n, w in ((q, t, d), (k, s, d), (v, s, dv)))
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (qh, kh, vh))
    out = _xla_attention(qh.reshape(-1, t, d), kh.reshape(-1, s, d),
                         vh.reshape(-1, s, dv), sm_scale, causal)
    return out.reshape(b, num_heads, t, dv).transpose(0, 2, 1, 3).reshape(
        b, t, num_heads * dv)


def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=None,
                    block_k=None, interpret=False):
    """Blockwise fused attention, differentiable through its own kernels.

    Args:
      q: (..., T, d) queries — any number of leading batch/head dims.
      k: (..., S, d) keys; v: (..., S, dv) values, matching leading dims;
        dv may differ from d.
      sm_scale: score scale; default 1/sqrt(d).
      causal: lower-triangular masking (decoder self-attention),
        bottom-right aligned when T < S.
      block_q, block_k: upper bounds of the tile; default chosen from
        T, S and the widths.
      interpret: run the Pallas kernels in interpreter mode (CPU testing).

    Returns (..., T, dv) in the input dtype; softmax/accumulation in fp32.
    On the TPU, outside a mesh, shapes the kernels tile (widths that are
    multiples of 128, blocks of at least 32 that are multiples of 128 or
    whole, within the VMEM budget) take them, every leading index a head
    of its own; everything else takes XLA's attention (a width under 128
    would move half-empty tiles: :func:`flash_attention_packed` is the
    form for those).
    """
    d, dv = q.shape[-1], v.shape[-1]
    t, s = q.shape[-2], k.shape[-2]
    out_shape = q.shape[:-1] + (dv,)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    qr = q.reshape((-1, t, d))
    kr = k.reshape((-1, s, d))
    vr = v.reshape((-1, s, dv))
    plan = _plan(t, s, d, dv, 1, causal, q.dtype.itemsize, block_q,
                 block_k, interpret)
    if plan is None:
        out = _xla_attention(qr, kr, vr, sm_scale, causal)
    else:
        out = _flash_packed(qr, kr, vr, 1, sm_scale, causal, plan)
    return out.reshape(out_shape)
