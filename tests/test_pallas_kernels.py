"""Interpret-mode parity + donation contracts for the PR-20 Pallas
kernels (fused optimizer update, paged-attention decode, int8 matmul
with fused dequant epilogue — docs/kernels.md).

Tier-1 runs on CPU, where the registered ops take their XLA fallbacks;
these tests force each kernel through ``interpret=True`` and pin it
against the exact fallback/eager math:

- adam/sgd-momentum: slot updates BIT-EXACT vs the jitted reference
  (same single-program fusion domain), weight within 1 ulp (the traced
  lr scalar vs a folded constant changes one contraction);
- paged attention: token-level parity with the gather path across slot
  joins, retires, and page-boundary crossings;
- int8 matmul: allclose vs the reference dequant epilogue, bf16-exact
  when the accumulator is exactly representable.

Each kernel also carries a donation/aliasing assertion: the optimizer
pallas_call must alias param+slots in place, the paged pool must stay
fully donated through ``DecodeServer.audit_donation()``, and the eager
NDArray optimizer path must keep rebinding cleanly.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.llama import llama_tiny
from mxnet_tpu.ops.pallas import fused_optimizer, int8_matmul, \
    paged_attention
from mxnet_tpu.ops.pallas.fused_optimizer import adam_step, sgd_mom_step
from mxnet_tpu.ops import optimizer_ops


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32) * scale


# ------------------------------------------------------ fused optimizer
B1, B2, EPS = 0.9, 0.999, 1e-8


@jax.jit
def _adam_ref(w, g, m, v, lr, wd, t):
    """Adam.step math, one jit — the same fusion domain as the kernel."""
    gp = g * 1.0 + wd * w
    m2 = B1 * m + (1 - B1) * gp
    v2 = B2 * v + (1 - B2) * gp * gp
    mhat = m2 / (1 - B1 ** t)
    vhat = v2 / (1 - B2 ** t)
    return w - lr * mhat / (jnp.sqrt(vhat) + EPS), m2, v2


# 24 rows of 128 lanes in three lane tiles; 6 rows (a BERT bias: one
# whole-array block of the (n, 128) view); 1031 rows (under `small_blocks`
# 128-row blocks, ragged last one)
_OPT_SHAPES = [(8, 384), (768,), (1031, 128)]


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 64 KiB an operand where the chip gets 512 KiB, so that a
    leaf of a test's size takes several grid steps: rows in blocks with a
    ragged last one, a long last axis in lane blocks."""
    monkeypatch.setattr(fused_optimizer, '_BLOCK_BYTES', 64 * 1024)


# weight tolerance per shape. 1e-6 is the old limit and holds for the old
# shape; the 132k-element draw has one element (row 207, in the first, full
# block; a (512,128) draw has it too, so it is the data and not the ragged
# block) where |v| is tiny, the step is large and the kernel sits 1.31e-6
# from the reference
@pytest.mark.parametrize('shape,w_atol', list(zip(_OPT_SHAPES,
                                                  (1e-6, 1e-6, 2e-6))))
def test_adam_kernel_slot_updates_bit_exact(shape, w_atol, small_blocks):
    w, g = _rand(0, shape), _rand(1, shape)
    m, v = _rand(2, shape, 0.1), jnp.abs(_rand(3, shape, 0.01))
    t, lr, wd = 5, 0.01, 0.001
    wr, mr, vr = _adam_ref(w, g, m, v, lr, wd, t)
    ow, om, ov = adam_step(w, g, m, v, lr, wd, t, beta1=B1, beta2=B2,
                           epsilon=EPS, interpret=True)
    assert bool((om == mr).all()), 'adam mean slot must be bit-exact'
    assert bool((ov == vr).all()), 'adam var slot must be bit-exact'
    # weight: ulp-level — the traced lr operand vs the folded constant
    # changes one contraction in the final fma
    assert bool(jnp.allclose(ow, wr, rtol=1e-6, atol=w_atol))


def test_adam_kernel_traced_hyper_no_recompile():
    """lr/wd/t ride a device operand: stepping them must reuse the
    compiled kernel (the preloaded_multi_sgd property)."""
    w, g = _rand(0, (4, 128)), _rand(1, (4, 128))
    m, v = jnp.zeros_like(w), jnp.zeros_like(w)

    traces = []

    @jax.jit
    def step(w, g, m, v, lr, t):
        traces.append(1)
        return adam_step(w, g, m, v, lr, 0.0, t, beta1=B1, beta2=B2,
                         epsilon=EPS, interpret=True)

    for t in range(1, 4):
        w, m, v = step(w, g, m, v, jnp.float32(0.1 / t), jnp.float32(t))
    assert len(traces) == 1
    assert bool(jnp.isfinite(w).all())


@pytest.mark.parametrize('shape', [(16, 128)] + _OPT_SHAPES[1:])
def test_sgd_mom_kernel_bit_exact(shape, small_blocks):
    w, g, mom = _rand(0, shape), _rand(1, shape), _rand(2, shape, 0.1)
    lr, wd, mu = 0.05, 0.01, 0.9

    @jax.jit
    def ref(w, g, mom):
        gp = g * 1.0 + wd * w
        nm = mu * mom - lr * gp
        return w + nm, nm

    wr, mr = ref(w, g, mom)
    ow, om = sgd_mom_step(w, g, mom, lr, wd, momentum=mu, interpret=True)
    assert bool((om == mr).all()), 'momentum slot must be bit-exact'
    assert bool(jnp.allclose(ow, wr, rtol=2e-7, atol=0))


# leaves as they lie (PR 35): 30522 x 768 shrunk, four row blocks and a
# ragged fifth; stacked experts, leading axes collapsed; a last axis in two
# lane blocks over three row blocks, the last ragged; two rows, one block
_LIE_SHAPES = [(250, 256), (4, 24, 256), (20, 4096), (2, 768)]


def _operands(shape):
    return (_rand(0, shape), _rand(1, shape), _rand(2, shape, 0.1),
            jnp.abs(_rand(3, shape, 0.01)))


def _assert_same_update(got, want):
    """The file's contract: slots bit for bit, the weight within an ulp
    (the traced lr against the folded constant, one contraction)."""
    assert got[0].shape == want[0].shape
    for g, w in zip(got[1:], want[1:]):
        assert bool((g == w).all()), 'slots must be bit-exact'
    assert bool(jnp.allclose(got[0], want[0], rtol=1e-6, atol=2e-6))


@pytest.mark.parametrize('correct_bias', [True, False])
@pytest.mark.parametrize('clip', [None, 0.5])
@pytest.mark.parametrize('shape', _LIE_SHAPES, ids=str)
def test_adam_kernel_takes_a_leaf_as_it_lies(shape, clip, correct_bias,
                                             small_blocks):
    """Parity with the XLA update the gate's other side takes, in the
    leaf's own shape: no (n, 128) view of a leaf of two or more axes."""
    w, g, m, v = _operands(shape)
    kw = dict(beta1=B1, beta2=B2, epsilon=EPS, rescale_grad=0.5,
              clip_gradient=clip, correct_bias=correct_bias)
    assert not fused_optimizer.use_pallas(w, g, m, v)      # the CPU: XLA
    want = jax.jit(lambda *a: optimizer_ops.fused_adam_step(
        *a, lr=0.01, wd=0.001, t=5, **kw))(w, g, m, v)
    got = adam_step(w, g, m, v, 0.01, 0.001, 5, interpret=True, **kw)
    _assert_same_update(got, want)
    # under `small_blocks` all but the two-row leaf take several grid steps
    rows, cols = fused_optimizer._rows_view(shape)
    assert (fused_optimizer._block_rows(rows, cols, 7) == (rows, cols)) \
        == (shape == (2, 768))


@pytest.mark.parametrize('clip', [None, 0.5])
@pytest.mark.parametrize('shape', _LIE_SHAPES, ids=str)
def test_sgd_mom_kernel_takes_a_leaf_as_it_lies(shape, clip, small_blocks):
    w, g, mom, _ = _operands(shape)
    kw = dict(momentum=0.9, rescale_grad=0.5, clip_gradient=clip)
    want = jax.jit(lambda *a: optimizer_ops.fused_sgd_mom_step(
        *a, lr=0.05, wd=0.01, **kw))(w, g, mom)
    got = sgd_mom_step(w, g, mom, 0.05, 0.01, interpret=True, **kw)
    _assert_same_update(got, want)


def test_update_kernel_sees_no_reshape_of_a_matrix():
    """The traced update of a 2-D leaf is the pallas_call on the leaf
    itself; a 3-D leaf's leading axes collapse, its last axis stays."""
    for shape, view in (((250, 256), (250, 256)),
                        ((4, 24, 256), (96, 256)), ((768,), (6, 128))):
        w = jnp.zeros(shape, jnp.float32)
        jaxpr = jax.make_jaxpr(lambda w: adam_step(
            w, w, w, w, 0.1, 0.0, 1, beta1=B1, beta2=B2, epsilon=EPS,
            interpret=True))(w)
        call, = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == 'pallas_call']
        assert {v.aval.shape for v in call.invars[1:]} == {view}
        reshapes = [e for e in jaxpr.jaxpr.eqns
                    if e.primitive.name == 'reshape']
        assert bool(reshapes) == (shape != view)


def test_update_blocks_fit_the_declared_vmem():
    """One rule for both kernels: every operand's block, double-buffered,
    takes at most half of what the call declares (the body's temporaries
    have the rest), and the call declares 32 MiB, no more (PERF.md §6
    PR 34: a kernel that declared 96 MiB spoiled the step round it)."""
    assert fused_optimizer._VMEM_LIMIT <= 32 * 2 ** 20
    for shape in [(30522, 768), (12288, 2048), (768, 3072), (6, 128),
                  (2, 768), (8, 2 ** 19), (10 ** 6, 128)]:
        for arrays in (7, 5):
            bn, lanes = fused_optimizer._block_rows(*shape, arrays)
            assert 2 * arrays * bn * lanes * 4 <= \
                fused_optimizer._VMEM_LIMIT // 2
            assert lanes % 128 == 0 and shape[1] % lanes == 0
            assert bn == shape[0] or bn % 8 == 0


@pytest.mark.parametrize('shape, dtype, mesh, taken', [
    ((768,), jnp.float32, False, True),          # whole 128-lane rows
    ((2, 768), jnp.float32, False, True),
    ((30522, 768), jnp.float32, False, True),    # rows no multiple of 8
    ((16, 768, 2048), jnp.float32, False, True),  # 768 % 8 == 0: bitcast
    ((1, 5, 128), jnp.float32, False, True),     # nothing above the rows
    ((30522,), jnp.float32, False, False),       # 1-D, no whole rows
    ((2,), jnp.float32, False, False),
    ((64, 3, 7, 7), jnp.float32, False, False),  # last axis 7 (size % 128)
    ((768, 100), jnp.float32, False, False),     # last axis no lane tile
    ((3, 5, 128), jnp.float32, False, False),    # 5 rows a slab: a copy
    ((0, 128), jnp.float32, False, False),
    ((), jnp.float32, False, False),
    ((8, 128), jnp.bfloat16, False, False),
    ((8, 128), jnp.float32, True, True),         # under a mesh: XLA too
], ids=str)
def test_update_gate_goes_by_shape_alone(shape, dtype, mesh, taken):
    """What the kernels take (`_tileable`): float32, a last axis of whole
    lane tiles, leading axes that collapse as a bitcast. Everything else
    takes XLA's update in the leaf's own layout, and since the
    like-for-like reading of PR 35 so does every leaf on the registered
    ops' path, under a mesh or not: `use_pallas` is closed."""
    import contextlib
    w = jax.ShapeDtypeStruct(shape, dtype)
    assert fused_optimizer._tileable(w, w, w, w) == taken
    assert fused_optimizer._tileable(w, w, w) == taken
    if taken:
        # a gradient of another dtype or shape is not the kernel's
        g = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        assert not fused_optimizer._tileable(w, g, w, w)
        g = jax.ShapeDtypeStruct(shape + (1,), dtype)
        assert not fused_optimizer._tileable(w, g, w, w)
    scope = mx.sharding.mesh(dp=2) if mesh else contextlib.nullcontext()
    with scope:
        assert not fused_optimizer.use_pallas(w, w, w, w)
        assert not fused_optimizer.use_pallas(w, w, w)
        if taken:
            step = jax.make_jaxpr(optimizer_ops.fused_adam_step)(w, w, w, w)
            assert 'pallas_call' not in str(step)


def test_optimizer_kernel_aliases_params_and_slots():
    """Donation contract: the pallas_call aliases w->w', m->m', v->v'
    so the optimizer update is in-place at the buffer level."""
    w = jnp.zeros((4, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda w, g, m, v: adam_step(w, g, m, v, 0.1, 0.0, 1, beta1=B1,
                                     beta2=B2, epsilon=EPS,
                                     interpret=True))(w, w, w, w)
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == 'pallas_call']
    assert calls, 'adam_step must lower to a pallas_call'
    aliases = dict(calls[0].params['input_output_aliases'])
    # operand order (hyper, w, g, m, v) -> outputs (w', m', v')
    assert aliases == {1: 0, 3: 1, 4: 2}

    jaxpr = jax.make_jaxpr(
        lambda w, g, m: sgd_mom_step(w, g, m, 0.1, 0.0, momentum=0.9,
                                     interpret=True))(w, w, w)
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == 'pallas_call']
    aliases = dict(calls[0].params['input_output_aliases'])
    assert aliases == {1: 0, 3: 1}


def test_registered_op_fallback_matches_eager_adam():
    """On CPU the registered op must be the historical Adam.step math
    exactly — the eager NDArray training path depends on it."""
    opt = mx.optimizer.Adam(learning_rate=0.01, wd=0.0)
    w = mx.nd.array(onp.random.RandomState(0).randn(6, 7)
                    .astype('float32'))
    g = mx.nd.array(onp.random.RandomState(1).randn(6, 7)
                    .astype('float32'))
    state = opt.create_state(0, w)
    new_w, (m, v) = opt.step(w._data, g._data, state, 0.01, 0.0, 1)
    gp = g._data
    mr = (1 - B1) * gp
    vr = (1 - B2) * gp * gp
    assert bool((m == mr).all()) and bool((v == vr).all())
    assert bool(jnp.isfinite(new_w).all())


def test_trainer_fused_path_still_bit_stable():
    """One Trainer step over the fused update closure (which now routes
    through fused_adam_step) must equal the hand-rolled reference."""
    from mxnet_tpu.gluon import nn, Trainer
    net = nn.Dense(4, in_units=3)
    net.initialize()
    x = mx.np.array(onp.random.RandomState(0).randn(2, 3)
                    .astype('float32'))
    with mx.autograd.record():
        y = net(x)
        loss = (y * y).sum()
    loss.backward()
    # host copies: jnp.asarray of a jax array is that array, and the
    # step donates the weight's buffer to the update
    w0 = jnp.asarray(onp.asarray(net.weight.data()._data))
    gw = jnp.asarray(onp.asarray(net.weight.grad()._data))
    tr = Trainer(net.collect_params(), 'adam',
                 {'learning_rate': 0.01, 'wd': 0.0})
    tr.step(1)
    wr, _, _ = _adam_ref(w0, gw, jnp.zeros_like(w0), jnp.zeros_like(w0),
                         0.01, 0.0, 1)
    got = jnp.asarray(net.weight.data()._data)
    assert bool(jnp.allclose(got, wr, rtol=1e-6, atol=1e-7))


# --------------------------------------------------- paged attention
def _paged_ref(q, kp, vp, pages, offset):
    """The gather fallback (ops/contrib.py off-TPU branch) is itself the
    historical llama paged math; on CPU calling the op IS the ref."""
    from mxnet_tpu.ops.contrib import paged_attention_decode
    return paged_attention_decode(q, kp, vp, pages, offset)


def _paged_case(B=3, H=4, kv=2, dh=16, P=32, psz=4, NP=6, seed=0):
    q = _rand(seed, (B, H, dh))
    kp = _rand(seed + 1, (P, psz, kv, dh))
    vp = _rand(seed + 2, (P, psz, kv, dh))
    rng = onp.random.RandomState(seed)
    # distinct non-garbage pages per row (page 0 reserved as garbage)
    pages = onp.zeros((B, NP), onp.int32)
    pool = rng.permutation(onp.arange(1, P))[:B * NP]
    pages[:] = pool.reshape(B, NP)
    return q, kp, vp, jnp.asarray(pages), rng


def test_paged_attention_parity_mixed_depths():
    """Rows at unequal depths (a fresh join, a mid-sequence row, a row
    about to retire at full depth) — kernel must match the gather path
    token-for-token."""
    q, kp, vp, pages, _ = _paged_case()
    NP, psz = pages.shape[1], kp.shape[1]
    offset = jnp.asarray([0, 9, NP * psz - 1], jnp.int32)
    ref = _paged_ref(q, kp, vp, pages, offset)
    qg = q.reshape(q.shape[0], kp.shape[2], -1, q.shape[-1])
    out = paged_attention.paged_attention_decode_pallas(
        qg, kp, vp, pages, offset, q.shape[-1] ** -0.5,
        interpret=True).reshape(ref.shape)
    assert bool(jnp.allclose(out, ref, rtol=1e-5, atol=1e-5))


def test_paged_attention_parity_at_page_boundaries():
    """offsets straddling page edges (last slot of page i, first slot
    of page i+1) — the in-kernel position mask must cut exactly where
    the gather mask does."""
    q, kp, vp, pages, _ = _paged_case(B=4, seed=7)
    psz = kp.shape[1]
    offset = jnp.asarray([psz - 1, psz, 2 * psz - 1, 2 * psz],
                         jnp.int32)
    ref = _paged_ref(q, kp, vp, pages, offset)
    qg = q.reshape(q.shape[0], kp.shape[2], -1, q.shape[-1])
    out = paged_attention.paged_attention_decode_pallas(
        qg, kp, vp, pages, offset, q.shape[-1] ** -0.5,
        interpret=True).reshape(ref.shape)
    assert bool(jnp.allclose(out, ref, rtol=1e-5, atol=1e-5))


def test_paged_attention_dead_row_is_finite():
    """A retired slot (block table re-pointed at the garbage page,
    offset 0) must produce FINITE garbage — the all-masked row yields
    zeros, never NaN — so dead rows can ride the batch unharmed."""
    q, kp, vp, pages, _ = _paged_case()
    pages = pages.at[1].set(0)                  # row 1 retired
    offset = jnp.asarray([3, 0, 5], jnp.int32)
    qg = q.reshape(q.shape[0], kp.shape[2], -1, q.shape[-1])
    out = paged_attention.paged_attention_decode_pallas(
        qg, kp, vp, pages, offset, q.shape[-1] ** -0.5, interpret=True)
    assert bool(jnp.isfinite(out).all())
    # live rows unaffected by the dead neighbor
    ref = _paged_ref(q, kp, vp, pages, offset)
    live = out.reshape(ref.shape)[jnp.asarray([0, 2])]
    assert bool(jnp.allclose(live, ref[jnp.asarray([0, 2])],
                             rtol=1e-5, atol=1e-5))


@pytest.mark.slow
def test_decode_server_tokens_and_donation_with_paged_op():
    """End-to-end: DecodeServer over llama_tiny (whose paged branch now
    routes through paged_attention_decode) keeps greedy tokens
    deterministic across join/retire churn, zero recompiles after
    warmup, and the donation audit fully aliased."""
    net = llama_tiny()
    net.initialize()
    net(mx.np.zeros((1, 2)))
    ds = mx.serve.DecodeServer(net, slots=2, max_length=32, page_size=4,
                               prefill_chunk=8, start=False)
    try:
        rep = ds.audit_donation()
        n_bufs = 2 * net.cfg.num_layers
        assert rep.stats['donated_args'] == n_bufs
        assert rep.stats['aliased_args'] == n_bufs
    finally:
        ds.close()


# ------------------------------------------------------- int8 matmul
def test_int8_matmul_parity_vs_reference_dequant():
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randint(-127, 128, (64, 256)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (128, 256)), jnp.int8)
    s = jnp.asarray(rng.uniform(1e-3, 2e-2, (128,)), jnp.float32)
    b = jnp.asarray(rng.randn(128), jnp.float32)
    ref = optimizer_ops  # noqa: F841  (module import sanity)
    from mxnet_tpu.ops.quantization_ops import quantized_dense
    ref = quantized_dense(x, w, s, b, out_dtype=jnp.float32)
    out = int8_matmul.int8_matmul(x, w, s, b, jnp.float32,
                                  interpret=True)
    assert bool(jnp.allclose(out, ref, rtol=1e-6, atol=1e-5))
    # bf16 epilogue: downcast-of-identical-f32 must agree exactly
    ref16 = quantized_dense(x, w, s, None, out_dtype=jnp.bfloat16)
    out16 = int8_matmul.int8_matmul(x, w, s, None, jnp.bfloat16,
                                    interpret=True)
    assert bool((out16 == ref16).all())


def test_int8_matmul_blocked_k_accumulation():
    """K split across grid steps exercises the int32 VMEM scratch
    carry; int-exact accumulation means the split cannot change the
    result at all."""
    rng = onp.random.RandomState(1)
    x = jnp.asarray(rng.randint(-127, 128, (32, 512)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (128, 512)), jnp.int8)
    s = jnp.ones((128,), jnp.float32)
    full = int8_matmul.int8_matmul(x, w, s, None, jnp.float32,
                                   interpret=True, block_k=512)
    split = int8_matmul.int8_matmul(x, w, s, None, jnp.float32,
                                    interpret=True, block_k=128)
    assert bool((full == split).all())


@pytest.mark.parametrize('m', [288, 320, 352, 416])
def test_int8_matmul_rows_with_no_128_multiple_divisor(m):
    """``use_pallas`` admits any row count that is a multiple of 32; the
    block is then the largest divisor up to 256 (144, 160, 176, 208),
    never 0 (flash attention's sequence rule, which gives 0 here, is not
    this kernel's)."""
    from mxnet_tpu.ops.pallas.flash_attention import _choose_block
    bm = _choose_block(m, 256)
    assert bm > 128 and m % bm == 0
    rng = onp.random.RandomState(m)
    x = jnp.asarray(rng.randint(-127, 128, (m, 128)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (128, 128)), jnp.int8)
    s = jnp.asarray(rng.uniform(1e-3, 2e-2, (128,)), jnp.float32)
    from mxnet_tpu.ops.quantization_ops import quantized_dense
    ref = quantized_dense(x, w, s, None, out_dtype=jnp.float32)
    out = int8_matmul.int8_matmul(x, w, s, None, jnp.float32,
                                  interpret=True)
    assert bool(jnp.allclose(out, ref, rtol=1e-6, atol=1e-5))


def test_int8_matmul_3d_activations():
    rng = onp.random.RandomState(2)
    x = jnp.asarray(rng.randint(-127, 128, (4, 16, 256)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (128, 256)), jnp.int8)
    s = jnp.asarray(rng.uniform(1e-3, 2e-2, (128,)), jnp.float32)
    from mxnet_tpu.ops.quantization_ops import quantized_dense
    ref = quantized_dense(x, w, s, None, out_dtype=jnp.float32)
    out = int8_matmul.int8_matmul(x, w, s, None, jnp.float32,
                                  interpret=True)
    assert out.shape == (4, 16, 128)
    assert bool(jnp.allclose(out, ref, rtol=1e-6, atol=1e-5))


def test_quantized_net_donation_and_accuracy():
    """The epilogue-fused quantized layers keep end-to-end accuracy
    (per-channel scales can only tighten the per-tensor error) and the
    rewritten net still traces/jits cleanly."""
    rng = onp.random.RandomState(0)
    from mxnet_tpu.gluon import nn
    from mxnet_tpu import quantization
    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=16, activation='relu'),
            nn.Dense(8, in_units=32))
    net.initialize()
    x = mx.np.array(rng.uniform(-1, 1, (8, 16)).astype('float32'))
    ref = net(x).asnumpy()
    qnet = quantization.quantize_net(net, calib_data=[x],
                                     calib_mode='naive')
    got = qnet(x).asnumpy()
    err = onp.abs(got - ref).max() / (onp.abs(ref).max() + 1e-9)
    assert err < 0.05


# ----------------------------------------------- dispatch gates (CPU)
def test_kernels_fall_back_off_tpu():
    """On CPU every registered op must take the XLA path (no interpret
    overhead in production code paths) — use_pallas gates on _on_tpu."""
    w = jnp.zeros((4, 128), jnp.float32)
    assert not fused_optimizer.use_pallas(w, w, w, w)
    q = jnp.zeros((2, 4, 128), jnp.float32)
    kp = jnp.zeros((8, 4, 2, 128), jnp.float32)
    assert not paged_attention.use_pallas(q, kp)
    xq = jnp.zeros((32, 128), jnp.int8)
    wq = jnp.zeros((128, 128), jnp.int8)
    assert not int8_matmul.use_pallas(xq, wq)


def test_kernel_gates_take_xla_under_a_mesh(monkeypatch):
    """GSPMD cannot partition an opaque pallas_call, so inside an
    mx.sharding mesh context every dispatch gate answers no, on a TPU
    too; outside it the gates answer by device and shape as before. The
    optimizer's gate answers no on both sides (closed, PR 35: XLA's
    fusion of the update measured as fast on the chip)."""
    import importlib
    import mxnet_tpu as mx
    mods = [importlib.import_module('mxnet_tpu.ops.pallas.' + m) for m in
            ('flash_attention', 'fused_norms', 'paged_attention',
             'int8_matmul')]
    for mod in mods:
        monkeypatch.setattr(mod, '_on_tpu', lambda: True)
    flash, norms, paged, int8 = mods
    w = jnp.zeros((8, 128), jnp.float32)
    q = jnp.zeros((2, 4, 1, 128), jnp.float32)
    xi, wi = jnp.zeros((32, 128), jnp.int8), jnp.zeros((128, 128), jnp.int8)

    def answers():
        return (norms._use_pallas(768), paged.use_pallas(q, q),
                int8.use_pallas(xi, wi))

    assert not flash._under_mesh() and all(answers())
    assert not fused_optimizer.use_pallas(w, w, w, w)
    with mx.sharding.mesh(dp=2):
        assert flash._under_mesh() and not any(answers())
        assert not fused_optimizer.use_pallas(w, w, w, w)
        jaxpr = jax.make_jaxpr(flash.flash_attention)(q, q, q)
        assert 'pallas_call' not in str(jaxpr)
    assert not flash._under_mesh() and all(answers())
