"""Transformer stack tests: Pallas flash attention + BERT model family.

Coverage model (SURVEY §4): numeric checks vs a plain XLA reference for the
kernel (the role of test_operator.py's numeric checks), end-to-end
train-step assertions for the model (the role of tests/python/train/).
"""

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.ops.pallas.flash_attention import (_reference_attention,
                                                  flash_attention)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('t,s', [(64, 64), (32, 96)])
def test_flash_kernel_matches_reference(causal, t, s):
    rng = onp.random.default_rng(0)
    import jax.numpy as jnp
    q = jnp.asarray(rng.standard_normal((2, 2, t, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, s, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, s, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=32, block_k=32)
    ref = _reference_attention(
        q.reshape(-1, t, 32), k.reshape(-1, s, 32), v.reshape(-1, s, 32),
        32 ** -0.5, causal).reshape(q.shape)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def _head_major(rng, bh, t, s, d, dv):
    import jax.numpy as jnp
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return mk(bh, t, d), mk(bh, s, d), mk(bh, s, dv), mk(bh, t, dv)


def _close_to(got, want, tol):
    """Every element within ``tol`` of the largest of the reference."""
    got, want = onp.asarray(got, 'f8'), onp.asarray(want, 'f8')
    assert got.shape == want.shape
    assert onp.abs(got - want).max() <= tol * onp.abs(want).max()


@pytest.fixture
def bf16_operands(monkeypatch):
    """Every product's operands rounded to bfloat16 in interpret mode
    too, as on the chip. The two kernel calls are jitted, so what was
    traced under another rounding is dropped before and after."""
    import importlib
    import jax.numpy as jnp
    flash_mod = importlib.import_module(
        'mxnet_tpu.ops.pallas.flash_attention')

    def drop_traces():
        flash_mod._flash_call.clear_cache()
        flash_mod._flash_bwd.clear_cache()

    drop_traces()
    monkeypatch.setattr(flash_mod, '_mxu',
                        lambda x, dtype: x.astype(jnp.bfloat16))
    yield flash_mod
    monkeypatch.undo()
    drop_traces()


def _packed(a, b, heads):
    """(B·H, T, w) -> (B, T, H·w): heads along the last axis."""
    return a.reshape(b, heads, a.shape[1], -1).transpose(0, 2, 1, 3).reshape(
        b, a.shape[1], -1)


def _unpacked(a, heads):
    """(B, T, H·w) -> (B·H, T, w)."""
    b, t, _ = a.shape
    return a.reshape(b, t, heads, -1).transpose(0, 2, 1, 3).reshape(
        b * heads, t, -1)


@pytest.mark.parametrize('widths', [(64, 64), (192, 128)],
                         ids=['d64', 'qk192_v128'])
@pytest.mark.parametrize('t,s', [(128, 128), (256, 512), (512, 512)])
@pytest.mark.parametrize('causal', [False, True])
def test_flash_kernel_pair_gradients_match_reference(monkeypatch, causal,
                                                     t, s, widths):
    """The forward and the backward kernel, run by the interpreter in
    blocks of 128 (so masked, unmasked and skipped blocks all occur),
    against jax.grad of the plain attention, through
    multi_head_attention: four heads in two groups of two, packed along
    the lanes as the kernels take them (64-wide heads are picked by
    zeroed lanes, as are the 192-wide; the 128-wide values by a lane
    slice). 64-wide heads also go through flash_attention itself, every
    head a group of its own. Interpret mode keeps float32 operands, so
    kernels and reference differ by the order of their sums alone: 1e-4
    of the largest element."""
    import functools
    import importlib
    import jax
    flash_mod = importlib.import_module(
        'mxnet_tpu.ops.pallas.flash_attention')
    d, dv = widths
    heads, b = 4, 1
    q, k, v, w = _head_major(onp.random.default_rng(t + s + d), b * heads,
                             t, s, d, dv)
    scale = d ** -0.5
    blocks = dict(interpret=True, block_q=128, block_k=128)
    monkeypatch.setattr(
        flash_mod, 'flash_attention_packed',
        functools.partial(flash_mod.flash_attention_packed, **blocks))
    routes = [lambda q, k, v: _unpacked(mx.ops.contrib.multi_head_attention(
        _packed(q, b, heads), _packed(k, b, heads), _packed(v, b, heads),
        heads, causal=causal), heads)]
    if d == dv:
        routes.append(functools.partial(flash_attention, causal=causal,
                                        **blocks))
    want = lambda q, k, v: _reference_attention(q, k, v, scale, causal)
    grads = lambda f: jax.grad(lambda *a: (f(*a) * w).sum(),
                               (0, 1, 2))(q, k, v)
    want_out, want_grads = want(q, k, v), grads(want)
    for got in routes:
        _close_to(got(q, k, v), want_out, 1e-4)
        for a, e in zip(grads(got), want_grads):
            _close_to(a, e, 1e-4)


@pytest.mark.parametrize('t,s', [(128, 128), (256, 512), (512, 512)])
@pytest.mark.parametrize('causal', [False, True])
def test_flash_forward_saves_each_rows_logsumexp(causal, t, s):
    """The one statistic the backward is rebuilt from: lse = m + log l of
    the scaled, masked scores, f32[batch, groups, heads a group, T]."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.flash_attention import _flash_call
    heads = 2
    q, k, v, _ = _head_major(onp.random.default_rng(s), heads, t, s, 64,
                             64)
    scale = 0.125
    o, lse = _flash_call(_packed(q, 1, heads), _packed(k, 1, heads),
                         _packed(v, 1, heads), heads, 2, scale, causal,
                         128, 128, True, q_offset=s - t,
                         return_stats=False)
    scores = jnp.einsum('bqd,bkd->bqk', q, k) * scale
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((t, s), bool), k=s - t),
                           scores, -jnp.inf)
    assert lse.shape == (1, 1, heads, t) and lse.dtype == jnp.float32
    onp.testing.assert_allclose(
        onp.asarray(lse[0, 0]),
        onp.asarray(jax.scipy.special.logsumexp(scores, axis=-1)),
        rtol=1e-5, atol=1e-5)
    _close_to(_unpacked(o, heads),
              _reference_attention(q, k, v, scale, causal), 1e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_backward_keeps_xlas_cancellation_at_one_bf16_pass(
        bf16_operands, causal):
    """ds = p (dp - delta) is a difference of two means of do·v over the
    keys. Where the values share a large common part (3 +- 0.1) the two
    nearly cancel, which holds only if delta has do as the kernel's dp
    has it: rounded as the MXU gets it. XLA's recompute, which takes
    delta from its own p and dp, is the bar: with every product's
    operands rounded to bfloat16 the kernels' dq and dk may be no
    further from the float32 gradient than 1.05 times what that branch
    reads at the same rounding (both 4.5 %; the kernels 6.8 % with do
    left float32 in delta)."""
    import jax
    import jax.numpy as jnp
    flash_mod = bf16_operands
    rng = onp.random.default_rng(0)
    b, heads, t, d = 1, 2, 256, 64
    mk = lambda scale, offset=0.0: jnp.asarray(
        offset + scale * rng.standard_normal((b * heads, t, d)),
        jnp.float32)
    q, k, v, w = mk(0.3), mk(0.3), mk(0.1, 3.0), mk(1.0)
    scale = d ** -0.5
    got = jax.grad(lambda q, k, v: (_unpacked(
        flash_mod.flash_attention_packed(
            _packed(q, b, heads), _packed(k, b, heads),
            _packed(v, b, heads), heads, causal=causal, interpret=True,
            block_q=128, block_k=128), heads) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: (_reference_attention(
        q, k, v, scale, causal) * w).sum(), (0, 1, 2))(q, k, v)

    # XLA's branch with each einsum's operands at one bfloat16 pass
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jnp.einsum('bqd,bkd->bqk', bf(q), bf(k)) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    p = jax.nn.softmax(s, -1)
    dp = jnp.einsum('bqd,bkd->bqk', bf(w), bf(v))
    ds = bf(p * (dp - jnp.sum(p * dp, -1, keepdims=True)) * scale)
    xla = (jnp.einsum('bqk,bkd->bqd', ds, bf(k)),
           jnp.einsum('bqk,bqd->bkd', ds, bf(q)))
    off = lambda a, e: float(jnp.linalg.norm((a - e).ravel())
                             / jnp.linalg.norm(e.ravel()))
    for mine, theirs, exact in zip(got, xla, want):
        assert off(mine, exact) <= 1.05 * off(theirs, exact)
    assert off(xla[0], want[0]) > 0.02          # the probe does probe


@pytest.mark.parametrize('heads, d, dv, group', [
    (12, 64, 64, 2),        # BERT-base: two heads fill a 128-lane tile
    (16, 64, 64, 2),        # BERT-large
    (32, 192, 128, 2),      # latent attention: 384 and 256 lanes
    (8, 128, 128, 1),       # Llama: a head is a tile
    (4, 96, 96, 4),         # 384 lanes
    (3, 64, 64, 3),         # no divisor fills tiles: the whole lane axis
    (1, 64, 64, 1),         # head-major operands: every head its own
])
def test_flash_heads_a_grid_step(heads, d, dv, group):
    from mxnet_tpu.ops.pallas.flash_attention import _choose_group
    assert _choose_group(heads, d, dv) == group


@pytest.mark.parametrize('n, preferred, block', [
    (512, 512, 512), (1024, 512, 512), (640, 512, 128), (96, 512, 96),
    (384, 256, 128), (1000, 512, 0), (8, 128, 8)])
def test_flash_block_is_a_multiple_of_128_or_the_whole(n, preferred, block):
    """What Mosaic can tile: the statistics' lane axis is cut by the
    block. A length with no such divisor goes to XLA (block 0); the
    interpreter, like int8_matmul, takes any divisor."""
    from mxnet_tpu.ops.pallas.flash_attention import _choose_block, \
        _choose_seq_block
    assert _choose_seq_block(n, preferred) == block
    assert _choose_block(1000, 512) == 500


def test_flash_kernels_declare_32_mib_of_vmem():
    """Both kernels ask Mosaic for 32 MiB and no more: with 96 MiB
    declared the sparse decoder's step drifted on the chip though each
    kernel's own outputs were right, for a cause that was not found
    (PERF.md §7, ROADMAP A3 has the probe). Whoever raises the number
    reruns that probe."""
    import jax
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_packed
    x = jax.ShapeDtypeStruct((1, 128, 128), 'float32')
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention_packed(
        q, k, v, 2, interpret=True).sum(), (0, 1, 2)))(x, x, x)

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)

    limits = {eqn.params['name']:
              eqn.params['compiler_params']['mosaic_tpu'].vmem_limit_bytes
              for eqn in pallas_calls(jaxpr.jaxpr)}
    assert limits == {'mx_flash_attention': 32 * 2 ** 20,
                      'mx_flash_attention_bwd': 32 * 2 ** 20}


def test_flash_gate_decides_by_what_it_sees(monkeypatch):
    """On the TPU (steered here) the kernels take shapes they tile, and
    XLA takes the rest: under a mesh, no 128-multiple block, tiny
    sequences, more causal queries than keys, K/V past the VMEM budget,
    head groups that do not fill whole 128-lane tiles."""
    import importlib
    flash_mod = importlib.import_module(
        'mxnet_tpu.ops.pallas.flash_attention')
    plan = lambda t, s, d=64, dv=64, heads=12, causal=False: \
        flash_mod._plan(t, s, d, dv, heads, causal, 4, None, None, False)
    assert plan(512, 512) is None                       # the CPU
    monkeypatch.setattr(flash_mod, '_on_tpu', lambda: True)
    assert plan(512, 512) == (2, 512, 512, False)
    assert plan(1024, 1024, 192, 128, 32, True) == (2, 512, 512, False)
    assert plan(128, 128) == (2, 128, 128, False)
    assert plan(640, 640) == (2, 128, 128, False)
    assert plan(1000, 1000) is None
    assert plan(16, 16) is None
    assert plan(512, 256, causal=True) is None
    assert plan(256, 512, causal=True) == (2, 256, 512, False)
    assert plan(2048, 2048, 128, 128, 8, True) == (1, 512, 512, False)
    assert plan(8192, 8192, 128, 128, 8, True) is None
    assert plan(4096, 4096, 192, 128, 32, True) is None
    # head-major operands: whole tiles a head, or XLA (half-empty tiles
    # lost to it on the chip); so with groups that fill no tile
    assert plan(512, 512, 128, 128, 1) == (1, 512, 512, False)
    assert plan(512, 512, 64, 64, 1) is None
    assert plan(512, 512, 192, 128, 1) is None
    assert plan(512, 512, 64, 64, 3) is None
    with mx.sharding.mesh(dp=1):
        assert plan(512, 512) is None


@pytest.mark.parametrize('causal', [False, True])
def test_flash_kernel_pair_with_operands_of_one_bf16_pass(bf16_operands,
                                                          causal):
    """On the chip every product's operands are rounded to bfloat16 and
    accumulated in float32, as XLA's default precision has them there.
    Forced here through the interpreter: outputs and gradients stay
    within 2 % of the largest element of the float32 reference (a
    bfloat16 operand carries 8 bits: 0.4 % an element, summed over a
    row), and the row statistics stay float32."""
    import jax
    import jax.numpy as jnp
    q, k, v, w = _head_major(onp.random.default_rng(5), 2, 256, 256, 64,
                             64)
    got = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                          interpret=True, block_q=128,
                                          block_k=128)
    want = lambda q, k, v: _reference_attention(q, k, v, 0.125, causal)
    out = got(q, k, v)
    assert out.dtype == jnp.float32
    _close_to(out, want(q, k, v), 2e-2)
    grads = lambda f: jax.grad(lambda *a: (f(*a) * w).sum(),
                               (0, 1, 2))(q, k, v)
    for a, e in zip(grads(got), grads(want)):
        assert a.dtype == jnp.float32
        _close_to(a, e, 2e-2)
    # and they are not the float32 kernels' bits
    assert not onp.array_equal(
        onp.asarray(out),
        onp.asarray(_reference_attention(q, k, v, 0.125, causal)))


def test_flash_attention_op_and_grad():
    rng = onp.random.default_rng(1)
    q = mx.np.array(rng.standard_normal((2, 2, 32, 16)), dtype='float32')
    q.attach_grad()
    with autograd.record():
        out = mx.npx.flash_attention(q, q, q, causal=True)
        loss = (out ** 2).sum()
    loss.backward()
    assert q.grad is not None
    g = q.grad.asnumpy()
    assert onp.isfinite(g).all() and onp.abs(g).sum() > 0


def test_multi_head_attention_flash_path_matches_masked_path():
    rng = onp.random.default_rng(2)
    b, t, e, h = 2, 16, 32, 4
    q = mx.np.array(rng.standard_normal((b, t, e)), dtype='float32')
    k = mx.np.array(rng.standard_normal((b, t, e)), dtype='float32')
    v = mx.np.array(rng.standard_normal((b, t, e)), dtype='float32')
    out_flash = mx.npx.multi_head_attention(q, k, v, h)
    full = mx.np.ones((b, 1, t, t), dtype='bool')
    out_masked = mx.npx.multi_head_attention(q, k, v, h, mask=full)
    onp.testing.assert_allclose(out_flash.asnumpy(), out_masked.asnumpy(),
                                rtol=1e-5, atol=1e-5)


def _tiny_bert(**kw):
    cfg = dict(vocab_size=200, num_layers=2, units=32, hidden_size=64,
               num_heads=4, max_length=32, dropout=0.0)
    cfg.update(kw)
    return bert.get_bert_model('bert_12_768_12', **cfg)


def test_bert_output_shapes():
    net = _tiny_bert()
    net.initialize()
    ids = mx.np.zeros((2, 12), dtype='int32')
    tt = mx.np.zeros((2, 12), dtype='int32')
    seq, pooled, mlm, nsp = net(ids, tt)
    assert seq.shape == (2, 12, 32)
    assert pooled.shape == (2, 32)
    assert mlm.shape == (2, 12, 200)
    assert nsp.shape == (2, 2)


def test_bert_valid_length_masks_padding():
    net = _tiny_bert(use_decoder=False, use_classifier=False)
    net.initialize()
    rng = onp.random.default_rng(3)
    base = rng.integers(1, 200, (1, 10))
    ids_a = mx.np.array(base, dtype='int32')
    # same first 6 tokens, garbage tail
    tail = base.copy()
    tail[0, 6:] = rng.integers(1, 200, 4)
    ids_b = mx.np.array(tail, dtype='int32')
    vl = mx.np.array([6], dtype='int32')
    tt = mx.np.zeros((1, 10), dtype='int32')
    out_a = net(ids_a, tt, vl)[0].asnumpy()
    out_b = net(ids_b, tt, vl)[0].asnumpy()
    # valid positions must not see the padded tail
    onp.testing.assert_allclose(out_a[0, :6], out_b[0, :6],
                                rtol=1e-5, atol=1e-5)


def test_bert_train_step_reduces_loss():
    net = _tiny_bert(use_classifier=False)
    net.initialize()
    rng = onp.random.default_rng(4)
    ids = mx.np.array(rng.integers(0, 200, (4, 12)), dtype='int32')
    tt = mx.np.zeros((4, 12), dtype='int32')
    labels = mx.np.array(rng.integers(0, 200, (4, 12)), dtype='int32')
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    losses = []
    for _ in range(8):
        with autograd.record():
            _, _, mlm = net(ids, tt)
            loss = loss_fn(mlm, labels).mean()
        loss.backward()
        trainer.step(4)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0]


def test_bert_hybridize_matches_eager():
    net = _tiny_bert(use_classifier=False, use_decoder=False)
    net.initialize()
    ids = mx.np.array(onp.arange(24).reshape(2, 12) % 200, dtype='int32')
    tt = mx.np.zeros((2, 12), dtype='int32')
    ref = net(ids, tt)[0].asnumpy()
    net.hybridize()
    net(ids, tt)
    out = net(ids, tt)[0].asnumpy()
    onp.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_bert_hybridized_train_step():
    """Full hybridized train step must work."""
    net = _tiny_bert(use_classifier=False)
    net.initialize()
    ids = mx.np.zeros((2, 8), dtype='int32')
    tt = mx.np.zeros((2, 8), dtype='int32')
    net(ids, tt)
    net.hybridize(static_alloc=True)
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    labels = mx.np.zeros((2, 8), dtype='int32')
    for _ in range(2):
        with autograd.record():
            _, _, mlm = net(ids, tt)
            loss = loss_fn(mlm, labels).mean()
        loss.backward()
        trainer.step(2)
    assert onp.isfinite(float(loss.asnumpy()))


def test_bert_large_config():
    cfg = bert._BERT_CONFIGS['bert_24_1024_16']
    assert cfg['num_layers'] == 24 and cfg['units'] == 1024


def test_mha_causal_alignment_consistent_tne_s():
    """Flash and masked branches must agree on causal alignment when T!=S
    (code-review regression: KV-cache decode)."""
    rng = onp.random.default_rng(5)
    b, t, s, e, h = 1, 2, 6, 16, 2
    q = mx.np.array(rng.standard_normal((b, t, e)), dtype='float32')
    k = mx.np.array(rng.standard_normal((b, s, e)), dtype='float32')
    v = mx.np.array(rng.standard_normal((b, s, e)), dtype='float32')
    out_flash = mx.npx.multi_head_attention(q, k, v, h, causal=True)
    full = mx.np.ones((b, 1, t, s), dtype='bool')
    out_masked = mx.npx.multi_head_attention(q, k, v, h, causal=True,
                                             mask=full)
    onp.testing.assert_allclose(out_flash.asnumpy(), out_masked.asnumpy(),
                                rtol=1e-5, atol=1e-5)


def test_symbolblock_from_traced_symbol_with_aux():
    """In-memory SymbolBlock(sym, inputs) must resolve hoisted constants
    (code-review regression)."""
    from mxnet_tpu.gluon import SymbolBlock, nn

    class PosBlock(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.table = mx.np.random.uniform(size=(1, 32, 16))

        def forward(self, x):
            return x + self.table

    net = PosBlock()
    x = mx.np.ones((2, 32, 16))
    ref = net(x).asnumpy()
    sym = net._trace_symbol(x)
    blk = SymbolBlock(sym, 'data')
    onp.testing.assert_allclose(blk(x).asnumpy(), ref, rtol=1e-6)


def test_symbol_unique_positional_flags():
    x = mx.sym.var('x')
    u = mx.sym.np.unique(x, True)
    assert u.num_outputs == 2


def test_flash_causal_more_queries_than_keys_matches_reference():
    """Code-review regression: T > S causal must agree with the XLA path."""
    import jax.numpy as jnp
    rng = onp.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 1, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 2, 8)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=2, block_k=2)
    ref = _reference_attention(q.reshape(-1, 4, 8), k.reshape(-1, 2, 8),
                               v.reshape(-1, 2, 8), 8 ** -0.5,
                               True).reshape(q.shape)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)


def test_mha_dropout_requires_key_and_masks():
    rng = onp.random.default_rng(8)
    x = mx.np.array(rng.standard_normal((2, 8, 16)), dtype='float32')
    with pytest.raises(ValueError, match='key'):
        mx.npx.multi_head_attention(x, x, x, 4, dropout_p=0.5)
    import jax
    out = mx.npx.multi_head_attention(x, x, x, 4, dropout_p=0.5,
                                      key=jax.random.PRNGKey(0))
    assert out.shape == (2, 8, 16)
    base = mx.npx.multi_head_attention(x, x, x, 4)
    assert abs(out.asnumpy() - base.asnumpy()).max() > 1e-4  # masked


def test_bert_classifier_requires_pooler():
    with pytest.raises(ValueError, match='use_pooler'):
        bert.BERTModel(vocab_size=10, units=8, hidden_size=16,
                       num_layers=1, num_heads=2, use_pooler=False,
                       use_classifier=True)


def test_bert_hf_weight_import_matches_transformers():
    """Cross-implementation parity for BERT: logits from an HF
    BertForPreTraining's random weights must match ours."""
    torch = pytest.importorskip('torch')
    transformers = pytest.importorskip('transformers')

    hf_cfg = transformers.BertConfig(
        vocab_size=120, hidden_size=48, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=96,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, hidden_act='gelu',
        attn_implementation='eager')
    torch.manual_seed(0)
    hf = transformers.BertForPreTraining(hf_cfg).eval()

    net = bert.BERTModel(vocab_size=120, units=48, hidden_size=96,
                         num_layers=2, num_heads=4, max_length=32,
                         dropout=0.0)
    net.initialize()
    toks = onp.array([[2, 45, 99, 7, 3]], 'f')
    segs = onp.array([[0, 0, 1, 1, 1]], 'f')
    net(mx.np.array(toks), mx.np.array(segs))
    bert.load_hf_state_dict(net, hf.state_dict())

    seq, pooled, mlm, nsp = net(mx.np.array(toks), mx.np.array(segs))
    with torch.no_grad():
        out = hf(torch.tensor(toks.astype('i8')),
                 token_type_ids=torch.tensor(segs.astype('i8')))
    err_mlm = onp.abs(mlm.asnumpy() -
                     out.prediction_logits.numpy()).max()
    err_nsp = onp.abs(nsp.asnumpy() -
                     out.seq_relationship_logits.numpy()).max()
    assert err_mlm < 5e-3, f'MLM logit mismatch {err_mlm}'
    assert err_nsp < 5e-3, f'NSP logit mismatch {err_nsp}'


def test_sliding_window_attention_matches_dense_band():
    """sldwin ops equal full attention under an explicit band mask."""
    B, S, H, D, w = 2, 8, 2, 4, 2
    rng = onp.random.default_rng(0)
    q = mx.np.array(rng.standard_normal((B, S, H, D), dtype='f'))
    k = mx.np.array(rng.standard_normal((B, S, H, D), dtype='f'))
    v = mx.np.array(rng.standard_normal((B, S, H, D), dtype='f'))

    score = mx.npx.sldwin_atten_score(q, k, 1, w)
    probs = mx.npx.softmax(score * (D ** -0.5), axis=-1)
    out = mx.npx.sldwin_atten_context(probs, v, 1, w)
    assert out.shape == (B, S, H, D)

    # dense reference with the same band
    qn, kn, vn = (t.asnumpy() for t in (q, k, v))
    s = onp.einsum('bqhd,bkhd->bhqk', qn, kn) * (D ** -0.5)
    i = onp.arange(S)[:, None]
    j = onp.arange(S)[None, :]
    band = (onp.abs(i - j) <= w)[None, None]
    s = onp.where(band, s, -1e30)
    e = onp.exp(s - s.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    want = onp.einsum('bhqk,bkhd->bqhd', p, vn)
    onp.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)

    # mask_like: band ∩ valid_length
    m = mx.npx.sldwin_atten_mask_like(mx.np.array(s.astype('f')), 1,
                                      mx.np.array(onp.array([8, 5], 'f')),
                                      w)
    mn = m.asnumpy()
    assert mn[0].astype(bool).sum() == band[0, 0].sum() * 2  # both heads
    assert not mn[1, 0, 6:, :].any()          # beyond valid_length 5


def test_flash_stats_merge_equals_single_shot():
    """flash_attention_stats blocks merged with _merge_stats must equal
    full softmax attention — the ring-attention correctness core."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.flash_attention import (
        flash_attention_stats, _reference_attention)
    from mxnet_tpu.parallel.ring_attention import _merge_stats

    rng = onp.random.default_rng(0)
    bh, t, d = 2, 8, 4
    q = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, 2 * t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, 2 * t, d)), jnp.float32)
    scale = d ** -0.5

    # two key blocks computed independently, then merged
    acc1, m1, l1 = flash_attention_stats(q, k[:, :t], v[:, :t], scale,
                                         interpret=True)
    acc2, m2, l2 = flash_attention_stats(q, k[:, t:], v[:, t:], scale,
                                         interpret=True)
    m0 = jnp.full((bh, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bh, t), jnp.float32)
    o0 = jnp.zeros((bh, t, d), jnp.float32)
    m, l, o = _merge_stats(m0, l0, o0, acc1, m1, l1)
    m, l, o = _merge_stats(m, l, o, acc2, m2, l2)
    out = o / jnp.maximum(l[..., None], 1e-30)
    ref = _reference_attention(q, k, v, scale, causal=False)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)


def test_flash_stats_causal_diagonal():
    """Diagonal-block causal stats (q_pos >= k_pos, same shard) match the
    masked reference."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.flash_attention import (
        flash_attention_stats, _reference_attention)

    rng = onp.random.default_rng(1)
    bh, t, d = 2, 8, 4
    q = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    scale = d ** -0.5
    acc, m, l = flash_attention_stats(q, k, v, scale, causal=True,
                                      interpret=True)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    ref = _reference_attention(q, k, v, scale, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-5, atol=2e-5)
