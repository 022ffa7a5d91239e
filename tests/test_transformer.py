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
