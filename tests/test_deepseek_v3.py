"""The deepseek_v3 family at tiny sizes on the CPU, seeded weights: the
zoo's ``DeepseekV3ForCausalLM`` against the plain reference
(``chipbench/reference/deepseek_v3.py``, float32 at ``highest``), the
sparse-expert Block's promises (the shares add up, no token is dropped),
hybridized against imperative, ``multi_head_attention`` with a value head
of another width, and the ``grad_req='null'`` leaf that a hybridized
block no longer donates.

Tolerances: both sides are float32 on the CPU, where a product is a
float32 product whatever the precision asked for; they differ in the
order of their sums (a flash-style recompute against a softmax, a sorted
grouped product against a dense loop over the experts), which is a few
ulps of the largest term: rel 1e-4 with an abs of 1e-6 for the elements
that nearly cancel.
"""

import os
import sys
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, npx
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.deepseek_v3 import (DeepseekV3Config,
                                                   DeepseekV3ForCausalLM)
from mxnet_tpu.gluon.model_zoo.llama import LlamaMLP
from mxnet_tpu.ndarray.ndarray import NDArray
from chipbench.families import deepseek_v3 as family
from chipbench.reference import deepseek_v3 as ref

RTOL, ATOL = 1e-4, 1e-6

CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=2, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    n_routed_experts=4, router_width=8, first_expert=2,
    n_shared_experts=2, num_experts_per_tok=2, first_k_dense_replace=1,
    moe_layer_freq=1, norm_topk_prob=True, scoring_func='sigmoid',
    routed_scaling_factor=2.448, rms_norm_eps=1e-6, rope_theta=1000000,
    vocab_size=256, initializer_range=0.05)
SEED = 11


def close(got, want, err_msg=''):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL, err_msg=err_msg)


def zoo_net(cfg=CFG, seed=SEED, biased=True):
    """The zoo's net with the reference's weights from the seed; the
    routers' biases drawn too, so that they change a choice."""
    net = DeepseekV3ForCausalLM(DeepseekV3Config(**cfg))
    net.initialize(mx.initializer.Zero())
    net(mx.np.zeros((1, 4), dtype='int32'))        # deferred shapes
    weights = ref.init_params(cfg, seed)
    if biased:
        rng = np.random.default_rng(seed)
        weights = {k: jnp.asarray(rng.normal(0, 0.05, a.shape), a.dtype)
                   if ref.frozen(k) else a for k, a in weights.items()}
    params = net.collect_params()
    by_name = family.by_program_name(weights)
    assert set(by_name) == set(params)
    for name, p in params.items():
        p.set_data(NDArray(by_name[name]))
    return net, weights


def rows(batch=2, positions=8, seed=3):
    return np.random.default_rng(seed).integers(
        0, CFG['vocab_size'], (batch, positions + 1)).astype(np.int32)


def zoo_loss(net, tokens):
    out = net(mx.np.array(tokens[:, :-1]))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()(
        out.reshape(-1, out.shape[-1]),
        mx.np.array(tokens[:, 1:].reshape(-1).astype('float32'))).mean()
    return out, loss


@pytest.fixture(scope='module')
def trained_once():
    """(net, weights, tokens, logits, loss) after one recorded forward
    and backward, imperative."""
    net, weights = zoo_net()
    tokens = rows()
    with autograd.record():
        out, loss = zoo_loss(net, tokens)
    loss.backward()
    return net, weights, tokens, out.asnumpy(), float(loss.asnumpy())


def test_logits_and_loss_agree_with_the_reference(trained_once):
    _, weights, tokens, logits, loss = trained_once
    with jax.default_matmul_precision('highest'):
        want = ref.logits_of(weights, CFG, jnp.asarray(tokens[:, :-1]))
        want_loss = ref.loss_fn(*ref.split(weights), CFG,
                                jnp.asarray(tokens))
    close(logits, want)
    assert loss == pytest.approx(float(want_loss), rel=RTOL)


def test_every_leafs_gradient_agrees_with_the_reference(trained_once):
    net, weights, tokens, _, _ = trained_once
    moved, held = ref.split(weights)
    with jax.default_matmul_precision('highest'):
        want = family.by_program_name(jax.grad(ref.loss_fn)(
            moved, held, CFG, jnp.asarray(tokens)))
    params = net.collect_params()
    frozen = {n for n, p in params.items() if p.grad_req == 'null'}
    assert frozen == {family.program_name(k) for k in held} and frozen
    assert set(want) == set(params) - frozen
    for name, w in want.items():
        assert np.abs(np.asarray(w)).max() > 0, name
        close(params[name].grad().asnumpy(), w, err_msg=name)


def test_hybridized_equals_imperative(trained_once):
    net, _, tokens, logits, loss = trained_once
    params = net.collect_params()
    grads = {n: p.grad().asnumpy().copy() for n, p in params.items()
             if p.grad_req != 'null'}
    net.hybridize(static_alloc=True)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter('error')   # 'donated buffers not usable'
            with autograd.record():
                out, again = zoo_loss(net, tokens)
            again.backward()
        close(out.asnumpy(), logits)
        assert float(again.asnumpy()) == pytest.approx(loss, rel=1e-6)
        for name, g in grads.items():
            close(params[name].grad().asnumpy(), g, err_msg=name)
    finally:
        net.hybridize(False)


def test_it_trains_through_the_trainer():
    net, _ = zoo_net()
    net.hybridize(static_alloc=True)
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-2}, kvstore=None)
    tokens = rows()
    bias = net.model.layers[1].mlp.router_bias.data().asnumpy().copy()
    losses = []
    for _ in range(8):
        with autograd.record():
            _, loss = zoo_loss(net, tokens)
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < 0.7 * losses[0], losses
    # nothing moves the correction bias
    assert np.array_equal(
        net.model.layers[1].mlp.router_bias.data().asnumpy(), bias)


# ------------------------------------------------------- the sparse Block
UNITS, EXPERTS, PER_TOKEN, SIZE, SHARED = 32, 8, 3, 16, 24


def layer_weights(seed=5, scale=0.3):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, scale, shape),
                                      jnp.float32)
    return {'router_w': draw(EXPERTS, UNITS), 'router_b': draw(EXPERTS),
            'experts_gate': draw(EXPERTS, SIZE, UNITS),
            'experts_up': draw(EXPERTS, SIZE, UNITS),
            'experts_down': draw(EXPERTS, UNITS, SIZE),
            'shared_gate': draw(SHARED, UNITS),
            'shared_up': draw(SHARED, UNITS),
            'shared_down': draw(UNITS, SHARED)}


LAYER_CFG = dict(n_routed_experts=EXPERTS, router_width=EXPERTS,
                 num_experts_per_tok=PER_TOKEN, routed_scaling_factor=1.7,
                 norm_topk_prob=True, scoring_func='sigmoid')


def share(lp, held, shared_size=0):
    """The Block that holds the experts ``held`` of the layer ``lp``."""
    shared = LlamaMLP(types.SimpleNamespace(
        units=UNITS, hidden_size=shared_size)) if shared_size else None
    blk = nn.SparseExperts(UNITS, EXPERTS, PER_TOKEN, SIZE, shared=shared,
                           held=held, routed_scaling_factor=1.7)
    blk.initialize()
    cut = slice(held.start, held.stop)
    blk.router.weight.set_data(NDArray(lp['router_w']))
    blk.router_bias.set_data(NDArray(lp['router_b']))
    for name in ref.STACKED:
        getattr(blk, name).set_data(NDArray(lp[name][cut]))
    if shared_size:
        for tail in ('gate', 'up', 'down'):
            getattr(blk.shared, f'{tail}_proj').weight.set_data(
                NDArray(lp[f'shared_{tail}']))
    return blk


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips hold an expert each; their routed parts, with the
    shared expert counted once, are the whole layer as the uncut
    reference gives it."""
    lp = layer_weights()
    x = np.random.default_rng(0).normal(0, 1, (2, 10, UNITS)) \
        .astype('float32')
    with jax.default_matmul_precision('highest'):
        want = ref.sparse_ffn(lp, LAYER_CFG, jnp.asarray(x))
    parts = [share(lp, range(j, j + 1))(mx.np.array(x)).asnumpy()
             for j in range(EXPERTS)]
    assert sum(np.abs(p).max() > 0 for p in parts) == EXPERTS
    whole = share(lp, range(EXPERTS), shared_size=SHARED)
    shared = whole.shared(mx.np.array(x)).asnumpy()
    close(sum(parts) + shared, want)
    # and the Block that holds them all is the layer
    close(whole(mx.np.array(x)).asnumpy(), want)
    # a share is what the reference gives for the same share
    cut = dict(LAYER_CFG, n_routed_experts=3, first_expert=4)
    cut_lp = {k: a[4:7] if k in ref.STACKED else a for k, a in lp.items()}
    with jax.default_matmul_precision('highest'):
        want_cut = ref.sparse_ffn(cut_lp, cut, jnp.asarray(x))
    close(share(lp, range(4, 7), shared_size=SHARED)(
        mx.np.array(x)).asnumpy(), want_cut)


@pytest.mark.parametrize('favoured, held', [
    ((2, 3, 4), range(2, 5)),     # every pair of every token is held
    ((1, 3, 6), range(3, 4)),     # one held expert takes every token
    ((0, 1, 2), range(5, 8)),     # nothing falls here
])
def test_no_token_is_dropped_when_all_choose_the_same(favoured, held):
    lp = layer_weights(seed=8)
    bias = np.full(EXPERTS, -4.0, 'float32')
    bias[list(favoured)] = 4.0         # sigmoid scores lie in (0, 1)
    lp['router_b'] = jnp.asarray(bias)
    x = np.random.default_rng(1).normal(0, 1, (40, UNITS)).astype('float32')
    chosen, _ = mx.ops.experts.route(
        jnp.asarray(x), lp['router_w'], lp['router_b'], PER_TOKEN,
        'sigmoid', True, 1.7)
    assert set(np.asarray(chosen).ravel()) == set(favoured)
    cut = dict(LAYER_CFG, n_routed_experts=len(held),
               first_expert=held.start)
    cut_lp = {k: a[held.start:held.stop] if k in ref.STACKED else a
              for k, a in lp.items()}
    with jax.default_matmul_precision('highest'):
        want = ref.sparse_ffn(cut_lp, cut, jnp.asarray(x))
    got = share(lp, held, shared_size=SHARED)(mx.np.array(x)).asnumpy()
    close(got, want)


def _sparse_experts_as_it_was(x, router_weight, router_bias, experts_gate,
                              experts_up, experts_down, experts_per_token,
                              first_expert, routed_scaling_factor):
    """ops/experts.py's ``sparse_experts`` forward before its work
    followed the live count (PR 37): every gather, mask and grouped
    product over the whole sorted buffer, a row for every pair."""
    ex = mx.ops.experts
    shape = x.shape
    units = shape[-1]
    held = experts_gate.shape[0]
    k = experts_per_token
    tokens = x.reshape(-1, units)
    chosen, weights = ex.route(
        tokens, router_weight, router_bias, k, 'sigmoid', True,
        routed_scaling_factor)
    local = chosen.reshape(-1) - first_expert
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    sizes = (local[:, None] == jnp.arange(held, dtype=jnp.int32)
             ).sum(0, dtype=jnp.int32)
    n_live = sizes.sum()
    rows = ex._live_rows(jnp.repeat(tokens, k, axis=0)[order], n_live)
    hidden = ex._gated(
        ex._grouped(rows, experts_gate, sizes),
        ex._grouped(rows, experts_up, sizes),
        weights.reshape(-1, 1).astype(x.dtype)[order])
    out = ex._live_rows(ex._grouped(hidden, experts_down, sizes), n_live)
    out = out[inverse].reshape(-1, k, units).sum(1)
    return out.reshape(shape).astype(x.dtype)


def test_swiglu_experts_are_the_program_they_were():
    """The default activation over a prefix of the sorted buffer gives
    the bits the whole buffer gave, on a partly held layer whose ladder
    has rungs to choose from; and the un-gated Block has no gate to
    hold."""
    lp = layer_weights(seed=6)
    held = slice(2, 4)
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (4, 256, UNITS)),
                    jnp.float32)
    args = (x, lp['router_w'], lp['router_b'], lp['experts_gate'][held],
            lp['experts_up'][held], lp['experts_down'][held])
    assert len(mx.ops.experts.prefix_ladder(
        4 * 256 * PER_TOKEN, 2, EXPERTS)) > 1
    new = lambda *a: mx.ops.experts.sparse_experts(
        *a, experts_per_token=PER_TOKEN, first_expert=2,
        routed_scaling_factor=1.7)
    old = lambda *a: _sparse_experts_as_it_was(*a, PER_TOKEN, 2, 1.7)
    want = np.asarray(old(*args))
    assert np.abs(want).max() > 0
    assert np.array_equal(np.asarray(new(*args)), want)
    assert np.array_equal(np.asarray(jax.jit(new)(*args)),
                          np.asarray(jax.jit(old)(*args)))
    # and through the Block, as the zoo's decoder calls it
    got = share(lp, range(2, 4))(mx.np.array(x)).asnumpy()
    assert np.array_equal(got, want)
    swiglu = nn.SparseExperts(UNITS, EXPERTS, PER_TOKEN, SIZE)
    relu2 = nn.SparseExperts(UNITS, EXPERTS, PER_TOKEN, SIZE,
                             activation='relu2')
    assert 'experts_gate' in swiglu.collect_params()
    assert set(swiglu.collect_params()) - set(relu2.collect_params()) == \
        {'experts_gate'}
    with pytest.raises(ValueError, match='a gate goes with swiglu'):
        mx.ops.experts.sparse_experts(*args, activation='relu2')


def test_a_held_range_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match='consecutive experts'):
        nn.SparseExperts(UNITS, EXPERTS, PER_TOKEN, SIZE,
                         held=range(6, 10))
    with pytest.raises(ValueError, match='consecutive experts'):
        nn.SparseExperts(UNITS, EXPERTS, PER_TOKEN, SIZE,
                         held=range(0, 8, 2))


# --------------------------------------------- attention, two head widths
def plain_attention(q, k, v, heads, causal, mask=None, scale=None):
    b, t, _ = q.shape
    qh, kh, vh = (a.reshape(b, a.shape[1], heads, -1) for a in (q, k, v))
    scale = qh.shape[-1] ** -0.5 if scale is None else scale
    s = jnp.einsum('bqnd,bknd->bnqk', qh, kh) * scale
    keep = jnp.ones((t, kh.shape[1]), bool)
    if causal:
        keep = jnp.tril(keep)
    if mask is not None:
        keep = keep & mask
    s = jnp.where(keep, s, -1e30)
    out = jnp.einsum('bnqk,bknd->bqnd', jax.nn.softmax(s, -1), vh)
    return out.reshape(b, t, -1)


@pytest.mark.parametrize('qk, vd, masked', [
    (24, 16, False), (24, 16, True), (16, 24, False), (16, 16, False)])
def test_attention_with_a_value_head_of_another_width(qk, vd, masked):
    heads, b, t = 4, 2, 16
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.normal(0, 1, (b, t, heads * qk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(0, 1, (b, t, heads * vd)), jnp.float32)
    mask = jnp.asarray(rng.random((b, 1, t, t)) > 0.3) | jnp.eye(t, dtype=bool) \
        if masked else None
    scale = 0.17
    op = mx.ops.contrib.multi_head_attention
    got = lambda q, k, v: op(q, k, v, heads, mask=mask, causal=True,
                             sm_scale=scale)
    want = lambda q, k, v: plain_attention(q, k, v, heads, True, mask,
                                           scale)
    assert got(q, k, v).shape == (b, t, heads * vd)
    close(got(q, k, v), want(q, k, v))
    g = jax.grad(lambda *a: (got(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    w = jax.grad(lambda *a: (want(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, e in zip(g, w):
        close(a, e)
    # the default scale is that of the query's own width
    close(op(q, k, v, heads, causal=True),
          plain_attention(q, k, v, heads, True))


def _attention_as_it_was(q, k, v, num_heads, mask, causal):
    """ops/contrib.py's ``_attention`` before this family (PR 30), the
    two branches a BERT or Llama call takes."""
    b, sq, e = q.shape
    hd = e // num_heads
    qh = q.reshape(b, sq, num_heads, hd)
    kh = k.reshape(b, k.shape[1], num_heads, hd)
    vh = v.reshape(b, v.shape[1], num_heads, hd)
    if mask is None:
        from mxnet_tpu.ops.pallas.flash_attention import flash_attention
        out = flash_attention(qh.transpose(0, 2, 1, 3),
                              kh.transpose(0, 2, 1, 3),
                              vh.transpose(0, 2, 1, 3), causal=causal)
        return out.transpose(0, 2, 1, 3).reshape(b, sq, e)
    if causal:
        sk = k.shape[1]
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)[None, None]
        mask = jnp.logical_and(mask, tri)
    out = jax.nn.dot_product_attention(qh, kh, vh, mask=mask)
    return out.reshape(b, sq, e)


@pytest.mark.parametrize('masked', [False, True])
@pytest.mark.parametrize('causal', [False, True])
def test_a_bert_shaped_call_is_the_program_it_was(masked, causal):
    """One head width and no scale given: the same jaxpr, forward and
    backward, and so the same bits."""
    heads, b, t, e = 4, 2, 16, 64
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (b, t, e)), jnp.float32)
               for _ in range(3))
    mask = (jnp.arange(t)[None, :] < jnp.asarray([9, 16])[:, None]) \
        [:, None, None, :] if masked else None
    new = lambda q, k, v: mx.ops.contrib._attention(
        q, k, v, heads, mask, 0.0, causal, None)
    old = lambda q, k, v: _attention_as_it_was(q, k, v, heads, mask, causal)
    for fn in (lambda f: f, lambda f: jax.grad(
            lambda *a: (f(*a) ** 2).sum(), (0, 1, 2))):
        assert str(jax.make_jaxpr(fn(new))(q, k, v)) == \
            str(jax.make_jaxpr(fn(old))(q, k, v))
    assert np.array_equal(np.asarray(new(q, k, v)),
                          np.asarray(old(q, k, v)))
    # and through the frontend, as the zoo's BERT calls it
    out = npx.multi_head_attention(mx.np.array(q), mx.np.array(k),
                                   mx.np.array(v), heads, causal=causal,
                                   mask=None if mask is None
                                   else mx.np.array(mask))
    assert np.array_equal(out.asnumpy(), np.asarray(old(q, k, v)))


# ------------------------------------- a leaf the optimizer never moves
class _Frozen(gluon.nn.HybridBlock):
    """A leaf the forward only reads, beside BatchNorm's running
    statistics, which it writes."""

    def __init__(self):
        super().__init__()
        self.dense = nn.Dense(8, in_units=8)
        self.norm = nn.BatchNorm(in_channels=8)
        self.offset = gluon.Parameter('offset', shape=(8,), init='ones',
                                      grad_req='null')

    def forward(self, x):
        return self.norm(self.dense(x)) + self.offset.data()


def test_a_leaf_that_is_only_read_is_not_donated():
    net = _Frozen()
    net.initialize()
    net.hybridize(static_alloc=True)
    x = mx.np.array(np.random.default_rng(0).normal(0, 1, (16, 8))
                    .astype('float32'))
    for step in range(3):
        offset = net.offset.data()._data
        mean = net.norm.running_mean.data()._data
        before = np.asarray(mean).copy()
        with warnings.catch_warnings():
            warnings.simplefilter('error')   # 'donated buffers not usable'
            with autograd.record():
                loss = (net(x) ** 2).mean()
            loss.backward()
        # the running statistics are written over in place, as before
        # (an entry's first call, which traces, leaves its operands be)
        assert mean.is_deleted() == (step > 0)
        assert not np.array_equal(
            net.norm.running_mean.data().asnumpy(), before)
        # the leaf that is only read keeps its buffer
        assert not offset.is_deleted()
        assert net.offset.data()._data is offset
    assert net.dense.weight.grad().asnumpy().any()


@pytest.mark.parametrize('toy', [True, False], ids=['toy_lm', 'deepseek_v3'])
def test_compiling_a_family_with_a_frozen_leaf_warns_of_no_donation(toy):
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'chipbench')
    if here not in sys.path:
        sys.path.insert(0, here)
    import chipbench_tiny
    cell = chipbench_tiny.TOY_CELLS[0] if toy else next(
        c for c in chipbench_tiny.CELLS
        if chipbench_tiny.load_cell(c)[1]['family'] == 'deepseek_v3')
    job = chipbench_tiny.tiny_job(cell, 3, mx.cpu(0))
    assert any(p.grad_req == 'null'
               for p in job.net.collect_params().values())
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        dev = job.upload(job.pool[0])
        with autograd.record():
            loss = job.loss(job.forward(dev), dev)
        loss.backward()
        job.trainer.step(1)
    assert np.isfinite(float(loss.asnumpy()))
