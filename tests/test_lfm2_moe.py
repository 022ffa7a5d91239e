"""The lfm2_moe family at tiny sizes on the CPU, seeded weights: the gated
short convolution (``npx.short_conv``) against a loop over positions,
``ssm_conv`` as it was before the two shared their taps, the rotate-half
rotary against its written-out formula, the zoo's ``Lfm2MoeForCausalLM``
against ``chipbench/reference/lfm2_moe.py`` (float32 at ``highest``), the
tied head's gradient, the eight shares of a sparse layer adding up, and
the scopes a profile's reader finds.

Tolerances: both sides are float32 on the CPU, where a product is a
float32 product whatever the precision asked for; they differ in the
order of their sums (shifted arrays against a rolling window, a sorted
grouped product against a dense loop over the experts, the flash path
against a dense softmax), a few ulps of the largest term: rel 1e-4, with
an abs of 1e-6 of the array's largest element (or of 1) for the elements
that nearly cancel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.lfm2_moe import (Lfm2MoeConfig,
                                                Lfm2MoeForCausalLM)
from mxnet_tpu.gluon.model_zoo.llama import _rope
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import ssm
from chipbench.families import lfm2_moe as family
from chipbench.reference import lfm2_moe as ref

RTOL, ATOL = 1e-4, 1e-6

# every kind of layer: a conv layer with the dense FFN, attention and
# conv with experts; experts 2-5 of 8 held
CFG = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    layer_types=['conv', 'full_attention', 'conv'], num_dense_layers=1,
    conv_L_cache=3, num_experts=4, router_width=8, first_expert=2,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1.0,
    norm_eps=1e-5, rope_parameters={'rope_theta': 1e6,
                                    'rope_type': 'default'},
    vocab_size=64, initializer_range=0.05)
SEED = 11


def close(got, want, err_msg='', rtol=RTOL):
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=rtol, err_msg=err_msg,
        atol=ATOL * max(1.0, float(np.abs(want).max())))


def draw(rng, *shape):
    return jnp.asarray(rng.normal(0, 1, shape), jnp.float32)


# ---------------------------------------------------- the short convolution
def conv_by_positions(b, c, x, w):
    """``c_t * sum_k w[:, k] (b x)_{t - K + 1 + k}``, a position and a
    tap at a time, nothing before a row's start."""
    t, taps = x.shape[1], w.shape[1]
    v = b * x
    out = []
    for i in range(t):
        s = jnp.zeros_like(v[:, 0])
        for k in range(taps):
            j = i - taps + 1 + k
            if j >= 0:
                s = s + w[:, k] * v[:, j]
        out.append(c[:, i] * s)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize('t', [1, 2, 3, 9])
def test_short_conv_is_the_sum_over_positions(t):
    """Forward and every gradient, at the row's start and with rows
    shorter than the taps."""
    rng = np.random.default_rng(t)
    b, c, x = (draw(rng, 2, t, 5) for _ in range(3))
    w = draw(rng, 5, 3)
    close(ssm.short_conv(b, c, x, w), conv_by_positions(b, c, x, w))
    loss = lambda fn: lambda *a: (fn(*a) * jnp.arange(1.0, t + 1)[:, None]
                                  ).sum()
    got = jax.grad(loss(ssm.short_conv), argnums=(0, 1, 2, 3))(b, c, x, w)
    want = jax.grad(loss(conv_by_positions), argnums=(0, 1, 2, 3))(
        b, c, x, w)
    for g, v in zip(got, want):
        close(g, v)


def test_the_first_output_reads_the_first_position_alone():
    b = c = jnp.ones((1, 4, 2))
    x = jnp.asarray([[[1.0, 1.0], [10.0, 10.0], [100.0, 100.0],
                      [1000.0, 1000.0]]])
    w = jnp.asarray([[1.0, 2.0, 3.0]] * 2)      # tap 2 on the position
    np.testing.assert_array_equal(ssm.short_conv(b, c, x, w)[0, :, 0],
                                  [3.0, 32.0, 321.0, 3210.0])


def _ssm_conv_before(x, weight, bias=None):
    """``ops/ssm.py``'s ``ssm_conv`` as it was before ``causal_taps``."""
    taps, t = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, k:k + t] * weight[:, k] for k in range(taps))
    if bias is not None:
        out = out + bias
    return jax.nn.silu(out).astype(x.dtype)


@pytest.mark.parametrize('bias', [True, False])
def test_ssm_conv_is_what_it_was(bias):
    """Bit for bit, forward and gradients, jitted as the ops are."""
    rng = np.random.default_rng(2)
    x, w, bb = draw(rng, 2, 7, 6), draw(rng, 6, 4), draw(rng, 6)
    args = (x, w, bb if bias else None)
    loss = lambda fn: lambda *a: (fn(*a) ** 2).sum()
    for fn in (lambda f: f, lambda f: jax.grad(loss(f), argnums=(0, 1))):
        got = jax.tree.leaves(jax.jit(fn(ssm.ssm_conv))(*args))
        want = jax.tree.leaves(jax.jit(fn(_ssm_conv_before))(*args))
        for g, v in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(v))


# ---------------------------------------------------------------- rotary
def _rope_interleaved_before(x, theta):
    """``llama._rope`` as it was before ``rotate_half``."""
    _, s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[None, :, None] * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def test_rotate_half_is_the_written_out_formula():
    x = draw(np.random.default_rng(4), 2, 9, 3, 8)
    close(_rope(x, 1e6, rotate_half=True), ref.rotary(x, 1e6))
    # channel j turns with j + d/2: at position 1 with theta 1, channel 0
    # by one radian against channel 4
    one = jnp.zeros((1, 2, 1, 8)).at[0, 1, 0, 0].set(1.0)
    got = np.asarray(_rope(one, 1.0, rotate_half=True))[0, 1, 0]
    close(got[[0, 4]], [np.cos(1.0), np.sin(1.0)])
    assert not np.allclose(got[1], np.sin(1.0))


def test_the_interleaved_form_is_what_it_was():
    x = draw(np.random.default_rng(5), 2, 9, 3, 8)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda a: _rope(a, 1e4))(x)),
        np.asarray(jax.jit(lambda a: _rope_interleaved_before(a, 1e4))(x)))


# ------------------------------------------------------- the zoo's model
def zoo_net(cfg=CFG, seed=SEED):
    """The zoo's net with the reference's weights from the seed; the
    routers' biases drawn too, so that they change a choice."""
    net = Lfm2MoeForCausalLM(Lfm2MoeConfig(**cfg))
    net.initialize(mx.initializer.Zero())
    rng = np.random.default_rng(seed)
    weights = {k: jnp.asarray(rng.normal(0, 0.05, a.shape), a.dtype)
               if ref.frozen(k) else a
               for k, a in ref.init_params(cfg, seed).items()}
    params = net.collect_params()
    by_name = family.by_program_name(weights)
    assert set(by_name) == set(params)
    for name, p in params.items():
        assert p.shape == by_name[name].shape, name
        p.set_data(NDArray(by_name[name]))
    return net, weights


def rows(batch=2, positions=10, seed=3):
    return np.random.default_rng(seed).integers(
        0, CFG['vocab_size'], (batch, positions + 1)).astype(np.int32)


def zoo_loss(net, tokens):
    out = net(mx.np.array(tokens[:, :-1]))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()(
        out.reshape(-1, out.shape[-1]),
        mx.np.array(tokens[:, 1:].reshape(-1).astype('float32'))).mean()
    return out, loss


def test_the_zoo_model_agrees_with_the_reference():
    """Logits, loss and the gradient of every leaf the optimizer moves,
    by name; hybridized, as it trains. The tied head's gradient is the
    sum of its two uses: the embedding's rows and the logits' matrix."""
    net, weights = zoo_net()
    net.hybridize(static_alloc=True)
    tokens = rows()
    with autograd.record():
        out, loss = zoo_loss(net, tokens)
    loss.backward()
    moved, held = ref.split(weights)
    rest = {k: a for k, a in moved.items() if k != 'embed'}

    @jax.jit
    def reference(rest, looked_up, head):
        """The reference's loss and logits, its two uses of the embedding
        taken apart so that each has a gradient of its own."""
        logits = ref.hidden_of({**rest, **held, 'embed': looked_up}, CFG,
                               jnp.asarray(tokens[:, :-1])) @ head.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, jnp.asarray(tokens[:, 1:, None]), axis=-1).mean(), logits

    with jax.default_matmul_precision('highest'):
        (want_loss, want), (grads, as_rows, as_head) = jax.value_and_grad(
            reference, argnums=(0, 1, 2), has_aux=True)(
            rest, moved['embed'], moved['embed'])
    grads['embed'] = as_rows + as_head
    close(out.asnumpy(), want)
    assert float(loss.asnumpy()) == pytest.approx(float(want_loss),
                                                  rel=RTOL)
    grads = family.by_program_name(grads)
    params = net.collect_params()
    frozen = {n for n, p in params.items() if p.grad_req == 'null'}
    assert frozen == {family.program_name(k) for k in held} and frozen
    assert set(grads) == set(params) - frozen
    for name, w in grads.items():
        assert np.abs(np.asarray(w)).max() > 0, name
        close(params[name].grad().asnumpy(), w, err_msg=name)
    # both uses carry a part of the tied leaf's gradient
    whole = np.abs(np.asarray(as_rows + as_head)).max()
    for part in (as_rows, as_head):
        assert np.abs(np.asarray(part)).max() > 0.05 * whole


def test_it_trains_through_the_trainer_with_the_fused_update():
    net, _ = zoo_net()
    net.hybridize(static_alloc=True, remat=True)
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-2}, kvstore=None)
    tokens = rows()
    conv = net.model.layers[0].conv.conv.weight
    taps = conv.data().asnumpy().copy()
    losses = []
    for _ in range(6):
        with autograd.record():
            _, loss = zoo_loss(net, tokens)
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < 0.7 * losses[0], losses
    assert not trainer._fused_fallback_taken
    assert not np.array_equal(conv.data().asnumpy(), taps)


def test_the_zoos_own_initialisers():
    net = Lfm2MoeForCausalLM(Lfm2MoeConfig(**CFG))
    net.initialize()
    taps = net.model.layers[0].conv.conv.weight.data().asnumpy()
    assert 0.4 < np.abs(taps).max() <= 1 / np.sqrt(3)
    assert net.model.layers[0].conv.conv.bias is None
    attn = net.model.layers[1].self_attn
    assert np.array_equal(attn.q_layernorm.weight.data().asnumpy(),
                          np.ones(8))


@pytest.mark.parametrize('key, value, what', [
    ('conv_bias', True, 'a bias on the convolution'),
    ('tie_word_embeddings', False, 'an untied head'),
    ('layer_types', ['conv', 'sliding_attention', 'conv'], 'layer type'),
    ('layer_types', ['conv', 'conv'], '2 layer_types for 3 layers'),
    ('rope_parameters', {'rope_theta': 1e6, 'rope_type': 'yarn'},
     "rope_type 'yarn'")])
def test_a_config_the_zoo_cannot_compute_is_refused(key, value, what):
    with pytest.raises(NotImplementedError, match=what):
        Lfm2MoeConfig(**dict(CFG, **{key: value}))


# ------------------------------------------------------- the eight shares
UNITS, EXPERTS, PER_TOKEN, SIZE, SHARES = 32, 16, 4, 8, 8
LAYER_CFG = dict(num_experts=EXPERTS, router_width=EXPERTS,
                 num_experts_per_tok=PER_TOKEN, routed_scaling_factor=1.0,
                 norm_topk_prob=True)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each (``first_expert`` 0, 2, ...),
    four a token as published, no shared expert: their routed parts are
    the whole layer as the uncut reference gives it, nothing counted
    twice."""
    rng = np.random.default_rng(5)
    lp = {'router_w': 0.3 * draw(rng, EXPERTS, UNITS),
          'router_b': 0.3 * draw(rng, EXPERTS),
          'experts_gate': 0.3 * draw(rng, EXPERTS, SIZE, UNITS),
          'experts_up': 0.3 * draw(rng, EXPERTS, SIZE, UNITS),
          'experts_down': 0.3 * draw(rng, EXPERTS, UNITS, SIZE)}
    x = np.random.default_rng(0).normal(0, 1, (2, 10, UNITS)) \
        .astype('float32')
    with jax.default_matmul_precision('highest'):
        want = ref.sparse_ffn(lp, LAYER_CFG, jnp.asarray(x))

    def share(held):
        blk = nn.SparseExperts(UNITS, EXPERTS, PER_TOKEN, SIZE, held=held)
        blk.initialize()
        blk.router.weight.set_data(NDArray(lp['router_w']))
        blk.router_bias.set_data(NDArray(lp['router_b']))
        for name in ref.STACKED:
            getattr(blk, name).set_data(
                NDArray(lp[name][held.start:held.stop]))
        return blk(mx.np.array(x)).asnumpy()

    each = EXPERTS // SHARES
    parts = [share(range(j, j + each)) for j in range(0, EXPERTS, each)]
    assert len(parts) == SHARES
    assert sum(np.abs(p).max() > 0 for p in parts) > SHARES // 2
    close(sum(parts), want)
    close(share(range(EXPERTS)), want)


# ----------------------------------------------- what a profile's reader finds
def test_the_compiled_forward_carries_the_scopes():
    net, _ = zoo_net()
    tokens = jnp.asarray(rows()[:, :-1])
    text = jax.jit(lambda ids: net(NDArray(ids))._data).lower(tokens) \
        .compile().as_text()
    for scope in ('mx.short_conv', 'mx.attention', 'mx.experts',
                  'mx.router', 'mx.layer_norm'):
        assert f'/{scope}/' in text, scope
    assert '/mx.ssm_conv/' not in text
