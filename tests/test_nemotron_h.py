"""The nemotron_h family at tiny sizes on the CPU, seeded weights: the
zoo's ``NemotronHForCausalLM`` against the plain reference
(``chipbench/reference/nemotron_h.py``, float32 at ``highest``, the
state-space layer position by position), the scan op against that
recurrence (whole chunks, a padded last chunk, decays that an unmasked
``exp`` would overflow on), the un-gated experts' shares adding up,
hybridized against imperative, and the scopes a profile's reader finds.

Tolerances: both sides are float32 on the CPU, where a product is a
float32 product whatever the precision asked for; they differ in the
order of their sums (a chunked scan against a recurrence, a sorted
grouped product against a dense loop over the experts, a flash-style
recompute against a softmax), which is a few ulps of the largest term:
rel 1e-4, with an abs of 1e-6 of the array's largest element (or of 1)
for the elements that nearly cancel.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.nemotron_h import (NemotronHConfig,
                                                  NemotronHForCausalLM,
                                                  Relu2MLP)
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import ssm
from chipbench.families import nemotron_h as family
from chipbench.reference import nemotron_h as ref

RTOL, ATOL = 1e-4, 1e-6

# every kind of layer, '-' too; positions that are no multiple of the chunk
CFG = dict(
    hidden_size=32, hybrid_override_pattern='ME*-M', num_hidden_layers=5,
    intermediate_size=24, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=24, n_routed_experts=4,
    router_width=8, first_expert=2, n_shared_experts=1,
    num_experts_per_tok=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
    conv_kernel=4, chunk_size=4, layer_norm_epsilon=1e-5,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    rescale_prenorm_residual=True, residual_rescale_layers=5,
    vocab_size=128, initializer_range=0.05)
SEED = 11
ONE_D = ('A_log', 'D', 'dt_bias', 'conv1d.weight', 'conv1d.bias')


def close(got, want, err_msg=''):
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=RTOL, err_msg=err_msg,
        atol=ATOL * max(1.0, float(np.abs(want).max())))


def zoo_net(cfg=CFG, seed=SEED):
    """The zoo's net with the reference's weights from the seed; the
    routers' biases drawn too, so that they change a choice."""
    net = NemotronHForCausalLM(NemotronHConfig(**cfg))
    net.initialize()
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
    # the state-space layer's own leaves are among them, by name
    for tail in ONE_D:
        assert f'backbone.layers0.mixer.{tail}' in want
    for name, w in want.items():
        assert np.abs(np.asarray(w)).max() > 0, name
        close(params[name].grad().asnumpy(), w, err_msg=name)


def test_hybridized_equals_imperative(trained_once):
    net, _, tokens, logits, loss = trained_once
    params = net.collect_params()
    grads = {n: p.grad().asnumpy().copy() for n, p in params.items()
             if p.grad_req != 'null'}
    net.hybridize(static_alloc=True, remat=True)
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


def test_it_trains_through_the_trainer_with_the_fused_update():
    net, _ = zoo_net()
    net.hybridize(static_alloc=True, remat=True)
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-2}, kvstore=None)
    tokens = rows()
    expert_layer = net.backbone.layers[1].mixer
    bias = expert_layer.router_bias.data().asnumpy().copy()
    a_log = net.backbone.layers[0].mixer.A_log.data().asnumpy().copy()
    losses = []
    for _ in range(8):
        with autograd.record():
            _, loss = zoo_loss(net, tokens)
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < 0.7 * losses[0], losses
    # one program for every leaf, the 1-D ones of the mixer among them
    assert not trainer._fused_fallback_taken
    assert not np.array_equal(
        net.backbone.layers[0].mixer.A_log.data().asnumpy(), a_log)
    # nothing moves the correction bias
    assert np.array_equal(expert_layer.router_bias.data().asnumpy(), bias)


def test_the_zoos_own_initialisers_are_the_model_types():
    cfg = NemotronHConfig(**CFG)
    net = NemotronHForCausalLM(cfg)
    net.initialize()
    mixer = net.backbone.layers[0].mixer
    heads = CFG['mamba_num_heads']
    close(mixer.A_log.data().asnumpy(), np.log(np.arange(1, heads + 1)))
    assert np.array_equal(mixer.D.data().asnumpy(), np.ones(heads))
    steps = np.log1p(np.exp(mixer.dt_bias.data().asnumpy()))   # softplus
    assert (steps >= 1e-3 * (1 - 1e-4)).all() and (steps <= 0.1001).all()
    for leaf in (mixer.conv1d.weight, mixer.conv1d.bias):
        a = leaf.data().asnumpy()
        assert np.abs(a).max() <= 0.5 and a.std() > 0.1
    # a projection onto the residual stream starts sqrt(layers) smaller
    ratio = mixer.out_proj.weight.data().asnumpy().std() \
        / mixer.in_proj.weight.data().asnumpy().std()
    assert ratio == pytest.approx(CFG['num_hidden_layers'] ** -0.5, rel=0.1)


@pytest.mark.parametrize('key, value, what', [
    ('hybrid_override_pattern', 'MEX-M', 'layer kind'),
    ('hybrid_override_pattern', 'ME*', 'pattern of 3 layers for 5'),
    ('mlp_hidden_act', 'silu', 'mlp_hidden_act'),
    ('use_conv_bias', False, 'convolution without its bias'),
    ('n_group', 2, 'grouped choice')])
def test_a_config_the_zoo_cannot_compute_is_refused(key, value, what):
    with pytest.raises(NotImplementedError, match=what):
        NemotronHConfig(**dict(CFG, **{key: value}))


# ----------------------------------------------------------- the scan op
def scan_inputs(t, seed=0, batch=2, heads=4, p=8, groups=2, n=8,
                dt_high=0.5):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, dt_high, (batch, t, heads)),
                     jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, (heads,)), jnp.float32)
    return (draw(batch, t, heads, p), dt, a, draw(batch, t, groups, n),
            draw(batch, t, groups, n), draw(heads))


def both_ways(fn, args):
    out = fn(*args)
    grads = jax.grad(lambda *a: (fn(*a) ** 2).sum(),
                     argnums=tuple(range(len(args))))(*args)
    return out, grads


@pytest.mark.parametrize('chunk', [4, 8])
@pytest.mark.parametrize('over', [0, 1, -1],
                         ids=['whole_chunks', 'one_more', 'one_less'])
def test_the_scan_is_the_recurrence(chunk, over):
    args = scan_inputs(3 * chunk + over)
    got, got_g = both_ways(
        lambda *a: ssm.ssm_scan(*a, chunk_size=chunk), args)
    want, want_g = both_ways(ref.recurrence, args)
    close(got, want)
    for name, g, w in zip('x dt a b c d'.split(), got_g, want_g):
        close(g, w, err_msg=name)


@pytest.mark.parametrize('chunk', [4, 8])
def test_decays_an_unmasked_exp_would_overflow_on(chunk):
    """One row's steps are so long that the differences of the running
    sum above the diagonal pass 88, where a float32 ``exp`` is ``inf``:
    masked after the exponential they would be ``inf * 0``."""
    x, dt, a, b, c, d = scan_inputs(2 * chunk + 1, seed=1)
    dt = dt.at[1].set(dt[1] * 0 + 40.0)
    span = np.asarray(dt[1, :chunk] * a).sum(0)
    assert (span < -88).all() and not np.isfinite(
        np.exp(-span.astype('float32'))).all()
    args = (x, dt, a, b, c, d)
    got, got_g = both_ways(
        lambda *a_: ssm.ssm_scan(*a_, chunk_size=chunk), args)
    want, want_g = both_ways(ref.recurrence, args)
    assert np.isfinite(np.asarray(got)).all()
    close(got, want)
    for name, g, w in zip('x dt a b c d'.split(), got_g, want_g):
        assert np.isfinite(np.asarray(g)).all(), name
        close(g, w, err_msg=name)


def test_the_convolution_is_four_shifted_multiply_adds():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 1, (2, 9, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (12, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 1, (12,)), jnp.float32)
    want_fn = lambda *a: jax.nn.silu(ref.causal_conv(*a))
    got, got_g = both_ways(ssm.ssm_conv, (x, w, bias))
    want, want_g = both_ways(want_fn, (x, w, bias))
    close(got, want)
    for g, w_ in zip(got_g, want_g):
        close(g, w_)
    # causal: position t reads nothing after t
    later = x.at[:, 5:].set(0.0)
    assert np.array_equal(np.asarray(ssm.ssm_conv(later, w, bias))[:, :5],
                          np.asarray(got)[:, :5])


# ----------------------------------------------- the un-gated expert layer
UNITS, EXPERTS, PER_TOKEN, SIZE, SHARED, SHARES = 32, 32, 3, 16, 24, 16


def layer_weights(seed=5, scale=0.3):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, scale, shape),
                                      jnp.float32)
    return {'router_w': draw(EXPERTS, UNITS), 'router_b': draw(EXPERTS),
            'experts_up': draw(EXPERTS, SIZE, UNITS),
            'experts_down': draw(EXPERTS, UNITS, SIZE),
            'shared_up': draw(SHARED, UNITS),
            'shared_down': draw(UNITS, SHARED),
            # for the gated form of the shared op; the model has none
            'experts_gate': draw(EXPERTS, SIZE, UNITS)}


LAYER_CFG = dict(hidden_size=UNITS, n_routed_experts=EXPERTS,
                 router_width=EXPERTS, num_experts_per_tok=PER_TOKEN,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 n_shared_experts=1, initializer_range=0.02,
                 moe_shared_expert_intermediate_size=SHARED,
                 num_hidden_layers=1, hybrid_override_pattern='E')


def share(lp, held, shared=False):
    """The Block that holds the experts ``held`` of the layer ``lp``."""
    blk = nn.SparseExperts(
        UNITS, EXPERTS, PER_TOKEN, SIZE, held=held,
        shared=Relu2MLP(NemotronHConfig(**LAYER_CFG), SHARED)
        if shared else None, routed_scaling_factor=2.5, activation='relu2')
    blk.initialize()
    cut = slice(held.start, held.stop)
    blk.router.weight.set_data(NDArray(lp['router_w']))
    blk.router_bias.set_data(NDArray(lp['router_b']))
    for name in ref.STACKED:
        getattr(blk, name).set_data(NDArray(lp[name][cut]))
    if shared:
        blk.shared.up_proj.weight.set_data(NDArray(lp['shared_up']))
        blk.shared.down_proj.weight.set_data(NDArray(lp['shared_down']))
    return blk


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold two experts each (``first_expert`` 0, 2, ...);
    their routed parts, with the shared expert counted once, are the
    whole layer as the uncut reference gives it."""
    lp = layer_weights()
    x = np.random.default_rng(0).normal(0, 1, (2, 10, UNITS)) \
        .astype('float32')
    with jax.default_matmul_precision('highest'):
        want = ref.sparse_ffn(lp, LAYER_CFG, jnp.asarray(x))
    each = EXPERTS // SHARES
    parts = [share(lp, range(j, j + each))(mx.np.array(x)).asnumpy()
             for j in range(0, EXPERTS, each)]
    assert len(parts) == SHARES
    assert sum(np.abs(p).max() > 0 for p in parts) > SHARES // 2
    whole = share(lp, range(EXPERTS), shared=True)
    shared = whole.shared(mx.np.array(x)).asnumpy()
    close(sum(parts) + shared, want)
    # and the Block that holds them all is the layer
    close(whole(mx.np.array(x)).asnumpy(), want)
    # a share is what the reference gives for the same share
    cut = dict(LAYER_CFG, n_routed_experts=each, first_expert=6)
    cut_lp = {k: a[6:6 + each] if k in ref.STACKED else a
              for k, a in lp.items()}
    with jax.default_matmul_precision('highest'):
        want_cut = ref.routed(cut_lp, cut, jnp.asarray(x))
    close(share(lp, range(6, 6 + each))(mx.np.array(x)).asnumpy(), want_cut)


def dense_loop(lp, cfg, u, activation):
    """The held experts' part of the layer an expert at a time, every
    token through every held expert."""
    first, held = cfg.get('first_expert', 0), cfg['n_routed_experts']
    w = ref.expert_weights(lp, cfg, u)[..., first:first + held]
    out = jnp.zeros_like(u)
    for j in range(held):
        up = u @ lp['experts_up'][j].T
        hidden = jax.nn.silu(u @ lp['experts_gate'][j].T) * up \
            if activation == 'swiglu' else jnp.square(jax.nn.relu(up))
        out = out + w[..., j:j + 1] * (hidden @ lp['experts_down'][j].T)
    return out


def leaf_names(activation):
    return ('experts_gate',) * (activation == 'swiglu') + ref.STACKED


def held_experts(activation, x, router_w, router_b, first, *leaves):
    """``sparse_experts`` for the consecutive experts from ``first`` on
    whose stacked ``leaves`` (a gate first, under swiglu) are given."""
    return mx.ops.experts.sparse_experts(
        x, router_w, router_b, leaves[0] if activation == 'swiglu' else None,
        *leaves[-2:], experts_per_token=PER_TOKEN, first_expert=first,
        routed_scaling_factor=2.5, activation=activation)


# 512 tokens x 3 pairs on 4 of 32 experts: 192 live rows expected of
# 1536, and a ladder with two rungs under the whole buffer
TOKENS, FIRST, HELD = 512, 8, 4
LADDER = (512, 1024, 1536)
# a router's bias and the rung it sends the layer to
ROUTINGS = {
    'none_held': (dict.fromkeys(range(FIRST, FIRST + HELD), -9.0), 512),
    'expected_share': ({}, 512),
    'one_rung_up': ({FIRST + 1: 9.0}, 1024),
    'all_held': (dict.fromkeys(range(FIRST, FIRST + 3), 9.0), 1536),
}


def partly_held(activation, routing, seed=9):
    """(the op on its differentiable arguments, the dense loop on the
    same, the arguments, live rows) of a layer that holds HELD of its
    EXPERTS experts under the bias of ``routing``."""
    lp = layer_weights(seed=seed)
    bias = np.asarray(lp['router_b']).copy()
    for expert, b in ROUTINGS[routing][0].items():
        bias[expert] = b
    lp['router_b'] = jnp.asarray(bias)
    names = leaf_names(activation)
    cut = dict(LAYER_CFG, n_routed_experts=HELD, first_expert=FIRST)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (TOKENS, UNITS)),
                    jnp.float32)
    args = (x, lp['router_w'], *(lp[n][FIRST:FIRST + HELD] for n in names))

    def got(x, rw, *leaves):
        return held_experts(activation, x, rw, lp['router_b'], FIRST, *leaves)

    def want(x, rw, *leaves):
        return dense_loop(dict(lp, router_w=rw, **dict(zip(names, leaves))),
                          cut, x, activation)

    chosen, _ = mx.ops.experts.route(x, lp['router_w'], lp['router_b'],
                                     PER_TOKEN, 'sigmoid', True, 2.5)
    live = int(((chosen >= FIRST) & (chosen < FIRST + HELD)).sum())
    return got, want, args, live


def agree_with_the_dense_loop(activation, routing):
    """Output and the gradient of every differentiable argument."""
    got, want, args, _ = partly_held(activation, routing)
    sq = lambda f: lambda *a: (f(*a) ** 2).sum()
    every = tuple(range(len(args)))
    with jax.default_matmul_precision('highest'):
        out, grads = want(*args), jax.grad(sq(want), every)(*args)
    close(got(*args), out)
    for a, e in zip(jax.grad(sq(got), every)(*args), grads):
        close(a, e)


@pytest.mark.parametrize('routing', ROUTINGS)
@pytest.mark.parametrize('activation', ['swiglu', 'relu2'])
def test_every_rung_of_the_ladder_is_the_dense_loop(activation, routing):
    """A layer that holds 4 of its 32 experts computes a prefix of its
    sorted buffer: whichever rung the routing lands on (no pair on a
    held expert; the expected share; one rung up; every token on held
    experts, the whole buffer, nothing dropped), the output and the
    gradients of x, the router and the stacked leaves are the dense
    loop's."""
    assert mx.ops.experts.prefix_ladder(
        TOKENS * PER_TOKEN, HELD, EXPERTS) == LADDER
    _, _, _, live = partly_held(activation, routing)
    rung = ROUTINGS[routing][1]
    assert live <= rung and all(p >= rung or p < live for p in LADDER), live
    assert (live == 0) == (routing == 'none_held')
    assert (live == TOKENS * PER_TOKEN) == (routing == 'all_held')
    agree_with_the_dense_loop(activation, routing)


@pytest.mark.parametrize('activation', ['swiglu', 'relu2'])
def test_the_shares_of_a_cut_buffer_add_up_to_the_uncut_layer(activation):
    """Eight chips hold four experts each and each computes a prefix:
    their parts add up to what one chip that holds all 32 gives over
    the whole buffer."""
    lp = layer_weights(seed=9)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (TOKENS, UNITS)),
                    jnp.float32)
    op = lambda first, n: held_experts(
        activation, x, lp['router_w'], lp['router_b'], first,
        *(lp[name][first:first + n] for name in leaf_names(activation)))
    parts = [np.asarray(op(first, HELD)) for first in range(0, EXPERTS, HELD)]
    assert all(np.abs(p).max() > 0 for p in parts)
    close(sum(parts), op(0, EXPERTS))


@pytest.mark.parametrize('activation', ['swiglu', 'relu2'])
def test_a_partly_held_layer_keeps_its_arguments_and_the_routing(activation):
    """What ``jax.vjp`` keeps of a layer with a ladder is no more than
    its arguments and the routing (a row's order, inverse and weight a
    pair, a count an expert): JAX would keep for a differentiated
    ``cond`` the residuals of every branch, each of its buffer's size."""
    got, _, args, _ = partly_held(activation, 'expected_share')
    _, vjp = jax.vjp(got, *args)
    kept = sum(a.nbytes for a in jax.tree_util.tree_leaves(vjp))
    pairs = TOKENS * PER_TOKEN
    # the router's own: scores and logits (tokens, E) twice, the chosen
    # and their weights a pair, a few counts
    routing = 2 * TOKENS * EXPERTS * 4 + 8 * pairs * 4 + 1024
    assert kept <= sum(a.nbytes for a in args) + routing, kept
    # a row of the buffer is UNITS wide: one buffer-sized array is more
    assert kept < sum(a.nbytes for a in args) + pairs * UNITS * 4


def _conds(jaxpr):
    """The ``cond`` equations of a jaxpr, those inside its equations'
    own jaxprs (a jit, a custom_vjp's call) among them."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'cond':
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _conds(sub)
    return found


@pytest.mark.parametrize('activation', ['swiglu', 'relu2'])
def test_a_fully_held_layer_has_no_cond_and_a_partly_held_one_has_one(
        activation):
    ladder = mx.ops.experts.prefix_ladder
    # the cells' own: 2,048 tokens x 6 on 16 and on 8 of 128 experts
    for held in (16, 8):
        rungs = ladder(12288, held, 128)
        assert len(rungs) <= 5 and rungs[-1] == 12288
        assert rungs[0] >= 1.5 * 12288 * held / 128
        assert all(p % 512 == 0 for p in rungs)
        assert list(rungs) == sorted(set(rungs))
    assert ladder(12288, 128, 128) == (12288,)
    assert ladder(60, 4, 8) == (60,)       # shorter than a tile: no rung
    got, _, args, _ = partly_held(activation, 'expected_share')
    conds = _conds(jax.make_jaxpr(got)(*args).jaxpr)
    assert len(conds) == 1 and len(conds[0].params['branches']) == len(LADDER)
    whole = lambda x, rw, *leaves: held_experts(
        activation, x, rw, layer_weights(seed=9)['router_b'], 0, *leaves)
    x, rw, *leaves = args
    every = [jnp.concatenate([a] * (EXPERTS // HELD)) for a in leaves]
    assert _conds(jax.make_jaxpr(whole)(x, rw, *every).jaxpr) == []
    assert _conds(jax.make_jaxpr(jax.grad(
        lambda *a: whole(*a).sum(), (0, 1)))(x, rw, *every).jaxpr) == []


def test_the_un_gated_experts_gradients_agree_with_the_dense_loop():
    lp = layer_weights(seed=9)
    held = range(8, 16)
    cut = dict(LAYER_CFG, n_routed_experts=len(held), first_expert=8)
    cut_lp = {k: a[8:16] if k in ref.STACKED else a for k, a in lp.items()}
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (24, UNITS)),
                    jnp.float32)
    op = mx.ops.experts.sparse_experts

    def got(x, rw, up, down):
        return (op(x, rw, lp['router_b'], None, up, down,
                   experts_per_token=PER_TOKEN, first_expert=8,
                   routed_scaling_factor=2.5, activation='relu2') ** 2).sum()

    def want(x, rw, up, down):
        return (ref.routed(dict(cut_lp, router_w=rw, experts_up=up,
                                experts_down=down), cut, x) ** 2).sum()

    args = (x, lp['router_w'], cut_lp['experts_up'], cut_lp['experts_down'])
    with jax.default_matmul_precision('highest'):
        w = jax.grad(want, (0, 1, 2, 3))(*args)
    for a, e in zip(jax.grad(got, (0, 1, 2, 3))(*args), w):
        close(a, e)


@pytest.mark.parametrize('routing', ['expected_share', 'one_rung_up'])
@pytest.mark.parametrize('activation', ['swiglu', 'relu2'])
def test_what_a_grouped_product_leaves_past_its_groups_reaches_no_gradient(
        monkeypatch, activation, routing):
    """On the TPU the rows of a grouped product past its last group are
    whatever the buffer held (PERF.md section 7; the CPU writes zeros).
    Here they are dirtied on purpose, forward and backward, in a prefix
    that has such rows: both forms' outputs and every gradient, the
    router's among them, are still the dense loop's."""
    real = jax.lax.ragged_dot

    def dirty(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        live = jnp.arange(out.shape[0])[:, None] < group_sizes.sum()
        return jnp.where(live, out, 7.5)

    monkeypatch.setattr(jax.lax, 'ragged_dot', dirty)
    _, _, _, live = partly_held(activation, routing)
    assert live < ROUTINGS[routing][1]        # the prefix has dead rows
    # a rung's body is traced once for all layers of a shape: here it
    # has to be traced anew, over the dirtied product, and not be kept
    fresh = mx.ops.experts._branches.cache_clear
    fresh()
    try:
        agree_with_the_dense_loop(activation, routing)
    finally:
        fresh()


# ------------------------------------------------ what a profile's reader finds
def test_the_compiled_forward_carries_the_scopes():
    net, _ = zoo_net()
    tokens = jnp.asarray(rows()[:, :-1])
    text = jax.jit(lambda ids: net(NDArray(ids))._data).lower(tokens) \
        .compile().as_text()
    for scope in ('mx.ssm_scan', 'mx.ssm_conv', 'mx.experts', 'mx.router',
                  'mx.attention'):
        assert f'/{scope}/' in text, scope
