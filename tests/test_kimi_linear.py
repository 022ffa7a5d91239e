"""The kimi_linear family at tiny sizes on the CPU, seeded weights: the
chunked delta rule (``npx.kda_scan``) against the per-position recurrence
of ``chipbench/reference/kimi_linear.py``, the zoo's
``KimiLinearForCausalLM`` against that reference (float32 at
``highest``), latent attention with and without rotary embedding, the
thirty-two shares of a sparse layer adding up, and the scopes a profile's
reader finds.

Tolerances: both sides are float32 on the CPU, where a product is a
float32 product whatever the precision asked for; they differ in the
order of their sums (a chunked delta rule that inverts its chunks by
products against a recurrence or a float64 solve, a sorted grouped
product against a dense loop over the experts), which is a few ulps of
the largest term: rel 1e-4, with an abs
of 1e-6 of the array's largest element (or of 1) for the elements that
nearly cancel. Where a chunk's decays pass 88 the chunked form takes the
difference of two running sums of that size, so its decays carry an error
of about |G| ulps: 1e-3 there.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.deepseek_v3 import (DeepseekV3Config,
                                                   MLAttention)
from mxnet_tpu.gluon.model_zoo.kimi_linear import (KimiLinearConfig,
                                                   KimiLinearForCausalLM)
from mxnet_tpu.gluon.model_zoo.llama import LlamaMLP
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import kda
from chipbench.families import kimi_linear as family
from chipbench.reference import deepseek_v3 as dsv3_ref
from chipbench.reference import kimi_linear as ref

RTOL, ATOL = 1e-4, 1e-6

# every kind of layer: KDA with the dense FFN, MLA and KDA with experts;
# rows that are no multiple of the chunk
CFG = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=3, num_attention_heads=2, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16, mla_use_nope=True,
    linear_attn_config=dict(kda_layers=[1, 3], full_attn_layers=[2],
                            num_heads=2, head_dim=8,
                            short_conv_kernel_size=4),
    num_experts=4, router_width=8, first_expert=2, num_shared_experts=1,
    num_experts_per_token=2, first_k_dense_replace=1, moe_layer_freq=1,
    moe_renormalize=True, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
    rope_theta=10000, vocab_size=128, chunk_size=8, initializer_range=0.05)
SEED = 11


def close(got, want, err_msg='', rtol=RTOL):
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=rtol, err_msg=err_msg,
        atol=ATOL * max(1.0, float(np.abs(want).max())))


# --------------------------------------------------------- the delta rule
def rule_inputs(t, seed=0, batch=2, heads=3, width=8, values=6,
                decay=(0.001, 0.5), beta=(0.0, 1.0)):
    """q and k L2-normalised, log decays drawn in -``decay``, write
    strengths in ``beta``."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(draw(batch, t, heads, width)),
            unit(draw(batch, t, heads, width)),
            draw(batch, t, heads, values),
            -jnp.asarray(rng.uniform(*decay, (batch, t, heads, width)),
                         jnp.float32),
            jnp.asarray(rng.uniform(*beta, (batch, t, heads)), jnp.float32))


def both_ways(fn, args):
    """fn's value and the gradients of every argument, one program."""
    return jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *b: (fn(*b) ** 2).sum(), argnums=tuple(range(len(a))))(*a)))(
        *args)


@pytest.mark.parametrize('chunk, t, decay, beta, rtol', [
    (4, 12, (0.001, 0.5), (0.0, 1.0), RTOL),           # whole chunks
    (16, 37, (0.001, 0.5), (0.0, 1e-3), RTOL),         # padded; beta near 0
    (32, 70, (0.001, 0.5), (0.999, 1.0), RTOL),        # two blocks; near 1
    (64, 64, (0.001, 0.5), (0.0, 1.0), RTOL),          # four blocks
    # a chunk's decays pass 88, where exp(-G) is inf in float32
    (32, 40, (3.0, 20.0), (0.0, 1.0), 1e-3),
], ids=['whole_chunks', 'padded_beta_near_0', 'two_blocks_beta_near_1',
        'four_blocks', 'strong_decays'])
def test_the_chunked_rule_is_the_recurrence(chunk, t, decay, beta, rtol):
    """Values and the gradients of all five inputs, the state from zero at
    each row's start."""
    args = rule_inputs(t, decay=decay, beta=beta)
    if decay[0] > 1:
        assert (np.asarray(args[3])[:, :chunk].sum(1) < -88).all()
    got, got_g = both_ways(
        lambda *a: kda.kda_scan(*a, chunk_size=chunk), args)
    with jax.default_matmul_precision('highest'):
        want, want_g = both_ways(ref.recurrence, args)
    assert np.isfinite(np.asarray(got)).all()
    close(got, want, rtol=rtol)
    for name, g, w in zip('q k v log_alpha beta'.split(), got_g, want_g):
        assert np.isfinite(np.asarray(g)).all(), name
        close(g, w, err_msg=name, rtol=rtol)


def test_a_padded_position_neither_decays_nor_writes():
    """What the positions after T would add never reaches the first T,
    and a row starts from zero whatever the row before it held."""
    q, k, v, la, beta = rule_inputs(20)
    whole = kda.kda_scan(q, k, v, la, beta, chunk_size=8)
    cut = kda.kda_scan(q[:, :13], k[:, :13], v[:, :13], la[:, :13],
                       beta[:, :13], chunk_size=8)
    close(cut, whole[:, :13])
    alone = kda.kda_scan(q[1:], k[1:], v[1:], la[1:], beta[1:], chunk_size=8)
    close(alone, whole[1:])


def test_a_chunk_that_is_no_multiple_of_a_block_is_refused():
    with pytest.raises(ValueError, match='no multiple of 16'):
        kda.kda_scan(*rule_inputs(24), chunk_size=24)


def chunk_system(chunk, decay, beta, alike=0.0, seed=0, systems=6, width=32):
    """``A_kk`` (systems, C, C) and the right-hand sides ``beta k o e^G``
    and ``beta v`` (systems, C, width) of one chunk a system, as the rule
    makes them; ``alike`` mixes each key with the one before it, as a
    short convolution makes neighbours alike."""
    rng = np.random.default_rng(seed)
    k = rng.normal(0, 1, (systems, chunk, width))
    for i in range(1, chunk):
        k[:, i] = alike * k[:, i - 1] + (1 - alike) * k[:, i]
    k = jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True),
                    jnp.float32)
    g = jnp.cumsum(-jnp.asarray(rng.uniform(*decay, k.shape), jnp.float32),
                   axis=-2)
    b = jnp.asarray(rng.uniform(*beta, (systems, chunk, 1)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, k.shape), jnp.float32)
    a_kk = b * kda._decayed_products(k, k, g, strict=True)
    return a_kk, b * k * jnp.exp(g), b * v


@pytest.mark.parametrize('chunk, decay, beta, alike', [
    (4, (0.001, 0.5), (0.0, 1.0), 0.0),
    (16, (0.001, 0.5), (0.0, 1.0), 0.0),
    (32, (0.001, 0.5), (0.0, 1.0), 0.0),
    (64, (0.001, 0.5), (0.0, 1.0), 0.0),
    (64, (3.0, 20.0), (0.0, 1.0), 0.0),          # decays pass 88
    (64, (0.001, 0.5), (0.0, 1e-3), 0.0),
    (64, (1e-4, 1e-3), (0.999, 1.0), 0.0),
    # neighbours alike: the powers of A_kk grow like binomials here
    (64, (1e-3, 0.1), (0.3, 0.7), 0.9),
], ids=['chunk_4', 'chunk_16', 'chunk_32', 'chunk_64', 'strong_decays',
        'beta_near_0', 'beta_near_1', 'neighbours_alike'])
def test_the_inverse_by_products_is_the_float64_solve(chunk, decay, beta,
                                                      alike):
    a_kk, rk, rv = chunk_system(chunk, decay, beta, alike)
    if decay[0] > 1:
        assert (np.asarray(rk) == 0).any()       # e^G underflows
    got = jax.jit(kda._wy_solve)(a_kk, rk, rv)
    eye = np.eye(chunk)
    system = eye + np.asarray(a_kk, np.float64)
    for g, r in zip(got, (rk, rv)):
        close(g, np.linalg.solve(system, np.asarray(r, np.float64)))
    close(kda._unit_lower_inverse(a_kk), np.linalg.inv(system))


def test_the_solves_own_backward_is_the_triangular_solves():
    """Every gradient of ``(I + N)^-1 [Rk | Rv]`` as JAX gives them
    through ``solve_triangular``; what lies on and above N's diagonal is
    read by neither and gets no gradient."""
    a_kk, rk, rv = chunk_system(64, (0.001, 0.5), (0.0, 1.0), alike=0.5)
    rng = np.random.default_rng(1)
    above = jnp.asarray(np.triu(rng.normal(0, 1, a_kk.shape)), jnp.float32)
    n = a_kk + above
    cot = [jnp.asarray(rng.normal(0, 1, r.shape), jnp.float32)
           for r in (rk, rv)]
    eye = jnp.eye(64, dtype=jnp.float32)
    by_solve = lambda n, rk, rv: [jax.scipy.linalg.solve_triangular(
        eye + n, r, lower=True, unit_diagonal=True) for r in (rk, rv)]
    grads = lambda fn: jax.jit(jax.grad(
        lambda *a: sum((y * c).sum() for y, c in zip(fn(*a), cot)),
        argnums=(0, 1, 2)))(n, rk, rv)
    got = grads(kda._wy_solve)
    with jax.default_matmul_precision('highest'):
        want = grads(by_solve)
        for g, w in zip(kda._wy_solve(n, rk, rv), by_solve(n, rk, rv)):
            close(g, w)
    for name, g, w in zip(('N', 'Rk', 'Rv'), got, want):
        close(g, w, err_msg=name)
    assert not np.triu(np.asarray(got[0])).any()


def test_no_triangular_solve_is_left_in_the_rule():
    """The value and every gradient of the rule in chunks of 64, lowered
    for the TPU (the CPU lowers a solve to LAPACK's ``trsm``): the inverse
    is products, in the forward and in the backward."""
    args = rule_inputs(64, batch=1, heads=1, width=8, values=8)
    traced = jax.jit(jax.value_and_grad(
        lambda *a: (kda.kda_scan(*a, chunk_size=64) ** 2).sum(),
        argnums=tuple(range(5)))).trace(*args)
    assert 'triangular_solve' not in str(traced.jaxpr)
    text = traced.lower(lowering_platforms=('tpu',)).as_text(dialect='hlo')
    assert ' dot(' in text and 'triangular-solve' not in text


# ------------------------------------------------------------ the zoo model
def zoo_net(cfg=CFG, seed=SEED):
    """The zoo's net with the reference's weights from the seed; the
    routers' biases drawn too, so that they change a choice."""
    net = KimiLinearForCausalLM(KimiLinearConfig(**cfg))
    net.initialize(mx.initializer.Zero())
    net(mx.np.zeros((1, 4), dtype='int32'))        # deferred shapes
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
    the delta rule's own among them, by name; hybridized, as it trains."""
    net, weights = zoo_net()
    net.hybridize(static_alloc=True)
    tokens = rows()
    with autograd.record():
        out, loss = zoo_loss(net, tokens)
    loss.backward()
    moved, held = ref.split(weights)
    with jax.default_matmul_precision('highest'):
        want = ref.logits_of(weights, CFG, jnp.asarray(tokens[:, :-1]))
        want_loss, grads = jax.value_and_grad(ref.loss_fn)(
            moved, held, CFG, jnp.asarray(tokens))
    close(out.asnumpy(), want)
    assert float(loss.asnumpy()) == pytest.approx(float(want_loss),
                                                  rel=RTOL)
    grads = family.by_program_name(grads)
    params = net.collect_params()
    frozen = {n for n, p in params.items() if p.grad_req == 'null'}
    assert frozen == {family.program_name(k) for k in held} and frozen
    assert set(grads) == set(params) - frozen
    for tail in ('A_log', 'dt_bias', 'q_conv1d.weight', 'f_b_proj.weight',
                 'g_a_proj.weight', 'o_norm.weight'):
        assert f'model.layers0.self_attn.{tail}' in grads
    for name, w in grads.items():
        assert np.abs(np.asarray(w)).max() > 0, name
        close(params[name].grad().asnumpy(), w, err_msg=name)


def test_it_trains_through_the_trainer_with_the_fused_update():
    net, _ = zoo_net()
    net.hybridize(static_alloc=True, remat=True)
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-2}, kvstore=None)
    tokens = rows()
    mixer = net.model.layers[0].self_attn
    a_log = mixer.A_log.data().asnumpy().copy()
    losses = []
    for _ in range(6):
        with autograd.record():
            _, loss = zoo_loss(net, tokens)
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < 0.7 * losses[0], losses
    assert not trainer._fused_fallback_taken
    assert not np.array_equal(mixer.A_log.data().asnumpy(), a_log)


def test_the_zoos_own_initialisers_are_the_model_types():
    net = KimiLinearForCausalLM(KimiLinearConfig(**CFG))
    net.initialize()
    net(mx.np.zeros((1, 4), dtype='int32'))
    mixer = net.model.layers[0].self_attn
    a = np.exp(mixer.A_log.data().asnumpy())
    assert ((a >= 1) & (a <= 16)).all()
    steps = np.log1p(np.exp(mixer.dt_bias.data().asnumpy()))   # softplus
    assert (steps >= 1e-3 * (1 - 1e-4)).all() and (steps <= 0.1001).all()
    assert mixer.q_conv1d.bias is None
    assert np.abs(mixer.q_conv1d.weight.data().asnumpy()).max() <= 0.5


@pytest.mark.parametrize('key, value, what', [
    ('q_lora_rank', 64, 'query compression'),
    ('num_expert_group', 4, 'grouped choice'),
    ('num_nextn_predict_layers', 1, 'multi-token prediction'),
    ('linear_attn_config', dict(CFG['linear_attn_config'], kda_layers=[1]),
     'not layers 1..3 once each')])
def test_a_config_the_zoo_cannot_compute_is_refused(key, value, what):
    with pytest.raises(NotImplementedError, match=what):
        KimiLinearConfig(**dict(CFG, **{key: value}))


# ------------------------------------------- latent attention without rotary
def mla(nope, seed=5):
    """An MLAttention of CFG's widths with seeded weights, and them."""
    cfg = DeepseekV3Config(
        hidden_size=CFG['hidden_size'],
        num_attention_heads=CFG['num_attention_heads'],
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=16, rope_theta=10000.0, rms_norm_eps=1e-5,
        mla_use_nope=nope)
    lp = {k[len('l1/'):]: a for k, a in ref.init_params(CFG, seed).items()
          if k.startswith('l1/')}
    blk = MLAttention(cfg)
    blk.initialize()
    for name, leaf in (('q_proj', 'q_w'), ('kv_a_proj_with_mqa', 'kva_w'),
                       ('kv_b_proj', 'kvb_w'), ('o_proj', 'o_w')):
        getattr(blk, name).weight.set_data(NDArray(lp[leaf]))
    blk.kv_a_layernorm.weight.set_data(NDArray(lp['kva_ln']))
    return blk, lp


def test_latent_attention_without_rotary_is_the_references():
    """Under ``mla_use_nope`` the zoo's MLAttention is the kimi_linear
    reference's; with the switch off it is the deepseek_v3 reference's,
    rotary embedding and all, and the two differ."""
    x = np.random.default_rng(1).normal(0, 1, (2, 10, CFG['hidden_size'])) \
        .astype('float32')
    nope_blk, lp = mla(True)
    rope_blk, _ = mla(False)
    with jax.default_matmul_precision('highest'):
        want_nope = ref.attention(lp, CFG, jnp.asarray(x))
        want_rope = dsv3_ref.attention(
            lp, dict(CFG, rope_theta=10000.0, rms_norm_eps=1e-5),
            jnp.asarray(x))
    got_nope = nope_blk(mx.np.array(x)).asnumpy()
    close(got_nope, want_nope)
    close(rope_blk(mx.np.array(x)).asnumpy(), want_rope)
    assert np.abs(got_nope - np.asarray(want_rope)).max() > 1e-3
    # the switch takes both rotary calls out and nothing else
    trace = lambda blk: str(jax.make_jaxpr(
        lambda a: blk(NDArray(a))._data)(jnp.asarray(x)))
    assert trace(rope_blk).count('sin') == 2 and 'sin' not in trace(nope_blk)


# ------------------------------------------------ the thirty-two shares
UNITS, EXPERTS, PER_TOKEN, SIZE, SHARES = 32, 64, 8, 16, 32
LAYER_CFG = dict(num_experts=EXPERTS, router_width=EXPERTS,
                 num_experts_per_token=PER_TOKEN, routed_scaling_factor=2.446,
                 moe_renormalize=True)


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """Thirty-two chips hold two experts each (``first_expert`` 0, 2, ...),
    eight a token as published; their routed parts, with the shared expert
    counted once, are the whole layer as the uncut reference gives it."""
    rng = np.random.default_rng(5)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)
    lp = {'router_w': draw(EXPERTS, UNITS), 'router_b': draw(EXPERTS),
          'experts_gate': draw(EXPERTS, SIZE, UNITS),
          'experts_up': draw(EXPERTS, SIZE, UNITS),
          'experts_down': draw(EXPERTS, UNITS, SIZE),
          'shared_gate': draw(SIZE, UNITS), 'shared_up': draw(SIZE, UNITS),
          'shared_down': draw(UNITS, SIZE)}
    x = np.random.default_rng(0).normal(0, 1, (2, 10, UNITS)) \
        .astype('float32')
    with jax.default_matmul_precision('highest'):
        want = ref.sparse_ffn(lp, LAYER_CFG, jnp.asarray(x))

    def share(held, shared=False):
        blk = nn.SparseExperts(
            UNITS, EXPERTS, PER_TOKEN, SIZE, held=held,
            shared=LlamaMLP(types.SimpleNamespace(
                units=UNITS, hidden_size=SIZE)) if shared else None,
            routed_scaling_factor=2.446)
        blk.initialize()
        blk.router.weight.set_data(NDArray(lp['router_w']))
        blk.router_bias.set_data(NDArray(lp['router_b']))
        for name in ref.STACKED:
            getattr(blk, name).set_data(
                NDArray(lp[name][held.start:held.stop]))
        if shared:
            for tail in ('gate', 'up', 'down'):
                getattr(blk.shared, f'{tail}_proj').weight.set_data(
                    NDArray(lp[f'shared_{tail}']))
        return blk

    each = EXPERTS // SHARES
    parts = [share(range(j, j + each))(mx.np.array(x)).asnumpy()
             for j in range(0, EXPERTS, each)]
    assert len(parts) == SHARES
    assert sum(np.abs(p).max() > 0 for p in parts) > SHARES // 2
    whole = share(range(EXPERTS), shared=True)
    close(sum(parts) + whole.shared(mx.np.array(x)).asnumpy(), want)
    close(whole(mx.np.array(x)).asnumpy(), want)


# ----------------------------------------------- what a profile's reader finds
def test_the_compiled_forward_carries_the_scopes():
    net, _ = zoo_net()
    tokens = jnp.asarray(rows()[:, :-1])
    text = jax.jit(lambda ids: net(NDArray(ids))._data).lower(tokens) \
        .compile().as_text()
    for scope in ('mx.kda', 'mx.ssm_conv', 'mx.attention', 'mx.experts',
                  'mx.router'):
        assert f'/{scope}/' in text, scope
