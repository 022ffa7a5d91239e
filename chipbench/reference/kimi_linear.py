"""The plain reference for the ``kimi_linear`` family: a decoder whose
token mixers are Kimi Delta Attention (KDA) and latent attention without
rotary embedding (Kimi Linear), with sparse experts, its next-token loss,
its gradients and Adam, in straightforward ``jax.numpy``. Written from the
published ``config.json`` keys and the model type's equations; it imports
nothing of ``mxnet_tpu`` and makes the weights itself, from the seed.

A layer, for a token's hidden vector x (``n``: RMSNorm, ``x rsqrt(mean(x^2)
+ eps) w``)::

    h = x + Mixer(n(x))            y = h + FFN(n(h))

The mixer of layer i (1-based) is KDA where i is in
``linear_attn_config['kda_layers']`` and MLA where it is in
``full_attn_layers``. KDA, H heads of d = ``linear_attn_config['head_dim']``
(keys and values alike), u = n(x)::

    q, k, v = silu(conv(W_{q,k,v} u))   depthwise, causal, K - 1 zeros left
    q, k    = x / sqrt(sum x^2 + 1e-6)  a head
    log a_t = -exp(A_log_h) softplus(W_fb W_fa u + dt_bias)     a channel
    beta_t  = sigmoid(W_b u)                                    a head
    S' = Diag(a_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t / sqrt(d)                  S_0 = 0 at each row's start
    out = W_o [RMSNorm_head(o) w sigmoid(W_gb W_ga u)]

The recurrence is computed **position by position** (``lax.scan`` over
single positions, the state (H, d, d) its carry), not in chunks: the
program's chunked form (a triangular solve a chunk) shares nothing with
it. The scan is taken in segments whose inner steps are made again in the
backward pass, so that what is kept is a state a segment and not a state
a position.

MLA: ``deepseek_v3``'s expanded latent attention with **no rotary
embedding** (``mla_use_nope``): ``[q_nope_i; q_pe_i] = W_q u``, ``[c;
k_pe] = W_kva u``, ``[k_nope_i; v_i] = W_kvb n(c)``, scores ``(q_nope_i .
k_nope_i + q_pe_i . k_pe) / sqrt(qk_nope + qk_rope)``, causal softmax,
``W_o [P_i v_i]``.

FFN of the first ``first_k_dense_replace`` layers: ``W_d (silu(W_g u) *
W_u u)``. Of the others: ``s = sigmoid(W_r u)`` over all ``router_width``
experts; the ``num_experts_per_token`` largest of ``s + b`` are chosen;
``w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20)``
(``moe_renormalize``); ``sum_e w_e SwiGLU_e(u)`` plus one SwiGLU
``num_shared_experts`` times as wide.

Departures from the published code, each with its reason:

* **The chip's share.** ``num_experts`` counts the experts held here
  (``first_expert`` .. ``first_expert + num_experts - 1``) and
  ``router_width`` all of them: the router scores and chooses over all,
  the weights are normalised over all the chosen, and the sum runs over
  the held experts only. ``vocab_size`` is this chip's slice of the rows
  of the embedding and of the head, and the loss is over the slice. With
  ``router_width == num_experts`` and the whole vocabulary it is the
  published model.
* **A dense loop over the experts**: every held expert is computed for
  every token and weighted by the token's weight for it, 0 where the
  token did not choose it. No sort, no grouped product.
* **The correction bias** ``b`` is a leaf no gradient is taken for (a
  buffer in the published code) and nothing moves it.
* **Sizes and initialisers the file does not give** (the configuration's
  ``assumed``): the L2 norm's eps 1e-6 (the published kernel's); no bias
  on the short convolutions nor on any projection; ``A_log = log U(1,
  16)``; ``dt_bias`` the inverse softplus of a log-uniform draw in
  [0.001, 0.1] floored at 1e-4 (Mamba's, so that decays lie where a
  trained model's do); the convolutions' weights uniform in
  ``+-1/sqrt(conv_kernel)``; other matrices N(0, ``initializer_range``).
* One expert group (``num_expert_group = topk_group = 1``), so the grouped
  choice is the plain top-k; no query compression; no multi-token
  prediction; no dropout.

Float32 with matmuls at ``highest`` precision is the reference; the same
code in ``bfloat16`` is the control. The router's product and scores, the
decays and the write strengths are float32 in both.
"""

import functools
import math

import jax
import jax.numpy as jnp

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
FROZEN = 'router_b'             # leaves of this name take no gradient
STACKED = ('experts_gate', 'experts_up', 'experts_down')  # by expert
SEGMENT = 64                    # positions recomputed together
L2_EPS = 1e-6
TIME_STEP = (0.001, 0.1, 1e-4)  # dt_bias: min, max, floor


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def kda_layers(cfg):
    """0-based indices of the KDA layers."""
    lin = cfg['linear_attn_config']
    layers = sorted(lin['kda_layers'] + lin['full_attn_layers'])
    if layers != list(range(1, cfg['num_hidden_layers'] + 1)):
        raise ValueError(f'{lin} does not name layers 1..'
                         f'{cfg["num_hidden_layers"]} once each')
    return {i - 1 for i in lin['kda_layers']}


def is_sparse(cfg, layer):
    return (cfg['num_experts'] > 0
            and layer >= cfg['first_k_dense_replace']
            and layer % cfg['moe_layer_freq'] == 0)


def leaf_specs(cfg):
    """{name: (shape, kind)}; ``l<i>/`` leads a layer's leaves. Linear
    weights are (out, in): y = x W^T."""
    u, voc = cfg['hidden_size'], cfg['vocab_size']
    lin = cfg['linear_attn_config']
    h, d = lin['num_heads'], lin['head_dim']
    heads = cfg['num_attention_heads']
    nope, pe = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, latent = cfg['v_head_dim'], cfg['kv_lora_rank']
    x, held = cfg['moe_intermediate_size'], cfg['num_experts']
    shared = cfg['num_shared_experts'] * x
    specs = {'embed': ((voc, u), 'normal'), 'norm': ((u,), 'ones'),
             'head': ((voc, u), 'normal')}
    linear = kda_layers(cfg)
    for i in range(cfg['num_hidden_layers']):
        layer = {'ln1': ((u,), 'ones'), 'ln2': ((u,), 'ones')}
        if i in linear:
            layer.update({
                'q_w': ((h * d, u), 'normal'), 'k_w': ((h * d, u), 'normal'),
                'v_w': ((h * d, u), 'normal'),
                'q_conv': ((h * d, lin['short_conv_kernel_size']), 'conv'),
                'k_conv': ((h * d, lin['short_conv_kernel_size']), 'conv'),
                'v_conv': ((h * d, lin['short_conv_kernel_size']), 'conv'),
                'fa_w': ((d, u), 'normal'), 'fb_w': ((h * d, d), 'normal'),
                'dt_bias': ((h * d,), 'dt_bias'), 'A_log': ((h,), 'A_log'),
                'b_w': ((h, u), 'normal'),
                'ga_w': ((d, u), 'normal'), 'gb_w': ((h * d, d), 'normal'),
                'o_norm': ((d,), 'ones'), 'o_w': ((u, h * d), 'normal')})
        else:
            layer.update({
                'q_w': ((heads * (nope + pe), u), 'normal'),
                'kva_w': ((latent + pe, u), 'normal'),
                'kva_ln': ((latent,), 'ones'),
                'kvb_w': ((heads * (nope + vd), latent), 'normal'),
                'o_w': ((u, heads * vd), 'normal')})
        if is_sparse(cfg, i):
            layer.update({
                'router_w': ((cfg['router_width'], u), 'normal'),
                'router_b': ((cfg['router_width'],), 'zeros'),
                'experts_gate': ((held, x, u), 'normal'),
                'experts_up': ((held, x, u), 'normal'),
                'experts_down': ((held, u, x), 'normal'),
                'shared_gate': ((shared, u), 'normal'),
                'shared_up': ((shared, u), 'normal'),
                'shared_down': ((u, shared), 'normal')})
        else:
            f = cfg['intermediate_size']
            layer.update({'gate_w': ((f, u), 'normal'),
                          'up_w': ((f, u), 'normal'),
                          'down_w': ((u, f), 'normal')})
        specs.update({f'l{i}/{k}': s for k, s in layer.items()})
    return specs


def frozen(name):
    return name.rsplit('/', 1)[-1] == FROZEN


def init_params(cfg, seed):
    """All weights on the device in one jitted call from the seed:
    N(0, initializer_range) matrices, unit gains, a zero correction bias;
    ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
    log-uniform draw of step sizes, the convolutions uniform in
    +-1/sqrt(kernel)."""
    specs = leaf_specs(cfg)
    std = cfg['initializer_range']
    lo, hi, floor = TIME_STEP

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            if kind == 'normal':
                a = std * jax.random.normal(k, shape, jnp.float32)
            elif kind == 'conv':
                bound = 1.0 / math.sqrt(shape[1])
                a = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
            elif kind == 'dt_bias':
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                math.log(lo), math.log(hi)))
                dt = jnp.maximum(dt, floor)
                a = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == 'A_log':
                a = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1, 16))
            else:
                a = jnp.full(shape, float(kind == 'ones'), jnp.float32)
            out[name] = a
        return out

    return jax.jit(make)(seed_key(seed))


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def swiglu(u, gate_w, up_w, down_w):
    return (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T


def causal_conv(x, w):
    """(B, T, C) by (C, K): tap K - 1 on the position itself, tap k on the
    one K - 1 - k before it; K shifted multiply-adds, no bias."""
    t, taps = x.shape[1], w.shape[1]
    out = jnp.zeros_like(x)
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        out = out + shifted * w[:, k]
    return out


def recurrence(q, k, v, log_alpha, beta):
    """q, k (B, T, H, d), v (B, T, H, V), log_alpha (B, T, H, d) float32,
    beta (B, T, H) -> o (B, T, H, V), a position at a time."""
    batch, t, h, d = q.shape
    decay = jnp.exp(log_alpha).astype(q.dtype)
    beta = beta.astype(q.dtype)

    def one(state, at):
        q_t, k_t, v_t, a_t, b_t = at
        state = state * a_t[..., None]
        err = v_t - jnp.einsum('bhkv,bhk->bhv', state, k_t)
        state = state + b_t[..., None, None] * k_t[..., :, None] \
            * err[..., None, :]
        return state, jnp.einsum('bhkv,bhk->bhv', state, q_t) / math.sqrt(d)

    @jax.checkpoint
    def segment(state, part):
        return jax.lax.scan(one, state, part)

    seg = max(s for s in range(1, SEGMENT + 1) if t % s == 0)
    by_time = lambda arr: jnp.moveaxis(arr, 1, 0).reshape(
        t // seg, seg, *arr.shape[:1], *arr.shape[2:])
    _, o = jax.lax.scan(segment, jnp.zeros((batch, h, d, v.shape[-1]),
                                           q.dtype),
                        tuple(by_time(a) for a in (q, k, v, decay, beta)))
    return jnp.moveaxis(o.reshape(t, batch, h, -1), 0, 1)


def kda(lp, cfg, u):
    lin = cfg['linear_attn_config']
    h, d = lin['num_heads'], lin['head_dim']
    b, t, _ = u.shape

    def heads(w, conv, normed):
        x = jax.nn.silu(causal_conv(u @ w.T, conv)).reshape(b, t, h, d)
        if normed:
            xf = x.astype(jnp.float32)
            x = (xf * jax.lax.rsqrt(jnp.square(xf).sum(-1, keepdims=True)
                                    + L2_EPS)).astype(x.dtype)
        return x

    q = heads(lp['q_w'], lp['q_conv'], True)
    k = heads(lp['k_w'], lp['k_conv'], True)
    v = heads(lp['v_w'], lp['v_conv'], False)
    step = jax.nn.softplus(((u @ lp['fa_w'].T) @ lp['fb_w'].T)
                           .astype(jnp.float32)
                           + lp['dt_bias'].astype(jnp.float32))
    log_alpha = -jnp.exp(lp['A_log'].astype(jnp.float32))[:, None] \
        * step.reshape(b, t, h, d)
    beta = jax.nn.sigmoid((u @ lp['b_w'].T).astype(jnp.float32))
    o = recurrence(q, k, v, log_alpha, beta)
    gate = ((u @ lp['ga_w'].T) @ lp['gb_w'].T).reshape(b, t, h, d)
    o = rms_norm(o, lp['o_norm'], cfg['rms_norm_eps']) \
        * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    return o.reshape(b, t, h * d) @ lp['o_w'].T


def attention(lp, cfg, u):
    """Latent attention, expanded, no rotary embedding."""
    heads = cfg['num_attention_heads']
    nope, pe = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, latent = cfg['v_head_dim'], cfg['kv_lora_rank']
    b, t, _ = u.shape
    q = (u @ lp['q_w'].T).reshape(b, t, heads, nope + pe)
    kva = u @ lp['kva_w'].T
    kv = rms_norm(kva[..., :latent], lp['kva_ln'], cfg['rms_norm_eps']) \
        @ lp['kvb_w'].T
    kv = kv.reshape(b, t, heads, nope + vd)
    s = jnp.einsum('bqnd,bknd->bnqk', q[..., :nope], kv[..., :nope]) \
        + jnp.einsum('bqnd,bkd->bnqk', q[..., nope:], kva[..., latent:])
    s = s / math.sqrt(nope + pe)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s,
                  jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(u.dtype)
    a = jnp.einsum('bnqk,bknd->bqnd', p, kv[..., nope:])
    return a.reshape(b, t, heads * vd) @ lp['o_w'].T


def expert_weights(lp, cfg, u):
    """(B, T, router_width): a token's weight for each expert, 0 for the
    ones it did not choose. Float32, as the published gate."""
    s = jax.nn.sigmoid(u.astype(jnp.float32)
                       @ lp['router_w'].astype(jnp.float32).T)
    _, chosen = jax.lax.top_k(s + lp['router_b'].astype(jnp.float32),
                              cfg['num_experts_per_token'])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg['moe_renormalize']:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * cfg['routed_scaling_factor']
    onehot = chosen[..., None] == jnp.arange(cfg['router_width'])
    return (picked[..., None] * onehot).sum(-2)


def routed(lp, cfg, u):
    """The held experts' part of the layer."""
    first = cfg.get('first_expert', 0)
    held = cfg['num_experts']
    w = expert_weights(lp, cfg, u)[..., first:first + held]

    def one(acc, e):
        gate_w, up_w, down_w, w_e = e
        y = swiglu(u, gate_w, up_w, down_w)
        return acc + w_e[..., None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (lp['experts_gate'], lp['experts_up'], lp['experts_down'],
         jnp.moveaxis(w, -1, 0)))
    return out


def sparse_ffn(lp, cfg, u):
    return routed(lp, cfg, u) + swiglu(u, lp['shared_gate'], lp['shared_up'],
                                       lp['shared_down'])


def layer_params(p, i):
    lead = f'l{i}/'
    return {k[len(lead):]: a for k, a in p.items() if k.startswith(lead)}


def layer(x, lp, cfg, linear, sparse):
    eps = cfg['rms_norm_eps']
    mixer = kda if linear else attention
    h = x + mixer(lp, cfg, rms_norm(x, lp['ln1'], eps))
    u = rms_norm(h, lp['ln2'], eps)
    if sparse:
        return h + sparse_ffn(lp, cfg, u)
    return h + swiglu(u, lp['gate_w'], lp['up_w'], lp['down_w'])


def hidden_of(p, cfg, tokens):
    """(B, T) ids -> (B, T, U) after the final norm. Each layer is
    recomputed in the backward pass."""
    x = p['embed'][tokens]
    linear = kda_layers(cfg)
    for i in range(cfg['num_hidden_layers']):
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, linear=i in linear, sparse=is_sparse(cfg, i)))(
            x, layer_params(p, i))
    return rms_norm(x, p['norm'], cfg['rms_norm_eps'])


def logits_of(p, cfg, tokens):
    return hidden_of(p, cfg, tokens) @ p['head'].T


def loss_fn(moved, held, cfg, rows):
    """Mean next-token loss over the rows' positions: ``rows`` (B, T + 1)
    ids, every position of the first T predicts the one after it.
    ``moved`` are the leaves a gradient is taken for, ``held`` the
    others."""
    logits = logits_of({**moved, **held}, cfg, rows[:, :-1])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1)
    return -picked.mean()


def split(p):
    """(the leaves the optimizer moves, the leaves it holds still)."""
    return ({k: a for k, a in p.items() if not frozen(k)},
            {k: a for k, a in p.items() if frozen(k)})


def leaf_norms(tree):
    """{name: norm} in float32; a vector, one an expert, for a leaf
    stacked over the experts."""
    out = {}
    for name, a in tree.items():
        a = a.astype(jnp.float32)
        if name.rsplit('/', 1)[-1] in STACKED:
            out[name] = jnp.sqrt(jnp.square(a).reshape(a.shape[0], -1)
                                 .sum(-1))
        else:
            out[name] = jnp.sqrt(jnp.square(a).sum())
    return out


def make_step(cfg, lr, block_rows):
    """One jitted Adam step over a batch taken in blocks of rows."""

    def step(p, held, m, v, t, rows):
        n_blocks = max(1, rows.shape[0] // block_rows)
        blocks = rows.reshape((n_blocks, -1) + rows.shape[1:])

        def one(carry, blk):
            loss, g = jax.value_and_grad(loss_fn)(p, held, cfg, blk)
            return (carry[0] + loss.astype(jnp.float32),
                    jax.tree.map(jnp.add, carry[1], g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (loss, g), _ = jax.lax.scan(one, zero, blocks)
        loss = loss / n_blocks
        g = jax.tree.map(lambda a: a / n_blocks, g)
        m = jax.tree.map(lambda a, b: BETA1 * a + (1 - BETA1) * b, m, g)
        v = jax.tree.map(lambda a, b: BETA2 * a + (1 - BETA2) * b * b, v, g)
        tf = t.astype(jnp.float32)
        c1, c2 = 1 - BETA1 ** tf, 1 - BETA2 ** tf

        def upd(w, a, b):
            new = w - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS)
            return new.astype(w.dtype)

        return jax.tree.map(upd, p, m, v), m, v, loss, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 2, 3))


def follow(cfg, seed, batches, lr, dtype='float32', block_rows=1):
    """Run the first ``len(batches)`` training steps from the seed.
    Returns host numbers: ``losses`` (one a step), ``grad_norms`` (step
    1's gradient, by leaf) and ``change_norms`` (the leaves after the
    last step less the initial ones), the leaves the optimizer moves."""
    precision = 'highest' if dtype == 'float32' else 'default'
    cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
    with jax.default_matmul_precision(precision):
        p, held = split(cast(init_params(cfg, seed)))
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        step = make_step(cfg, lr, block_rows)
        losses, grad_norms = [], None
        for t, rows in enumerate(batches, 1):
            p, m, v, loss, gn = step(p, held, m, v, jnp.int32(t),
                                     jnp.asarray(rows))
            losses.append(float(loss))
            if t == 1:
                grad_norms = jax.device_get(gn)
        del m, v
        # against the initial weights as this precision holds them, made
        # again: the change is the optimizer's, not the cast's, and a
        # second copy was not held through the steps
        first, _ = split(cast(init_params(cfg, seed)))
        change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))(p, first)
        return {'losses': losses, 'grad_norms': grad_norms,
                'change_norms': jax.device_get(change)}
