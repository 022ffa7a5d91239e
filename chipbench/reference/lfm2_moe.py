"""The plain reference for the ``lfm2_moe`` family: a decoder whose token
mixers are gated short convolutions and grouped-query attention with
normalised queries and keys (LFM2, LFM2-MoE), with sparse experts, its
next-token loss, its gradients and Adam, in straightforward
``jax.numpy``. Written from the published ``config.json`` keys and the
model type's equations; it imports nothing of ``mxnet_tpu`` and makes the
weights itself, from the seed.

A layer, for a token's hidden vector x (``n``: RMSNorm, ``x rsqrt(mean(x^2)
+ eps) w``)::

    h = x + Mixer(n_op(x))            y = h + FFN(n_ffn(h))

after the last layer ``n_emb``, then the head, which is the embedding's
own matrix (tied). U = ``hidden_size``; no projection has a bias.

``conv`` (``layer_types``), K = ``conv_L_cache``, u = n_op(x)::

    [b; c; x] = W_in u
    s_t = sum_{k < K} w[:, k] (b x)_{t - K + 1 + k}      zeros before t = 0
    out = W_out (c s)                                    no activation

The convolution is computed **position by position**, as the published
code's decode path keeps it: a ``lax.scan`` over the positions carrying
the last K inputs (the newest last), each output their sum weighted by
the taps. The program's shifted arrays share nothing with it.

``full_attention``, H heads and G key/value heads of d = U / H::

    q = n_d(W_q u), k = n_d(W_k u) a head (one weight of d for all heads)
    v = W_v u
    rotary at rope_theta, rotate-half: x cos + rotate_half(x) sin, where
    rotate_half([x1; x2]) = [-x2; x1] and the angle of channel j and of
    j + d/2 is t theta^(-2j/d)
    causal softmax at 1/sqrt(d), query head i reading key/value head
    i // (H / G); W_o

The scores are taken in blocks of ``QUERY_BLOCK`` queries, each made
again in the backward pass, so that a row's (H, T, T) scores are never
held at once.

FFN of the first ``num_dense_layers`` layers: ``w2(silu(w1 u) * w3 u)``.
Of the others: ``s = sigmoid(W_r u)`` over all ``router_width`` experts;
the ``num_experts_per_tok`` largest of ``s + b`` (``expert_bias``) are
chosen; ``w_e = routed_scaling_factor s_e / (sum of the chosen s +
1e-20)`` (``norm_topk_prob``); ``sum_e w_e SwiGLU_e(u)``; no shared
expert.

Departures from the published code, each with its reason:

* **The chip's share.** ``num_experts`` counts the experts held here
  (``first_expert`` .. ``first_expert + num_experts - 1``) and
  ``router_width`` all of them: the router scores and chooses over all,
  the weights are normalised over all the chosen, and the sum runs over
  the held experts only. ``vocab_size`` is this chip's slice of the rows
  of the tied embedding, and the loss is over the slice. With
  ``router_width == num_experts`` and the whole vocabulary it is the
  published model.
* **A dense loop over the experts**: every held expert is computed for
  every token and weighted by the token's weight for it, 0 where the
  token did not choose it. No sort, no grouped product.
* **The correction bias** ``b`` is a leaf no gradient is taken for (a
  buffer in the published code) and nothing moves it.
* **The weights' normalisation** adds 1e-20 to the sum of the chosen
  scores, as the program's router does; the published code is described
  as adding 1e-6 (not checkable here: the installed ``transformers`` has
  no ``lfm2_moe``), which moves a weight by under 1e-6 of itself where
  four sigmoids sum to 0.5 or more.
* **Sizes the file does not give** (the configuration's ``assumed``):
  matrices N(0, ``initializer_range``), unit gains, the convolution's
  weights uniform in ``+-1/sqrt(conv_L_cache)``; the head tied (the
  ``lfm2`` type's default; the file has no ``tie_word_embeddings``).

Float32 with matmuls at ``highest`` precision is the reference; the same
code in ``bfloat16`` is the control. The router's product and scores are
float32 in both.
"""

import functools
import math

import jax
import jax.numpy as jnp

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
FROZEN = 'router_b'             # leaves of this name take no gradient
STACKED = ('experts_gate', 'experts_up', 'experts_down')  # by expert
FUSED = {'in_w': 3}             # the short convolution's b, c and x
QUERY_BLOCK = 512               # queries whose scores are made together


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def kinds(cfg):
    """The layers' mixers, ``conv`` or ``full_attention`` a layer."""
    kinds = list(cfg['layer_types'])
    if len(kinds) != cfg['num_hidden_layers'] \
            or set(kinds) - {'conv', 'full_attention'}:
        raise ValueError(f'{kinds} are no layer_types of '
                         f'{cfg["num_hidden_layers"]} conv and '
                         'full_attention layers')
    return kinds


def is_sparse(cfg, layer):
    return layer >= cfg['num_dense_layers']


def head_dim(cfg):
    return cfg['hidden_size'] // cfg['num_attention_heads']


def leaf_specs(cfg):
    """{name: (shape, kind)}; ``l<i>/`` leads a layer's leaves. Linear
    weights are (out, in): y = x W^T. No head: it is ``embed``."""
    u, voc = cfg['hidden_size'], cfg['vocab_size']
    heads, kv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                    head_dim(cfg))
    specs = {'embed': ((voc, u), 'normal'), 'norm_f': ((u,), 'ones')}
    for i, kind in enumerate(kinds(cfg)):
        layer = {'op_norm': ((u,), 'ones'), 'ffn_norm': ((u,), 'ones')}
        if kind == 'conv':
            layer.update({'in_w': ((3 * u, u), 'normal'),
                          'conv_w': ((u, cfg['conv_L_cache']), 'conv'),
                          'out_w': ((u, u), 'normal')})
        else:
            layer.update({'q_w': ((heads * d, u), 'normal'),
                          'k_w': ((kv * d, u), 'normal'),
                          'v_w': ((kv * d, u), 'normal'),
                          'o_w': ((u, heads * d), 'normal'),
                          'q_norm': ((d,), 'ones'),
                          'k_norm': ((d,), 'ones')})
        if is_sparse(cfg, i):
            x, held = cfg['moe_intermediate_size'], cfg['num_experts']
            layer.update({
                'router_w': ((cfg['router_width'], u), 'normal'),
                'router_b': ((cfg['router_width'],), 'zeros'),
                'experts_gate': ((held, x, u), 'normal'),
                'experts_up': ((held, x, u), 'normal'),
                'experts_down': ((held, u, x), 'normal')})
        else:
            f = cfg['intermediate_size']
            layer.update({'w1': ((f, u), 'normal'), 'w3': ((f, u), 'normal'),
                          'w2': ((u, f), 'normal')})
        specs.update({f'l{i}/{k}': s for k, s in layer.items()})
    return specs


def frozen(name):
    return name.rsplit('/', 1)[-1] == FROZEN


def init_params(cfg, seed):
    """All weights on the device in one jitted call from the seed:
    N(0, initializer_range) matrices, the convolution uniform in
    +-1/sqrt(conv_L_cache), unit gains, a zero correction bias."""
    return _maker(tuple(sorted(leaf_specs(cfg).items())),
                  cfg['initializer_range'],
                  1.0 / math.sqrt(cfg['conv_L_cache']))(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _maker(specs, std, bound):
    """The jitted maker of the leaves ``specs`` ((name, (shape, kind))
    sorted by name), made once a configuration."""

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == 'normal':
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif kind == 'conv':
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -bound, bound)
            else:
                out[name] = jnp.full(shape, float(kind == 'ones'),
                                     jnp.float32)
        return out

    return jax.jit(make)


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def swiglu(u, gate_w, up_w, down_w):
    return (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T


def short_conv(lp, cfg, u):
    """The gated short convolution, a position at a time: the carry is
    the last K values of ``b x`` (B, K, U), the newest last."""
    units = cfg['hidden_size']
    b, c, x = jnp.split(u @ lp['in_w'].T, [units, 2 * units], axis=-1)
    w = lp['conv_w'].T                                  # (K, U)

    def one(window, v_t):
        window = jnp.concatenate([window[:, 1:], v_t[:, None]], axis=1)
        return window, (window * w).sum(1)

    start = jnp.zeros((u.shape[0], w.shape[0], units), u.dtype)
    _, s = jax.lax.scan(one, start, jnp.moveaxis(b * x, 1, 0))
    return (c * jnp.moveaxis(s, 0, 1)) @ lp['out_w'].T


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rotary(x, theta):
    """(B, T, heads, d), HuggingFace's rotate-half convention."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]    # (T, 1, d)
    xf = x.astype(jnp.float32)
    return (xf * jnp.cos(ang) + rotate_half(xf) * jnp.sin(ang)) \
        .astype(x.dtype)


def attention(lp, cfg, u):
    heads, kv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                    head_dim(cfg))
    eps, theta = cfg['norm_eps'], float(cfg['rope_parameters']['rope_theta'])
    b, t, _ = u.shape
    q = rotary(rms_norm((u @ lp['q_w'].T).reshape(b, t, heads, d),
                        lp['q_norm'], eps), theta)
    k = rotary(rms_norm((u @ lp['k_w'].T).reshape(b, t, kv, d),
                        lp['k_norm'], eps), theta)
    v = (u @ lp['v_w'].T).reshape(b, t, kv, d)
    # query head i reads key/value head i // (heads / kv)
    q = q.reshape(b, t, kv, heads // kv, d)
    block = min(QUERY_BLOCK, t)
    blocks = -(-t // block)
    q = jnp.pad(q, ((0, 0), (0, blocks * block - t), (0, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def one(args):
        qb, first = args
        s = jnp.einsum('bqgrd,bkgd->bgrqk', qb, k) / math.sqrt(d)
        seen = first + jnp.arange(block)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(seen, s, jnp.finfo(s.dtype).min)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(u.dtype)
        return jnp.einsum('bgrqk,bkgd->bqgrd', p, v)

    out = jax.lax.map(one, (jnp.moveaxis(q.reshape(
        b, blocks, block, kv, heads // kv, d), 1, 0),
        jnp.arange(blocks) * block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, blocks * block, heads * d)
    return out[:, :t] @ lp['o_w'].T


def expert_weights(lp, cfg, u):
    """(B, T, router_width): a token's weight for each expert, 0 for the
    ones it did not choose. Float32, as the published router."""
    s = jax.nn.sigmoid(u.astype(jnp.float32)
                       @ lp['router_w'].astype(jnp.float32).T)
    _, chosen = jax.lax.top_k(s + lp['router_b'].astype(jnp.float32),
                              cfg['num_experts_per_tok'])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get('norm_topk_prob', True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * cfg['routed_scaling_factor']
    onehot = chosen[..., None] == jnp.arange(cfg['router_width'])
    return (picked[..., None] * onehot).sum(-2)


def sparse_ffn(lp, cfg, u):
    """The held experts' part of the layer."""
    first = cfg.get('first_expert', 0)
    w = expert_weights(lp, cfg, u)[..., first:first + cfg['num_experts']]

    def one(acc, e):
        gate_w, up_w, down_w, w_e = e
        y = swiglu(u, gate_w, up_w, down_w)
        return acc + w_e[..., None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (lp['experts_gate'], lp['experts_up'], lp['experts_down'],
         jnp.moveaxis(w, -1, 0)))
    return out


def layer_params(p, i):
    lead = f'l{i}/'
    return {k[len(lead):]: a for k, a in p.items() if k.startswith(lead)}


def layer(x, lp, cfg, kind, sparse):
    eps = cfg['norm_eps']
    mixer = short_conv if kind == 'conv' else attention
    h = x + mixer(lp, cfg, rms_norm(x, lp['op_norm'], eps))
    u = rms_norm(h, lp['ffn_norm'], eps)
    if sparse:
        return h + sparse_ffn(lp, cfg, u)
    return h + swiglu(u, lp['w1'], lp['w3'], lp['w2'])


def hidden_of(p, cfg, tokens):
    """(B, T) ids -> (B, T, U) after the final norm. Each layer is
    recomputed in the backward pass."""
    x = p['embed'][tokens]
    for i, kind in enumerate(kinds(cfg)):
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, kind=kind, sparse=is_sparse(cfg, i)))(
            x, layer_params(p, i))
    return rms_norm(x, p['norm_f'], cfg['norm_eps'])


def logits_of(p, cfg, tokens):
    return hidden_of(p, cfg, tokens) @ p['embed'].T


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
    stacked over the experts, and one a block of a fused projection."""
    out = {}
    for name, a in tree.items():
        a = a.astype(jnp.float32)
        tail = name.rsplit('/', 1)[-1]
        if tail in STACKED or tail in FUSED:
            parts = FUSED.get(tail, a.shape[0])
            out[name] = jnp.sqrt(jnp.square(a).reshape(parts, -1).sum(-1))
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
