"""The plain reference for the ``deepseek_v3`` family: a decoder with
multi-head latent attention and sparse experts (DeepSeek-V3, Kanana-2 and
kin), its next-token loss, its gradients and Adam, in straightforward
``jax.numpy``. Written from the published ``config.json`` keys and the
published modelling code's equations; it imports nothing of ``mxnet_tpu``
and makes the weights itself, from the seed.

A layer, for a token's hidden vector x (``n``: RMSNorm, ``u``: the normed
input of the sub-layer)::

    h = x + MLA(n(x))            y = h + FFN(n(h))

MLA, head i::

    [q_nope_i; q_pe_i] = W_q u        [c; k_pe] = W_kva u
    [k_nope_i; v_i]    = W_kvb n(c)
    rotary on q_pe_i and on the one k_pe that all heads share
    scores (q_nope_i . k_nope_i + q_pe_i . k_pe) / sqrt(qk_head_dim),
    causal softmax, o = W_o [P_i v_i]

FFN of the first ``first_k_dense_replace`` layers: ``W_d (silu(W_g u) *
W_u u)``. FFN of the others: ``s = sigmoid(W_r u)`` over all
``router_width`` experts; the ``num_experts_per_tok`` largest of ``s + b``
are chosen; ``w_e = routed_scaling_factor * s_e / (sum of the chosen s +
1e-20)``; ``sum_e w_e SwiGLU_e(u)`` plus one SwiGLU ``n_shared_experts``
times as wide.

Departures from the published code, each with its reason:

* **The chip's share.** ``n_routed_experts`` counts the experts held here
  (``first_expert`` .. ``first_expert + n_routed_experts - 1``) and
  ``router_width`` all of them: the router scores and chooses over all,
  the weights are normalised over all the chosen, and the sum runs over
  the held experts only; what the absent experts would add is another
  chip's. ``vocab_size`` is this chip's slice of the rows of the
  embedding and of the head, and the loss is over the slice. With
  ``router_width == n_routed_experts`` and the whole vocabulary it is the
  published model.
* **A dense loop over the experts.** Every held expert is computed for
  every token and weighted by the token's weight for it, 0 where the
  token did not choose it: the same sum as the published gather and
  scatter, with no sort and no grouped product, so nothing here is shared
  with the code under test.
* **Rotary pairs.** The published code (``rope_interleave: true``) moves
  the pairs (2j, 2j + 1) of ``q_pe`` and ``k_pe`` to the half-split
  layout and rotates halves; here each pair is rotated in place. Queries
  and keys get the same fixed permutation of columns there, so every
  score is the same number.
* **The correction bias** ``b`` is a leaf no gradient is taken for, as in
  the published code (a buffer), and nothing moves it: no balance update,
  no auxiliary loss.
* ``n_group = topk_group = 1``, so the grouped choice is the plain top-k;
  no query compression (``q_lora_rank`` null); no dropout.

Float32 with matmuls at ``highest`` precision is the reference; the same
code in ``bfloat16`` is the control. The router's product and scores are
float32 in both, as the published gate computes them.
"""

import functools
import math

import jax
import jax.numpy as jnp

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
FROZEN = 'router_b'             # leaves of this name take no gradient
STACKED = ('experts_gate', 'experts_up', 'experts_down')  # by expert


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def is_sparse(cfg, layer):
    return (cfg['n_routed_experts'] > 0
            and layer >= cfg['first_k_dense_replace']
            and layer % cfg['moe_layer_freq'] == 0)


def leaf_specs(cfg):
    """{name: (shape, kind)}; ``l<i>/`` leads a layer's leaves. Linear
    weights are (out, in): y = x W^T; there are no biases."""
    u, v = cfg['hidden_size'], cfg['vocab_size']
    heads = cfg['num_attention_heads']
    nope, pe = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, latent = cfg['v_head_dim'], cfg['kv_lora_rank']
    x, held = cfg['moe_intermediate_size'], cfg['n_routed_experts']
    shared = cfg['n_shared_experts'] * x
    specs = {'embed': ((v, u), 'normal'), 'norm': ((u,), 'ones'),
             'head': ((v, u), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        layer = {
            'ln1': ((u,), 'ones'),
            'q_w': ((heads * (nope + pe), u), 'normal'),
            'kva_w': ((latent + pe, u), 'normal'),
            'kva_ln': ((latent,), 'ones'),
            'kvb_w': ((heads * (nope + vd), latent), 'normal'),
            'o_w': ((u, heads * vd), 'normal'),
            'ln2': ((u,), 'ones'),
        }
        if is_sparse(cfg, i):
            layer.update({
                'router_w': ((cfg['router_width'], u), 'normal'),
                'router_b': ((cfg['router_width'],), 'zeros'),
                'experts_gate': ((held, x, u), 'normal'),
                'experts_up': ((held, x, u), 'normal'),
                'experts_down': ((held, u, x), 'normal'),
                'shared_gate': ((shared, u), 'normal'),
                'shared_up': ((shared, u), 'normal'),
                'shared_down': ((u, shared), 'normal'),
            })
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
    N(0, initializer_range) matrices, unit gains, a zero bias."""
    specs = leaf_specs(cfg)
    std = cfg['initializer_range']

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items())):
            if kind == 'normal':
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, float(kind == 'ones'),
                                     jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rotary(x, theta):
    """(B, T, H, d): the pair (2j, 2j + 1) of position t turned by
    t * theta^(-2j / d)."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a = x[..., 0::2].astype(jnp.float32)
    b = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def swiglu(u, gate_w, up_w, down_w):
    return (jax.nn.silu(u @ gate_w.T) * (u @ up_w.T)) @ down_w.T


def attention(lp, cfg, u):
    heads = cfg['num_attention_heads']
    nope, pe = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, latent = cfg['v_head_dim'], cfg['kv_lora_rank']
    theta, eps = float(cfg['rope_theta']), cfg['rms_norm_eps']
    b, t, _ = u.shape
    q = (u @ lp['q_w'].T).reshape(b, t, heads, nope + pe)
    kva = u @ lp['kva_w'].T
    kv = rms_norm(kva[..., :latent], lp['kva_ln'], eps) @ lp['kvb_w'].T
    kv = kv.reshape(b, t, heads, nope + vd)
    q_pe = rotary(q[..., nope:], theta)
    k_pe = rotary(kva[..., latent:].reshape(b, t, 1, pe), theta)
    s = jnp.einsum('bqnd,bknd->bnqk', q[..., :nope], kv[..., :nope]) \
        + jnp.einsum('bqnd,bkd->bnqk', q_pe, k_pe[:, :, 0])
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
    if cfg.get('scoring_func', 'sigmoid') != 'sigmoid':
        raise NotImplementedError(cfg['scoring_func'])
    _, chosen = jax.lax.top_k(s + lp['router_b'].astype(jnp.float32),
                              cfg['num_experts_per_tok'])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get('norm_topk_prob', True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * cfg['routed_scaling_factor']
    onehot = chosen[..., None] == jnp.arange(cfg['router_width'])
    return (picked[..., None] * onehot).sum(-2)


def sparse_ffn(lp, cfg, u):
    first = cfg.get('first_expert', 0)
    held = cfg['n_routed_experts']
    w = expert_weights(lp, cfg, u)[..., first:first + held]

    def one(acc, e):
        gate_w, up_w, down_w, w_e = e
        y = swiglu(u, gate_w, up_w, down_w)
        return acc + w_e[..., None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (lp['experts_gate'], lp['experts_up'], lp['experts_down'],
         jnp.moveaxis(w, -1, 0)))
    return out + swiglu(u, lp['shared_gate'], lp['shared_up'],
                        lp['shared_down'])


def layer_params(p, i):
    lead = f'l{i}/'
    return {k[len(lead):]: a for k, a in p.items() if k.startswith(lead)}


def layer(x, lp, cfg, sparse):
    eps = cfg['rms_norm_eps']
    h = x + attention(lp, cfg, rms_norm(x, lp['ln1'], eps))
    u = rms_norm(h, lp['ln2'], eps)
    if sparse:
        return h + sparse_ffn(lp, cfg, u)
    return h + swiglu(u, lp['gate_w'], lp['up_w'], lp['down_w'])


def hidden_of(p, cfg, tokens):
    """(B, T) ids -> (B, T, U) after the final norm. Each layer is
    recomputed in the backward pass."""
    x = p['embed'][tokens]
    for i in range(cfg['num_hidden_layers']):
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, sparse=is_sparse(cfg, i)))(x, layer_params(p, i))
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
