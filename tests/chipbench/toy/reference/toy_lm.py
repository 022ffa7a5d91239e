"""The plain reference for the tests' second family, ``toy_lm``: a
decoder of two pre-norm blocks (causal attention, then a dense mixture
of experts) with an untied head, its next-token loss, its gradients and
Adam, in straightforward ``jax.numpy``. As ``chipbench/reference/`` asks
of a family's: it imports nothing of ``mxnet_tpu`` and makes the weights
itself, from the seed.

A block, for a token's hidden vector x (``n``: LayerNorm)::

    h = x + W_o attention(W_qkv n1(x))           causal, all heads
    u = n2(h)
    g = softmax(W_r u + b_r)                      over the experts
    y = h + sum_e g_e W_out,e silu(W_in,e u)

``b_r`` is a leaf the optimizer never moves (the program holds it with
``grad_req='null'``): it is in the weights and in no gradient, no slot
and no norm. ``W_in`` and ``W_out`` are one leaf each, stacked over the
experts, and :func:`leaf_norms` reads them an expert at a time.

Float32 with matmuls at ``highest`` precision is the reference; the same
code in ``bfloat16`` is the control.
"""

import math

import jax
import jax.numpy as jnp

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
FROZEN = 'router_b'             # leaves of this name take no gradient
STACKED = ('experts_in', 'experts_out')     # read an expert at a time


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_specs(cfg):
    """{name: (shape, kind)}; ``l<i>/`` leads a block's leaves. Linear
    weights are (out, in): y = x W^T + b."""
    u, x = cfg['hidden_size'], cfg['expert_size']
    e, v = cfg['num_experts'], cfg['vocab_size']
    specs = {'embed': ((v, u), 'normal'),
             'ln_f_g': ((u,), 'ones'), 'ln_f_b': ((u,), 'zeros'),
             'head_w': ((v, u), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        specs.update({f'l{i}/{k}': s for k, s in {
            'ln1_g': ((u,), 'ones'), 'ln1_b': ((u,), 'zeros'),
            'qkv_w': ((3 * u, u), 'normal'), 'qkv_b': ((3 * u,), 'zeros'),
            'proj_w': ((u, u), 'normal'), 'proj_b': ((u,), 'zeros'),
            'ln2_g': ((u,), 'ones'), 'ln2_b': ((u,), 'zeros'),
            'router_w': ((e, u), 'normal'), 'router_b': ((e,), 'normal'),
            'experts_in': ((e, x, u), 'normal'),
            'experts_out': ((e, u, x), 'normal'),
        }.items()})
    return specs


def frozen(name):
    return name.rsplit('/', 1)[-1] == FROZEN


def init_params(cfg, seed):
    """All weights on the device in one jitted call from the seed."""
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


def layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def logits_of(p, cfg, tokens):
    """(B, T) ids -> (B, T, V)."""
    eps, heads = cfg['layer_norm_eps'], cfg['num_attention_heads']
    b, t = tokens.shape
    x = p['embed'][tokens]
    u = x.shape[-1]
    dh = u // heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg['num_hidden_layers']):
        lp = {k.split('/', 1)[1]: a for k, a in p.items()
              if k.startswith(f'l{i}/')}
        qkv = layer_norm(x, lp['ln1_g'], lp['ln1_b'], eps) \
            @ lp['qkv_w'].T + lp['qkv_b']
        q, k, v = (a.reshape(b, t, heads, dh)
                   for a in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum('bqnd,bknd->bnqk', q, k) / math.sqrt(dh)
        s = jnp.where(causal, s, jnp.finfo(s.dtype).min)
        a = jnp.einsum('bnqk,bknd->bqnd', jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(b, t, u) @ lp['proj_w'].T + lp['proj_b']
        n = layer_norm(x, lp['ln2_g'], lp['ln2_b'], eps)
        gate = jax.nn.softmax(n @ lp['router_w'].T + lp['router_b'], -1)
        act = jax.nn.silu(jnp.einsum('btu,exu->btex', n, lp['experts_in']))
        x = x + jnp.einsum('btex,eux,bte->btu', act, lp['experts_out'],
                           gate)
    x = layer_norm(x, p['ln_f_g'], p['ln_f_b'], eps)
    return x @ p['head_w'].T


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

    return jax.jit(step)


def follow(cfg, seed, batches, lr, dtype='float32', block_rows=8):
    """Run the first ``len(batches)`` training steps from the seed.
    Returns host numbers: ``losses`` (one a step), ``grad_norms`` (step
    1's gradient, by leaf) and ``change_norms`` (the leaves after the
    last step less the initial ones), the leaves the optimizer moves."""
    precision = 'highest' if dtype == 'float32' else 'default'
    with jax.default_matmul_precision(precision):
        first, held = split(jax.tree.map(lambda a: a.astype(dtype),
                                         init_params(cfg, seed)))
        p = first
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
        change = leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            p, first))
        return {'losses': losses, 'grad_norms': grad_norms,
                'change_norms': jax.device_get(change)}
