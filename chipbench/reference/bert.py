"""The plain reference for the ``bert`` family: BERT as the paper gives it
(Devlin et al. 2018, arXiv:1810.04805), its two training losses, their
gradients and Adam, in straightforward ``jax.numpy``.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made.
The benchmark makes the weights here, from the seed, and hands a copy to
the program (``families/bert.py`` knows the program's names for them);
after the window the reference makes them again for itself.

Float32 with matmuls at ``highest`` precision is the reference. The same
code in ``bfloat16`` throughout is the control that ``correct`` has to
fail (``follow(..., dtype='bfloat16')``).

Departures from the paper, each because the program's zoo model does the
same: the Q, K and V projections are one matrix of 3 x hidden rows;
Adam has no weight decay and no warm-up (the cells state a constant
rate); dropout is 0 (the configurations say why).

So that a step of BERT-large fits one chip beside its Adam state, a step
takes its rows in blocks and sums the blocks' gradients, and each layer
is recomputed in the backward pass (``jax.checkpoint``): neither changes
the result beyond summation order.
"""

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = {
    # name: (shape as a function of (U, H), kind)
    'qkv_w': (lambda u, h: (3 * u, u), 'normal'),
    'qkv_b': (lambda u, h: (3 * u,), 'zeros'),
    'proj_w': (lambda u, h: (u, u), 'normal'),
    'proj_b': (lambda u, h: (u,), 'zeros'),
    'ln1_g': (lambda u, h: (u,), 'ones'),
    'ln1_b': (lambda u, h: (u,), 'zeros'),
    'ffn1_w': (lambda u, h: (h, u), 'normal'),
    'ffn1_b': (lambda u, h: (h,), 'zeros'),
    'ffn2_w': (lambda u, h: (u, h), 'normal'),
    'ffn2_b': (lambda u, h: (u,), 'zeros'),
    'ln2_g': (lambda u, h: (u,), 'ones'),
    'ln2_b': (lambda u, h: (u,), 'zeros'),
}

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_specs(cfg, job):
    """{name: (shape, kind)}; a name under ``layers/`` is stacked over the
    layers. Linear weights are (out, in), as the paper's y = xW^T + b."""
    u, h = cfg['hidden_size'], cfg['intermediate_size']
    n = cfg['num_hidden_layers']
    specs = {
        'word': ((cfg['vocab_size'], u), 'normal'),
        'type': ((cfg['type_vocab_size'], u), 'normal'),
        'pos': ((cfg['max_position_embeddings'], u), 'normal'),
        'emb_ln_g': ((u,), 'ones'), 'emb_ln_b': ((u,), 'zeros'),
        'pooler_w': ((u, u), 'normal'), 'pooler_b': ((u,), 'zeros'),
    }
    for name, (shape, kind) in LAYER_LEAVES.items():
        specs['layers/' + name] = ((n,) + shape(u, h), kind)
    if job['kind'] == 'classify':
        specs['head_w'] = ((job['num_classes'], u), 'normal')
        specs['head_b'] = ((job['num_classes'],), 'zeros')
    elif job['kind'] == 'mlm_nsp':
        specs.update({
            'dec_w': ((u, u), 'normal'), 'dec_b': ((u,), 'zeros'),
            'dec_ln_g': ((u,), 'ones'), 'dec_ln_b': ((u,), 'zeros'),
            'dec_bias': ((cfg['vocab_size'],), 'zeros'),
            'nsp_w': ((2, u), 'normal'), 'nsp_b': ((2,), 'zeros'),
        })
    else:
        raise ValueError(f'unknown job kind {job["kind"]!r}')
    return specs


def init_params(cfg, job, seed):
    """All weights on the device in one jitted call from the seed:
    N(0, initializer_range) matrices and embeddings, zero biases, unit
    LayerNorm gains (the paper's initialisation)."""
    specs = leaf_specs(cfg, job)
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


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def linear(x, w, b):
    return x @ w.T + b


def encode(p, cfg, tokens, types, valid_length):
    """(B, T) ids -> (B, T, U). ``valid_length`` (B,) masks the keys
    beyond each row's length; None attends everywhere."""
    eps = cfg['layer_norm_eps']
    heads = cfg['num_attention_heads']
    b, t = tokens.shape
    x = p['word'][tokens] + p['type'][types] + p['pos'][:t][None]
    x = layer_norm(x, p['emb_ln_g'], p['emb_ln_b'], eps)
    u = x.shape[-1]
    dh = u // heads
    keep = None
    if valid_length is not None:
        keep = (jnp.arange(t)[None, :] < valid_length[:, None])
        keep = keep[:, None, None, :]

    @jax.checkpoint
    def layer(x, lp):
        qkv = linear(x, lp['qkv_w'], lp['qkv_b'])
        q, k, v = (a.reshape(b, t, heads, dh)
                   for a in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum('bqnd,bknd->bnqk', q, k) / math.sqrt(dh)
        if keep is not None:
            s = jnp.where(keep, s, jnp.finfo(s.dtype).min)
        a = jnp.einsum('bnqk,bknd->bqnd', jax.nn.softmax(s, axis=-1), v)
        a = linear(a.reshape(b, t, u), lp['proj_w'], lp['proj_b'])
        x = layer_norm(x + a, lp['ln1_g'], lp['ln1_b'], eps)
        h = linear(gelu(linear(x, lp['ffn1_w'], lp['ffn1_b'])),
                   lp['ffn2_w'], lp['ffn2_b'])
        return layer_norm(x + h, lp['ln2_g'], lp['ln2_b'], eps), None

    layers = {k[len('layers/'):]: v for k, v in p.items()
              if k.startswith('layers/')}
    x, _ = jax.lax.scan(layer, x, layers)
    return x


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss_fn(p, cfg, job, batch):
    """Mean loss over the rows of ``batch`` (a dict of arrays)."""
    seq = encode(p, cfg, batch['tokens'], batch['types'],
                 batch.get('valid_length'))
    pooled = jnp.tanh(linear(seq[:, 0], p['pooler_w'], p['pooler_b']))
    if job['kind'] == 'classify':
        logits = linear(pooled, p['head_w'], p['head_b'])
        return cross_entropy(logits, batch['labels']).mean()
    # masked LM at the predicted positions + next-sentence prediction
    rows = jnp.arange(seq.shape[0])[:, None]
    h = seq[rows, batch['mlm_positions']]                  # (B, P, U)
    h = layer_norm(gelu(linear(h, p['dec_w'], p['dec_b'])),
                   p['dec_ln_g'], p['dec_ln_b'], cfg['layer_norm_eps'])
    logits = h @ p['word'].T + p['dec_bias']               # tied
    mlm = cross_entropy(logits, batch['mlm_labels']).mean()
    nsp = cross_entropy(linear(pooled, p['nsp_w'], p['nsp_b']),
                        batch['nsp_labels']).mean()
    return mlm + nsp


FUSED = {'layers/qkv_b': 3}     # leaves read as their equal parts


def leaf_norms(tree):
    """{name: norm} in float32: one a layer for a stacked leaf, and one
    for each of Q, K and V in the fused bias (the key's bias has no
    gradient under softmax; the other two have)."""
    out = {}
    for name, a in tree.items():
        a = a.astype(jnp.float32)
        if name in FUSED:
            out[name] = jnp.sqrt(jnp.square(a).reshape(
                a.shape[0], FUSED[name], -1).sum(-1))
        elif name.startswith('layers/'):
            out[name] = jnp.sqrt(jnp.square(a).reshape(a.shape[0], -1)
                                 .sum(-1))
        else:
            out[name] = jnp.sqrt(jnp.square(a).sum())
    return out


def make_step(cfg, job, lr, block_rows):
    """One jitted Adam step over a batch taken in blocks of rows."""

    def step(p, m, v, t, batch):
        rows = batch['tokens'].shape[0]
        n_blocks = max(1, rows // block_rows)
        blocks = {k: a.reshape((n_blocks, rows // n_blocks) + a.shape[1:])
                  for k, a in batch.items()}

        def one(carry, blk):
            loss, g = jax.value_and_grad(loss_fn)(p, cfg, job, blk)
            acc_l, acc_g = carry
            return (acc_l + loss.astype(jnp.float32),
                    jax.tree.map(jnp.add, acc_g, g)), None

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

    return jax.jit(step, donate_argnums=(0, 1, 2))


def follow(cfg, job, seed, batches, lr, dtype='float32', block_rows=8):
    """Run the first ``len(batches)`` training steps from the seed.

    Returns host numbers: ``losses`` (one a step), ``grad_norms`` (step
    1's gradient, by leaf) and ``change_norms`` (parameters after the
    last step minus the initial ones, by leaf).
    """
    precision = 'highest' if dtype == 'float32' else 'default'
    with jax.default_matmul_precision(precision):
        p = jax.tree.map(lambda a: a.astype(dtype),
                         init_params(cfg, job, seed))
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        step = make_step(cfg, job, lr, block_rows)
        losses, grad_norms = [], None
        for t, batch in enumerate(batches, 1):
            batch = {k: jnp.asarray(a) for k, a in batch.items()}
            p, m, v, loss, gn = step(p, m, v, jnp.int32(t), batch)
            losses.append(float(loss))
            if t == 1:
                grad_norms = jax.device_get(gn)
        del m, v
        # against the initial weights as this precision holds them: the
        # change is the optimizer's, not the cast's
        first = jax.tree.map(lambda a: a.astype(dtype),
                             init_params(cfg, job, seed))
        change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))(p, first)
        return {'losses': losses, 'grad_norms': grad_norms,
                'change_norms': jax.device_get(change)}
