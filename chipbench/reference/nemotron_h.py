"""The plain reference for the ``nemotron_h`` family: a decoder whose
layers are of three kinds (Mamba-2 mixers, sparse experts, grouped-query
attention; Nemotron-H and kin), its next-token loss, its gradients and
Adam, in straightforward ``jax.numpy``. Written from the published
``config.json`` keys and the model type's equations; it imports nothing
of ``mxnet_tpu`` and makes the weights itself, from the seed.

``hybrid_override_pattern`` gives a layer's kind, a character a layer:
``M`` a Mamba-2 mixer, ``E`` sparse experts, ``*`` attention, ``-`` the
family's dense MLP. Every layer is ``x <- x + mixer(n(x))`` with one
RMSNorm ``n`` (``x rsqrt(mean(x^2) + eps) w``); after the last layer
``norm_f``, then the untied head. No weight has a bias but the
convolution. U = ``hidden_size``.

``M``, H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, d = H P,
G = ``n_groups``, N = ``ssm_state_size``, K = ``conv_kernel``::

    [z (d); xBC (d + 2 G N); dt (H)] = W_in u
    xBC = silu(conv(xBC))    depthwise, causal (K - 1 zeros on the left)
    [x (H x P); B (G x N); C (G x N)] = xBC     head h reads group h // (H/G)
    delta = softplus(dt + dt_bias)              A = -exp(A_log)
    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t      S_0 = 0 a row
    y_t = S_t C_t + D x_t
    out = W_out GroupRMSNorm(y silu(z))   groups of d / G, gate first

The recurrence is computed **position by position** (``lax.scan`` over
single positions, the state (H, P, N) its carry), not in chunks: the
program's chunked form shares nothing with it. The scan is taken in
segments whose inner steps are made again in the backward pass, so that
what is kept is a state a segment and not a state a position.

``E``: ``s = sigmoid(W_r u)`` over all ``router_width`` experts, float32;
the ``num_experts_per_tok`` largest of ``s + b`` are chosen; ``w_e =
routed_scaling_factor s_e / (sum of the chosen s + 1e-20)``; ``sum_e w_e
W_down,e relu(W_up,e u)^2`` (no gate) plus one shared expert of the same
form, ``moe_shared_expert_intermediate_size`` wide.

``*``: ``q`` of ``num_attention_heads`` heads, ``k`` and ``v`` of
``num_key_value_heads``, all ``head_dim`` wide; causal softmax attention
at ``1 / sqrt(head_dim)``, a key/value head shared by heads/kv_heads
query heads; ``W_o``. **No rotary embedding**: the model type's attention
applies none (the state-space layers carry position).

``-``: ``W_down relu(W_up u)^2``, ``intermediate_size`` wide.

Departures from the published code, each with its reason:

* **The chip's share.** ``n_routed_experts`` counts the experts held here
  (``first_expert`` .. ``first_expert + n_routed_experts - 1``) and
  ``router_width`` all of them: the router scores and chooses over all,
  the weights are normalised over all the chosen, and the sum runs over
  the held experts only. ``vocab_size`` is this chip's slice of the rows
  of the embedding and of the head, and the loss is over the slice. With
  ``router_width == n_routed_experts`` and the whole vocabulary it is the
  published tower.
* **A dense loop over the experts**: every held expert is computed for
  every token and weighted by the token's weight for it, 0 where the
  token did not choose it. No sort, no grouped product.
* **The correction bias** ``b`` is a leaf no gradient is taken for (a
  buffer in the published code) and nothing moves it.
* ``n_group = topk_group = 1``; ``time_step_limit`` (0, inf) clips
  nothing; no dropout; the second (denoiser) tower of
  Nemotron-Labs-TwoTower is not here: the published file has no key for
  it.

Float32 with matmuls at ``highest`` precision is the reference; the same
code in ``bfloat16`` is the control. The router's product and scores, and
the scan's step sizes and decays, are float32 in both.
"""

import functools
import math

import jax
import jax.numpy as jnp

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
FROZEN = 'router_b'             # leaves of this name take no gradient
STACKED = ('experts_up', 'experts_down')    # by expert
SEGMENT = 64                    # positions of the scan recomputed together
# the projections back onto the residual stream: divided by
# sqrt(residual_rescale_layers) at the start (rescale_prenorm_residual)
ONTO_RESIDUAL = ('out_w', 'o_w', 'experts_down', 'shared_down', 'down_w')


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def kinds(cfg):
    """The layers' kinds, a character a layer."""
    pattern = cfg['hybrid_override_pattern']
    if len(pattern) != cfg['num_hidden_layers'] or set(pattern) - set('ME*-'):
        raise ValueError(f'{pattern!r} is no pattern of '
                         f'{cfg["num_hidden_layers"]} layers of M, E, *, -')
    return pattern


def mamba_sizes(cfg):
    """(heads, head width, groups, state, d, conv_dim)."""
    h, p = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    g, n = cfg['n_groups'], cfg['ssm_state_size']
    return h, p, g, n, h * p, h * p + 2 * g * n


def leaf_specs(cfg):
    """{name: (shape, kind)}; ``l<i>/`` leads a layer's leaves. Linear
    weights are (out, in): y = x W^T."""
    u, v = cfg['hidden_size'], cfg['vocab_size']
    specs = {'embed': ((v, u), 'normal'), 'norm_f': ((u,), 'ones'),
             'head': ((v, u), 'normal')}
    for i, kind in enumerate(kinds(cfg)):
        layer = {'norm': ((u,), 'ones')}
        if kind == 'M':
            h, _, _, _, d, conv = mamba_sizes(cfg)
            layer.update({
                'in_w': ((d + conv + h, u), 'normal'),
                'conv_w': ((conv, cfg['conv_kernel']), 'conv'),
                'conv_b': ((conv,), 'conv'),
                'dt_bias': ((h,), 'dt_bias'),
                'A_log': ((h,), 'A_log'),
                'D': ((h,), 'ones'),
                'gate_norm': ((d,), 'ones'),
                'out_w': ((u, d), 'normal')})
        elif kind == 'E':
            x, held = cfg['moe_intermediate_size'], cfg['n_routed_experts']
            shared = cfg['n_shared_experts'] \
                * cfg['moe_shared_expert_intermediate_size']
            layer.update({
                'router_w': ((cfg['router_width'], u), 'normal'),
                'router_b': ((cfg['router_width'],), 'zeros'),
                'experts_up': ((held, x, u), 'normal'),
                'experts_down': ((held, u, x), 'normal'),
                'shared_up': ((shared, u), 'normal'),
                'shared_down': ((u, shared), 'normal')})
        elif kind == '*':
            heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
            hd = cfg['head_dim']
            layer.update({
                'q_w': ((heads * hd, u), 'normal'),
                'k_w': ((kv * hd, u), 'normal'),
                'v_w': ((kv * hd, u), 'normal'),
                'o_w': ((u, heads * hd), 'normal')})
        else:
            f = cfg['intermediate_size']
            layer.update({'up_w': ((f, u), 'normal'),
                          'down_w': ((u, f), 'normal')})
        specs.update({f'l{i}/{k}': s for k, s in layer.items()})
    return specs


def frozen(name):
    return name.rsplit('/', 1)[-1] == FROZEN


def init_params(cfg, seed):
    """All weights on the device in one jitted call from the seed, by the
    model type's own initialisers: N(0, initializer_range) matrices, the
    ones onto the residual stream divided by sqrt(residual_rescale_layers);
    unit gains and ``D``; ``A_log = log(1..H)``; ``dt_bias`` the inverse
    softplus of a log-uniform draw in [time_step_min, time_step_max]
    floored at time_step_floor; the convolution's weight and bias uniform
    in +-1/sqrt(conv_kernel); a zero correction bias."""
    specs = leaf_specs(cfg)
    std = cfg['initializer_range']
    rescale = 1.0 / math.sqrt(cfg['residual_rescale_layers']) \
        if cfg.get('rescale_prenorm_residual', True) else 1.0
    lo, hi = math.log(cfg['time_step_min']), math.log(cfg['time_step_max'])
    bound = 1.0 / math.sqrt(cfg['conv_kernel'])

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            if kind == 'normal':
                a = std * jax.random.normal(k, shape, jnp.float32)
                if name.rsplit('/', 1)[-1] in ONTO_RESIDUAL:
                    a = a * rescale
            elif kind == 'conv':
                a = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
            elif kind == 'dt_bias':
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                lo, hi))
                dt = jnp.maximum(dt, cfg['time_step_floor'])
                a = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == 'A_log':
                a = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
            else:
                a = jnp.full(shape, float(kind == 'ones'), jnp.float32)
            out[name] = a
        return out

    return jax.jit(make)(seed_key(seed))


def rms_norm(x, g, eps, groups=1):
    """RMSNorm over the last axis, or over each of ``groups`` equal parts
    of it."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], groups, -1)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return (y.reshape(x.shape) * g.astype(jnp.float32)).astype(x.dtype)


def relu2(u, up_w, down_w):
    return jnp.square(jax.nn.relu(u @ up_w.T)) @ down_w.T


def causal_conv(x, w, b):
    """(B, T, C) by (C, K): tap K - 1 on the position itself, tap k on the
    one K - 1 - k before it; K shifted multiply-adds."""
    t, taps = x.shape[1], w.shape[1]
    out = jnp.broadcast_to(b, x.shape)
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        out = out + shifted * w[:, k]
    return out


def recurrence(x, dt, a, b, c, d):
    """x (B, T, H, P), dt (B, T, H) float32, a (H,) float32, b and c
    (B, T, G, N), d (H,) -> y (B, T, H, P), a position at a time."""
    batch, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    per = h // g
    decay = jnp.exp(dt * a).astype(x.dtype)                 # (B, T, H)
    dx = x * dt[..., None].astype(x.dtype)
    bh = jnp.repeat(b, per, axis=2)                         # (B, T, H, N)
    ch = jnp.repeat(c, per, axis=2)

    def one(state, at):
        decay_t, dx_t, b_t, c_t = at
        state = state * decay_t[..., None, None] \
            + dx_t[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum('bhpn,bhn->bhp', state, c_t)

    @jax.checkpoint
    def segment(state, part):
        return jax.lax.scan(one, state, part)

    seg = max(s for s in range(1, SEGMENT + 1) if t % s == 0)
    by_time = lambda arr: jnp.moveaxis(arr, 1, 0).reshape(
        t // seg, seg, *arr.shape[:1], *arr.shape[2:])
    _, y = jax.lax.scan(segment, jnp.zeros((batch, h, p, n), x.dtype),
                        tuple(by_time(arr) for arr in (decay, dx, bh, ch)))
    y = jnp.moveaxis(y.reshape(t, batch, h, p), 0, 1)
    return y + x * d[:, None].astype(x.dtype)


def mamba(lp, cfg, u):
    h, p, g, n, d, conv = mamba_sizes(cfg)
    batch, t, _ = u.shape
    zxbcdt = u @ lp['in_w'].T
    z, xbc, dt = jnp.split(zxbcdt, [d, d + conv], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, lp['conv_w'], lp['conv_b']))
    x, b, c = jnp.split(xbc, [d, d + g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lp['dt_bias'].astype(jnp.float32))
    a = -jnp.exp(lp['A_log'].astype(jnp.float32))
    y = recurrence(x.reshape(batch, t, h, p), dt, a,
                   b.reshape(batch, t, g, n), c.reshape(batch, t, g, n),
                   lp['D'])
    y = y.reshape(batch, t, d) * jax.nn.silu(z)
    y = rms_norm(y, lp['gate_norm'], cfg['layer_norm_epsilon'], groups=g)
    return y @ lp['out_w'].T


def attention(lp, cfg, u):
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    hd = cfg['head_dim']
    b, t, _ = u.shape
    # query head j reads key/value head j // (heads / kv)
    q = (u @ lp['q_w'].T).reshape(b, t, kv, heads // kv, hd)
    k = (u @ lp['k_w'].T).reshape(b, t, kv, hd)
    v = (u @ lp['v_w'].T).reshape(b, t, kv, hd)
    s = jnp.einsum('bqgrd,bkgd->bgrqk', q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s,
                  jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(u.dtype)
    a = jnp.einsum('bgrqk,bkgd->bqgrd', p, v)
    return a.reshape(b, t, heads * hd) @ lp['o_w'].T


def expert_weights(lp, cfg, u):
    """(B, T, router_width): a token's weight for each expert, 0 for the
    ones it did not choose. Float32, as the published gate."""
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


def routed(lp, cfg, u):
    """The held experts' part of the layer."""
    first = cfg.get('first_expert', 0)
    held = cfg['n_routed_experts']
    w = expert_weights(lp, cfg, u)[..., first:first + held]

    def one(acc, e):
        up_w, down_w, w_e = e
        y = relu2(u, up_w, down_w)
        return acc + w_e[..., None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (lp['experts_up'], lp['experts_down'],
                           jnp.moveaxis(w, -1, 0)))
    return out


def sparse_ffn(lp, cfg, u):
    return routed(lp, cfg, u) + relu2(u, lp['shared_up'], lp['shared_down'])


MIXERS = {'M': mamba, 'E': sparse_ffn, '*': attention,
          '-': lambda lp, cfg, u: relu2(u, lp['up_w'], lp['down_w'])}


def layer_params(p, i):
    lead = f'l{i}/'
    return {k[len(lead):]: a for k, a in p.items() if k.startswith(lead)}


def layer(x, lp, cfg, kind):
    u = rms_norm(x, lp['norm'], cfg['layer_norm_epsilon'])
    return x + MIXERS[kind](lp, cfg, u)


def hidden_of(p, cfg, tokens):
    """(B, T) ids -> (B, T, U) after the final norm. Each layer is
    recomputed in the backward pass."""
    x = p['embed'][tokens]
    for i, kind in enumerate(kinds(cfg)):
        x = jax.checkpoint(functools.partial(layer, cfg=cfg, kind=kind))(
            x, layer_params(p, i))
    return rms_norm(x, p['norm_f'], cfg['layer_norm_epsilon'])


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
