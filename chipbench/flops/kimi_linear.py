"""Operations and bytes a ``kimi_linear`` training step requires, from
shapes: the matrix products of the forward pass, and twice that again for
the backward pass; nothing recomputed.

A Kimi Delta Attention layer is its projections (q, k, v, the two-step
decay and gate projections through ``head_dim``, the write strengths,
the output) and the chunked delta rule's products, a chunk of C =
``chunk_size`` positions a head of d = ``head_dim`` (keys and values
alike): ``A_kk`` over the C (C - 1) / 2 pairs below the diagonal and
``A_qk`` over the C (C + 1) / 2 on and below it, d columns each; the
triangular solve of ``I + A_kk`` against ``[k; v]`` (2 d columns) over
the pairs below the diagonal; ``A_qk V'`` over the pairs on and below it;
and three products with the (d, d) state a position: ``W S``, ``q S`` and
the state's update. Causal latent attention needs the pairs of a query
with the keys up to its own position, T (T + 1) / 2 a row, scores over
``qk_nope_head_dim + qk_rope_head_dim`` columns and the context over
``v_head_dim``. The routed experts are counted at the pairs a token is
**expected** to land on the experts held here, ``num_experts_per_token *
num_experts / router_width``. The short convolutions, the norms, the
decays and the gates are not matrix products and are not counted."""

from ..reference.kimi_linear import is_sparse, kda_layers

ADAM_BYTES_PER_PARAM = 28   # read w, g, m, v; write w, m, v; float32


def causal_pairs(positions, window=None):
    """(i, j <= i) pairs of a row, or of its chunks of ``window``."""
    if window is None:
        return positions * (positions + 1) // 2
    whole, rest = divmod(positions, window)
    return whole * causal_pairs(window) + causal_pairs(rest)


def kda_sizes(cfg):
    lin = cfg['linear_attn_config']
    return lin['num_heads'], lin['head_dim'], lin['short_conv_kernel_size']


def attention_flops(cfg, rows, positions):
    """Scores and context over the causal pairs, forward and the two
    gradients of each, every latent attention layer."""
    width = cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim'] \
        + cfg['v_head_dim']
    forward = 2 * causal_pairs(positions) * cfg['num_attention_heads'] \
        * width
    mla = cfg['num_hidden_layers'] - len(kda_layers(cfg))
    return 3 * mla * rows * forward


def kda_flops(cfg, rows, positions):
    """The chunked delta rule's products, forward and the two gradients of
    each, every KDA layer."""
    h, d, _ = kda_sizes(cfg)
    c = cfg['chunk_size']
    whole, rest = divmod(positions, c)
    below = whole * (c * (c - 1) // 2) + rest * (rest - 1) // 2
    on_and_below = causal_pairs(positions, c)
    inside = 2 * d * (below + on_and_below)             # A_kk, A_qk
    inside += 2 * below * 2 * d                         # the solve
    inside += 2 * on_and_below * d                      # A_qk V'
    states = 3 * 2 * positions * d * d                  # W S, q S, update
    return 3 * len(kda_layers(cfg)) * rows * h * (inside + states)


def kda_bytes(cfg, rows, positions, itemsize=4):
    """What the delta rule has to read and write, forward and the two
    gradients, every KDA layer: a call reads q, k, v, the decays (d each
    a head) and the write strength (1 a head) and writes o (d); the
    backward reads them and the output's gradient again and writes a
    gradient of each input, so twice as much."""
    h, d, _ = kda_sizes(cfg)
    a_call = rows * positions * h * itemsize * (5 * d + 1)
    return 3 * len(kda_layers(cfg)) * a_call


def expected_pairs_per_token(cfg):
    return cfg['num_experts_per_token'] * cfg['num_experts'] \
        / cfg['router_width']


def mixer_matmul_params(cfg, linear):
    """Weights a token is multiplied with in a mixer, the delta rule's and
    attention's own products apart."""
    u = cfg['hidden_size']
    if linear:
        h, d, _ = kda_sizes(cfg)
        # q, k, v, o; f and g through d; beta
        return 4 * u * h * d + 2 * (u * d + d * h * d) + u * h
    heads = cfg['num_attention_heads']
    nope, pe = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, latent = cfg['v_head_dim'], cfg['kv_lora_rank']
    return u * heads * (nope + pe) + u * (latent + pe) \
        + latent * heads * (nope + vd) + heads * vd * u


def ffn_matmul_params(cfg, layer):
    u = cfg['hidden_size']
    if not is_sparse(cfg, layer):
        return 3 * u * cfg['intermediate_size']
    x = cfg['moe_intermediate_size']
    return u * cfg['router_width'] + 3 * u * x * cfg['num_shared_experts'] \
        + 3 * u * x * expected_pairs_per_token(cfg)


def step_flops(cfg, rows, positions):
    linear = kda_layers(cfg)
    a_token = sum(mixer_matmul_params(cfg, i in linear)
                  + ffn_matmul_params(cfg, i)
                  for i in range(cfg['num_hidden_layers'])) \
        + cfg['hidden_size'] * cfg['vocab_size']            # the head
    return int(3 * rows * positions * 2 * a_token) \
        + attention_flops(cfg, rows, positions) \
        + kda_flops(cfg, rows, positions)


def moved_param_count(cfg):
    """The parameters the optimizer moves: all but the routers'
    correction biases."""
    u, voc = cfg['hidden_size'], cfg['vocab_size']
    h, d, conv = kda_sizes(cfg)
    x, held = cfg['moe_intermediate_size'], cfg['num_experts']
    linear = kda_layers(cfg)
    total = 2 * voc * u + u
    for i in range(cfg['num_hidden_layers']):
        total += 2 * u + mixer_matmul_params(cfg, i in linear)  # + norms
        if i in linear:
            total += 3 * h * d * conv + h * d + h + d   # convs, dt, A, norm
        else:
            total += cfg['kv_lora_rank']                # the latent's norm
        if is_sparse(cfg, i):
            total += u * cfg['router_width'] \
                + 3 * u * x * (held + cfg['num_shared_experts'])
        else:
            total += 3 * u * cfg['intermediate_size']
    return total


def update_bytes(cfg):
    return ADAM_BYTES_PER_PARAM * moved_param_count(cfg)
