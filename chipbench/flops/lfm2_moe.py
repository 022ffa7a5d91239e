"""Operations and bytes an ``lfm2_moe`` training step requires, from
shapes: the matrix products of the forward pass, and twice that again for
the backward pass; nothing recomputed.

A gated short convolution layer is its two projections (``in_proj`` to
three blocks of U, ``out_proj``); its gates and its K taps are no matrix
products and are not in the step's count, but they are the scope
``mx.short_conv``, whose roofline needs its own operations
(:func:`short_conv_flops`) and bytes (:func:`short_conv_bytes`): it is
bound by the memory. An attention layer is its four projections and the
causal pairs of a query with the keys up to its own position, T (T + 1)
/ 2 a row, scores and context over ``head_dim`` columns each, every query
head. The routed experts are counted at the pairs a token is **expected**
to land on the experts held here, ``num_experts_per_tok * num_experts /
router_width``. The head is the tied embedding's matrix."""

from ..reference.lfm2_moe import head_dim, is_sparse, kinds

ADAM_BYTES_PER_PARAM = 28   # read w, g, m, v; write w, m, v; float32


def causal_pairs(positions):
    return positions * (positions + 1) // 2


def attention_flops(cfg, rows, positions):
    """Scores and context over the causal pairs, forward and the two
    gradients of each, every attention layer."""
    forward = 2 * causal_pairs(positions) * cfg['num_attention_heads'] \
        * 2 * head_dim(cfg)
    return 3 * kinds(cfg).count('full_attention') * rows * forward


def _conv_elements(cfg, rows, positions):
    """(position, channel) elements a call of the convolution takes, every
    conv layer together."""
    return kinds(cfg).count('conv') * rows * positions * cfg['hidden_size']


def short_conv_flops(cfg, rows, positions):
    """The gates and the taps, forward and the two gradients: a forward
    element is ``b x``, K multiplies and K - 1 adds, and ``c s``, 2 K + 1
    operations; its backward twice that."""
    taps = cfg['conv_L_cache']
    return 3 * (2 * taps + 1) * _conv_elements(cfg, rows, positions)


def short_conv_bytes(cfg, rows, positions, remat=True, itemsize=4):
    """What ``mx.short_conv`` has to read and write a step: the forward
    reads b, c and x and writes the output (4 elements); the backward
    reads them and the output's gradient and writes the gradients of b,
    c and x (7); under ``remat`` the forward runs again inside the
    backward (4 more)."""
    a_element = 4 + 7 + (4 if remat else 0)
    return a_element * itemsize * _conv_elements(cfg, rows, positions)


def expected_pairs_per_token(cfg):
    return cfg['num_experts_per_tok'] * cfg['num_experts'] \
        / cfg['router_width']


def mixer_matmul_params(cfg, kind):
    """Weights a token is multiplied with in a mixer, attention's own
    products apart."""
    u = cfg['hidden_size']
    if kind == 'conv':
        return 4 * u * u                                # in_proj, out_proj
    d = head_dim(cfg)
    return 2 * u * cfg['num_attention_heads'] * d \
        + 2 * u * cfg['num_key_value_heads'] * d        # q, o; k, v


def ffn_matmul_params(cfg, layer):
    u = cfg['hidden_size']
    if not is_sparse(cfg, layer):
        return 3 * u * cfg['intermediate_size']
    return u * cfg['router_width'] + 3 * u * cfg['moe_intermediate_size'] \
        * expected_pairs_per_token(cfg)


def step_flops(cfg, rows, positions):
    a_token = sum(mixer_matmul_params(cfg, kind) + ffn_matmul_params(cfg, i)
                  for i, kind in enumerate(kinds(cfg))) \
        + cfg['hidden_size'] * cfg['vocab_size']            # the head
    return int(3 * rows * positions * 2 * a_token) \
        + attention_flops(cfg, rows, positions)


def moved_param_count(cfg):
    """The parameters the optimizer moves: all but the routers'
    correction biases. The head is the embedding, counted once."""
    u = cfg['hidden_size']
    total = cfg['vocab_size'] * u + u
    for i, kind in enumerate(kinds(cfg)):
        total += 2 * u + mixer_matmul_params(cfg, kind)         # + norms
        if kind == 'conv':
            total += u * cfg['conv_L_cache']
        else:
            total += 2 * head_dim(cfg)                          # q, k norms
        if is_sparse(cfg, i):
            total += u * cfg['router_width'] \
                + 3 * u * cfg['moe_intermediate_size'] * cfg['num_experts']
        else:
            total += 3 * u * cfg['intermediate_size']
    return total


def update_bytes(cfg):
    return ADAM_BYTES_PER_PARAM * moved_param_count(cfg)
