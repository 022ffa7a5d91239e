"""Operations and bytes a ``toy_lm`` training step requires, from shapes:
the matrix products of the forward pass, and twice that again for the
backward pass. Causal attention needs the products of a query with the
keys up to its own position, T (T + 1) / 2 pairs a row of T."""

ADAM_BYTES_PER_PARAM = 28   # read w, g, m, v; write w, m, v; float32


def attention_flops(cfg, rows, positions):
    """Scores and context over the causal pairs, forward and the two
    gradients of each."""
    pairs = positions * (positions + 1) // 2
    forward = 2 * 2 * pairs * cfg['hidden_size']
    return 3 * cfg['num_hidden_layers'] * rows * forward


def step_flops(cfg, rows, positions):
    u, x = cfg['hidden_size'], cfg['expert_size']
    e, v = cfg['num_experts'], cfg['vocab_size']
    a_token = cfg['num_hidden_layers'] * (
        2 * u * 3 * u           # Q, K, V
        + 2 * u * u             # output projection
        + 2 * u * e             # router
        + e * 2 * 2 * u * x     # every expert, in and out
    ) + 2 * u * v               # head
    return 3 * rows * positions * a_token \
        + attention_flops(cfg, rows, positions)


def moved_param_count(cfg):
    """The parameters the optimizer moves: all but the router's bias."""
    u, x = cfg['hidden_size'], cfg['expert_size']
    e, v = cfg['num_experts'], cfg['vocab_size']
    block = 2 * u + 3 * u * u + 3 * u + u * u + u + 2 * u + e * u \
        + 2 * e * x * u
    return 2 * v * u + 2 * u + cfg['num_hidden_layers'] * block


def update_bytes(cfg):
    return ADAM_BYTES_PER_PARAM * moved_param_count(cfg)
