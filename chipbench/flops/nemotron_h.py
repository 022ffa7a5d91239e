"""Operations and bytes a ``nemotron_h`` training step requires, from
shapes: the matrix products of the forward pass, and twice that again for
the backward pass; nothing recomputed.

A Mamba-2 layer is its two projections and the scan's four products a
chunk of L = ``chunk_size`` positions: inside a chunk the pairs (i, j <=
i), L (L + 1) / 2 of them, each a score over N = ``ssm_state_size``
columns a group and an output over P = ``mamba_head_dim`` columns a head;
a chunk's end state and what the carried state adds, P N a position a
head each. Causal attention needs the pairs of a query with the keys up
to its own position, T (T + 1) / 2 a row, over ``head_dim`` columns for
the scores and ``head_dim`` for the context, every query head. The routed
experts are counted at the pairs a token is **expected** to land on the
experts held here, ``num_experts_per_tok * n_routed_experts /
router_width``; an expert is two products (no gate). The depthwise
convolution, the norms and the gates are not matrix products and are not
counted."""

from ..reference.nemotron_h import kinds, mamba_sizes

ADAM_BYTES_PER_PARAM = 28   # read w, g, m, v; write w, m, v; float32


def causal_pairs(positions, window=None):
    """(i, j <= i) pairs of a row, or of its chunks of ``window``."""
    if window is None:
        return positions * (positions + 1) // 2
    whole, rest = divmod(positions, window)
    return whole * causal_pairs(window) + causal_pairs(rest)


def attention_flops(cfg, rows, positions):
    """Scores and context over the causal pairs, forward and the two
    gradients of each, every attention layer."""
    forward = 2 * causal_pairs(positions) * cfg['num_attention_heads'] \
        * 2 * cfg['head_dim']
    return 3 * kinds(cfg).count('*') * rows * forward


def scan_flops(cfg, rows, positions):
    """The selective scan's four products, forward and the two gradients
    of each, every Mamba-2 layer."""
    h, p, g, n, _, _ = mamba_sizes(cfg)
    inside = 2 * causal_pairs(positions, cfg['chunk_size']) * (g * n + h * p)
    states = 2 * positions * h * p * n * 2
    return 3 * kinds(cfg).count('M') * rows * (inside + states)


def scan_bytes(cfg, rows, positions, itemsize=4):
    """What one call of the scan has to read and write, forward: x and y
    (H P a position), B and C (G N each), the step sizes (H)."""
    h, p, g, n, _, _ = mamba_sizes(cfg)
    return rows * positions * itemsize * (2 * h * p + 2 * g * n + h)


def expected_pairs_per_token(cfg):
    return cfg['num_experts_per_tok'] * cfg['n_routed_experts'] \
        / cfg['router_width']


def shared_width(cfg):
    return cfg['n_shared_experts'] \
        * cfg['moe_shared_expert_intermediate_size']


def layer_matmul_params(cfg, kind):
    """Weights a token is multiplied with in a layer of ``kind``, the
    scan's and attention's own products apart."""
    u = cfg['hidden_size']
    if kind == 'M':
        h, _, _, _, d, conv = mamba_sizes(cfg)
        return u * (d + conv + h) + d * u
    if kind == 'E':
        return u * cfg['router_width'] + 2 * u * shared_width(cfg) \
            + 2 * u * cfg['moe_intermediate_size'] \
            * expected_pairs_per_token(cfg)
    if kind == '*':
        hd = cfg['head_dim']
        return 2 * u * hd * (cfg['num_attention_heads']
                             + cfg['num_key_value_heads'])
    return 2 * u * cfg['intermediate_size']


def step_flops(cfg, rows, positions):
    a_token = sum(layer_matmul_params(cfg, kind) for kind in kinds(cfg)) \
        + cfg['hidden_size'] * cfg['vocab_size']            # the head
    return int(3 * rows * positions * 2 * a_token) \
        + attention_flops(cfg, rows, positions) \
        + scan_flops(cfg, rows, positions)


def moved_param_count(cfg):
    """The parameters the optimizer moves: all but the routers'
    correction biases."""
    u, v = cfg['hidden_size'], cfg['vocab_size']
    total = 2 * v * u + u
    for kind in kinds(cfg):
        total += u                                          # the norm
        if kind == 'M':
            h, _, _, _, d, conv = mamba_sizes(cfg)
            total += u * (d + conv + h) + conv * (cfg['conv_kernel'] + 1) \
                + 3 * h + d + d * u
        elif kind == 'E':
            total += u * cfg['router_width'] + 2 * u * (
                cfg['moe_intermediate_size'] * cfg['n_routed_experts']
                + shared_width(cfg))
        else:
            total += layer_matmul_params(cfg, kind)
    return total


def update_bytes(cfg):
    return ADAM_BYTES_PER_PARAM * moved_param_count(cfg)
