"""Operations and bytes a ``deepseek_v3`` training step requires, from
shapes: the matrix products of the forward pass, and twice that again for
the backward pass; nothing recomputed.

Causal attention needs the products of a query with the keys up to its
own position, T (T + 1) / 2 pairs a row of T: scores over
``qk_nope_head_dim + qk_rope_head_dim`` columns, the context over
``v_head_dim``. The routed experts are counted at the pairs a token is
**expected** to land on the experts held here,
``num_experts_per_tok * n_routed_experts / router_width`` (all of them
where the chip holds every expert): how many a batch really draws is the
router's to decide (PERF.md section 5 has a chip run's count)."""

from ..reference.deepseek_v3 import is_sparse

ADAM_BYTES_PER_PARAM = 28   # read w, g, m, v; write w, m, v; float32


def attention_flops(cfg, rows, positions):
    """Scores and context over the causal pairs, forward and the two
    gradients of each, every layer."""
    pairs = positions * (positions + 1) // 2
    width = cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim'] \
        + cfg['v_head_dim']
    forward = 2 * pairs * cfg['num_attention_heads'] * width
    return 3 * cfg['num_hidden_layers'] * rows * forward


def expected_pairs_per_token(cfg):
    return cfg['num_experts_per_tok'] * cfg['n_routed_experts'] \
        / cfg['router_width']


def layer_matmul_params(cfg, layer):
    """Weights a token is multiplied with in one layer, attention's
    scores and context apart."""
    u, heads = cfg['hidden_size'], cfg['num_attention_heads']
    nope, pe = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, latent = cfg['v_head_dim'], cfg['kv_lora_rank']
    attn = u * heads * (nope + pe) + u * (latent + pe) \
        + latent * heads * (nope + vd) + heads * vd * u
    if not is_sparse(cfg, layer):
        return attn + 3 * u * cfg['intermediate_size']
    x = cfg['moe_intermediate_size']
    return attn + u * cfg['router_width'] \
        + 3 * u * x * cfg['n_shared_experts'] \
        + 3 * u * x * expected_pairs_per_token(cfg)


def step_flops(cfg, rows, positions):
    a_token = sum(layer_matmul_params(cfg, i)
                  for i in range(cfg['num_hidden_layers'])) \
        + cfg['hidden_size'] * cfg['vocab_size']            # the head
    return int(3 * rows * positions * 2 * a_token) \
        + attention_flops(cfg, rows, positions)


def moved_param_count(cfg):
    """The parameters the optimizer moves: all but the routers'
    correction biases."""
    u, v = cfg['hidden_size'], cfg['vocab_size']
    heads = cfg['num_attention_heads']
    nope, pe = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, latent = cfg['v_head_dim'], cfg['kv_lora_rank']
    x, held = cfg['moe_intermediate_size'], cfg['n_routed_experts']
    attn = u * heads * (nope + pe) + u * (latent + pe) + latent \
        + latent * heads * (nope + vd) + heads * vd * u + 2 * u
    total = 2 * v * u + u
    for i in range(cfg['num_hidden_layers']):
        if is_sparse(cfg, i):
            total += attn + u * cfg['router_width'] \
                + 3 * u * x * (held + cfg['n_shared_experts'])
        else:
            total += attn + 3 * u * cfg['intermediate_size']
    return total


def update_bytes(cfg):
    return ADAM_BYTES_PER_PARAM * moved_param_count(cfg)
