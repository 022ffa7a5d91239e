"""Operations and bytes a BERT training step *requires*, from shapes.

Required means: the matrix products of the forward pass on the real
(non-padding) tokens, the MLM head at the predicted positions only, and
twice that again for the backward pass (one product for the gradient of
each operand). Nothing for padding, nothing recomputed, nothing for
embedding lookups, softmax, LayerNorm, GELU or the optimizer: those are
bandwidth, not FLOPs that a matrix unit's peak is the yardstick for.
"""

ADAM_BYTES_PER_PARAM = 28   # read w, g, m, v; write w, m, v; float32


def forward_flops(cfg, job, lengths, predicted=0):
    """FLOPs of one forward pass over sequences of the given real
    ``lengths`` (a list), ``predicted`` MLM positions in each."""
    u, h = cfg['hidden_size'], cfg['intermediate_size']
    layers = cfg['num_hidden_layers']
    total = 0
    for n in lengths:
        n = int(n)
        per_layer = (2 * n * u * 3 * u      # Q, K, V projections
                     + 2 * n * n * u        # scores, all heads
                     + 2 * n * n * u        # context
                     + 2 * n * u * u        # output projection
                     + 2 * 2 * n * u * h)   # the two FFN products
        total += layers * per_layer
        total += 2 * u * u                  # pooler, on [CLS]
        if job['kind'] == 'classify':
            total += 2 * u * job['num_classes']
        else:
            total += 2 * predicted * u * u                  # transform
            total += 2 * predicted * u * cfg['vocab_size']  # tied decoder
            total += 2 * u * 2                              # NSP
    return total


def step_flops(cfg, job, lengths, predicted=0):
    """Forward and backward: three times the forward's products."""
    return 3 * forward_flops(cfg, job, lengths, predicted)


def attention_flops(cfg, lengths):
    """The part of :func:`step_flops` that is attention's own: in every
    layer the scores and the context of the real tokens (2 n^2 U each, all
    heads together), forward and the two gradients of each. The
    projections round them are not attention's, and a backward pass that
    computes the scores again earns nothing for it."""
    per_row = sum(2 * 2 * int(n) * int(n) * cfg['hidden_size']
                  for n in lengths)
    return 3 * cfg['num_hidden_layers'] * per_row


def param_count(cfg, job):
    u, h = cfg['hidden_size'], cfg['intermediate_size']
    n = (cfg['vocab_size'] + cfg['type_vocab_size']
         + cfg['max_position_embeddings']) * u + 2 * u
    n += cfg['num_hidden_layers'] * (
        3 * u * u + 3 * u + u * u + u + 2 * u * h + h + u + 4 * u)
    n += u * u + u                                          # pooler
    if job['kind'] == 'classify':
        n += u * job['num_classes'] + job['num_classes']
    else:
        n += u * u + u + 2 * u + cfg['vocab_size'] + 2 * u + 2
    return n


def update_bytes(cfg, job):
    """Bytes Adam has to move for one update of every parameter."""
    return ADAM_BYTES_PER_PARAM * param_count(cfg, job)
