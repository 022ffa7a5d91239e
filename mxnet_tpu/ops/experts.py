"""Sparse-expert feed-forward: a router over all experts, the part of the
result that the experts held here give, no token dropped.

NEW capability over the reference (it has no mixture of experts). One
function does what one chip of an expert-parallel layer does between the
exchanges: every token is scored against *all* experts, its ``k`` experts
are chosen and its weights normalised over them, and then only the
(token, choice) pairs that fall on a held expert are computed: sorted by
expert, gathered, taken through the grouped products of the experts
(``jax.lax.ragged_dot``, the per-expert counts as group sizes: three of a
SwiGLU, ``down(silu(gate u) * up u)``, or two of an un-gated
``down(relu(up u)^2)``), weighted and summed back onto their tokens.
What the absent experts would have added is left out, so the shares of
all chips add up to the whole layer (tests/test_deepseek_v3.py,
tests/test_nemotron_h.py). ``gluon.nn.SparseExperts`` is the Block.

There is no capacity: the sorted buffer has a row for every pair
(tokens x k), so a batch that sends every token to one expert loses
nothing. Rows past the last held group are computed by nobody: the rows
that go in and the rows that come out are masked, forward and backward.
What a grouped product leaves *in between* there is zeros on the CPU and
whatever the buffer held on the TPU; the un-gated form masks those rows'
weights too, so that no gradient reads them (the gated form does not
yet: PERF.md section 7).
"""

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

ROUTER_SCOPE = 'mx.router'      # scores, top-k, the sort by expert, counts
SCOPE = 'mx.experts'            # gathers, grouped products, the sum back


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation of the rows: its gradient is a
    gather by the inverse, where XLA's for a gather is a scatter-add."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    _, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def route(x, router_weight, router_bias, experts_per_token, score_func,
          norm_topk_prob, routed_scaling_factor):
    """(tokens, U) -> (chosen experts (tokens, k) int32, their weights
    (tokens, k) float32), over all the experts the router has.

    Scores are float32 and the router's product runs at ``highest``
    precision: at the TPU's default a float32 product is one bfloat16
    pass, and the k-th and (k+1)-th score change places. The bias only
    chooses (it carries no gradient); the weights are the scores
    themselves, normalised over the chosen and scaled."""
    logits = jnp.dot(x.astype(jnp.float32),
                     router_weight.astype(jnp.float32).T,
                     precision=lax.Precision.HIGHEST)
    if score_func == 'sigmoid':
        scores = jax.nn.sigmoid(logits)
    elif score_func == 'softmax':
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f'unknown score function {score_func!r}')
    _, chosen = lax.top_k(
        scores + lax.stop_gradient(router_bias.astype(jnp.float32)),
        experts_per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * routed_scaling_factor


@jax.custom_vjp
def _live_rows(x, n_live):
    """The first ``n_live`` rows of ``x`` and zeros below them, forward
    and backward: what lies past the last group of a grouped product is
    nobody's, and is never a NaN times zero. Keeps a count, not a mask."""
    return jnp.where(
        jnp.arange(x.shape[0], dtype=jnp.int32)[:, None] < n_live, x, 0)


_live_rows.defvjp(lambda x, n_live: (_live_rows(x, n_live), n_live),
                  lambda n_live, g: (_live_rows(g, n_live), None))


@jax.custom_vjp
def _grouped(rows, weights, sizes):
    """rows (m, in) x weights (groups, out, in) -> (m, out): each row by
    the weight of its group, ``sizes`` rows a group in order. The grouped
    product XLA has a TPU kernel for takes its weights (groups, in, out):
    the forward hands it a transposed view, and the backward, which wants
    them (groups, out, in), takes the leaf as it lies. Written out so
    that what is kept for the backward is the leaf itself, not a
    transposed copy of it."""
    return lax.ragged_dot(rows, weights.transpose(0, 2, 1), sizes)


def _grouped_fwd(rows, weights, sizes):
    return _grouped(rows, weights, sizes), (rows, weights, sizes)


# dy (m, out) x rows (m, in) -> (groups, out, in), summed over a group's
# rows: the weights' gradient in the leaf's own layout
_OVER_ROWS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=())


def _grouped_bwd(res, g):
    rows, weights, sizes = res
    return (lax.ragged_dot(g, weights, sizes),
            lax.ragged_dot_general(g, rows, sizes, _OVER_ROWS), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@jax.checkpoint
def _gated(gate, up, weight):
    """silu(gate) * up * weight; its parts are made again in the
    backward pass rather than kept, five arrays of the buffer's size."""
    return jax.nn.silu(gate) * up * weight


@jax.checkpoint
def _squared(up, weight):
    """relu(up)^2 * weight, made again in the backward pass as
    :func:`_gated` is."""
    return jnp.square(jax.nn.relu(up)) * weight


@register('sparse_experts', f32_only=True)
def sparse_experts(x, router_weight, router_bias, experts_gate, experts_up,
                   experts_down, experts_per_token=2, first_expert=0,
                   score_func='sigmoid', norm_topk_prob=True,
                   routed_scaling_factor=1.0, activation='swiglu'):
    """The routed part of a sparse-expert FFN for the experts held here.

    x: (..., U). router_weight: (E, U) and router_bias: (E,) over all E
    experts. experts_gate, experts_up: (n, X, U) and experts_down:
    (n, U, X) for the n experts ``first_expert .. first_expert + n - 1``.
    Returns (..., U): sum over a token's chosen experts that are held
    here of ``weight * down(silu(gate u) * up u)`` (``activation``
    ``'swiglu'``) or of ``weight * down(relu(up u)^2)`` (``'relu2'``:
    there is no gate, and ``experts_gate`` is None).
    """
    if activation not in ('swiglu', 'relu2'):
        raise ValueError(f'unknown activation {activation!r}')
    if (experts_gate is None) != (activation == 'relu2'):
        raise ValueError('a gate goes with swiglu, and none with relu2')
    shape = x.shape
    units = shape[-1]
    held = experts_up.shape[0]
    k = experts_per_token
    tokens = x.reshape(-1, units)
    # the router's scope is opened beside the experts', not inside it: a
    # profile's reader puts an operation down to the first scope in its
    # name (chipbench/trace_reduce.py scope_of)
    with jax.named_scope(ROUTER_SCOPE):
        chosen, weights = route(
            tokens, router_weight, router_bias, k, score_func,
            norm_topk_prob, routed_scaling_factor)
        # a pair's expert by this chip's count; ``held`` for absent
        local = chosen.reshape(-1) - first_expert
        local = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        sizes = (local[:, None] == jnp.arange(held, dtype=jnp.int32)
                 ).sum(0, dtype=jnp.int32)
        n_live = sizes.sum()
    with jax.named_scope(SCOPE):
        # pair p is token p // k: the sorted rows are a permutation of
        # the tokens repeated k times
        rows = _permute(jnp.repeat(tokens, k, axis=0), order, inverse)
        rows = _live_rows(rows, n_live)
        # the weight goes onto the narrow side of the down projection
        # (linear, so the same sum): what is kept for the weights'
        # gradient is then X wide, not U
        if activation == 'swiglu':
            hidden = _gated(
                _grouped(rows, experts_gate, sizes),
                _grouped(rows, experts_up, sizes),
                _permute(weights.reshape(-1, 1).astype(x.dtype), order,
                         inverse))
        else:
            # on the TPU a grouped product leaves the rows past its last
            # group as the buffer held them (not zeros, though the rows
            # that went in are): relu(that)^2 times the backward's like
            # rows would be the gradient of an absent pair's weight, and
            # through it the router's. The dead rows' weights are masked,
            # forward and backward, so that it is 0.
            hidden = _squared(
                _grouped(rows, experts_up, sizes),
                _live_rows(_permute(weights.reshape(-1, 1).astype(x.dtype),
                                    order, inverse), n_live))
        out = _live_rows(_grouped(hidden, experts_down, sizes), n_live)
        out = _permute(out, inverse, order).reshape(-1, k, units).sum(1)
        return out.reshape(shape).astype(x.dtype)
