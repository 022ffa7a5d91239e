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

**The buffer.** The (token, choice) pairs are sorted by held expert, the
pairs of absent experts last: the live rows are the first ``n_live`` of
``m = tokens x k``. There is no capacity: a batch that sends every token
to a held expert has ``m`` live rows and loses nothing.

**The ladder.** A chip that holds ``held`` of ``E`` experts expects
``m x held / E`` live rows, and the gathers, the masks and the
compiler's grouped kernel (in 512-row tiles) cost by the rows they are
given, not by the live ones. So everything under ``mx.experts`` runs
over the first ``P`` rows only, ``P`` the shortest of
a few static prefixes (:func:`prefix_ladder`, a function of the shapes
alone) that holds the live rows, chosen on the device by ``lax.switch``.
The last rung is ``m``, the whole buffer. A layer that holds every expert
(or whose buffer is no longer than the first rung) has one rung and no
``cond``. The switch sits inside one ``custom_vjp``: what is kept for the
backward pass is the op's inputs and the routing, whichever rung runs,
and the backward's own switch makes the taken rung's body again and
takes its vjp (a differentiated ``cond`` keeps the union of every
branch's residuals). Each rung opens the scope ``rows_<P>`` inside
``mx.experts``: a profile's operation names say which rung ran.

**Dead rows.** A prefix still has rows past the last held group
(``n_live .. P``), computed by nobody: the rows that go in and the rows
that come out are masked, forward and backward. What a grouped product
leaves *in between* there is zeros on the CPU and whatever the buffer
held on the TPU, so both activations mask those rows' routing weights
too, forward and backward: no gradient reads them (PERF.md section 7,
fault 10).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

ROUTER_SCOPE = 'mx.router'      # scores, top-k, the sort by expert, counts
SCOPE = 'mx.experts'            # gathers, grouped products, the sum back


# The ladder's numbers, settled on the v5e (PERF.md section 6, PR 38). A
# router's load on the held experts is uneven: 0.2 to 2.3 times the
# expected count of live rows were read over a run with 16 of 128 held,
# nothing to 6.6 times it with 8 held. A rung is a copy of the body in
# the forward and in the backward program (27 MB of code a rung with
# four such layers, on a chip 91 % full), so there are few.
TILE = 512          # rows of a tile of the compiler's grouped kernel
MARGIN = 1.5        # the first rung over the expected live count
RUNGS = 2           # at most so many rungs below the whole buffer


def prefix_ladder(m, held, experts):
    """The static prefixes of a sorted buffer of ``m`` rows that a layer
    holding ``held`` of ``experts`` experts chooses from: whole tiles,
    the first ``MARGIN`` times the expected live count ``m x held /
    experts``, ``RUNGS`` of them in all under ``m`` in equal ratios from
    the first to ``m``, then ``m`` itself. ``(m,)`` where every expert
    is held, or where the first rung would be no shorter than the
    buffer."""
    if held >= experts:
        return (m,)
    tiles = lambda rows: -(-int(rows) // TILE) * TILE
    first = tiles(MARGIN * m * held / experts)
    ratio = (m / first) ** (1 / RUNGS)
    rungs = sorted({tiles(first * ratio ** i) for i in range(RUNGS)})
    return (*(p for p in rungs if p < m), m)


def _spread(x, head, k):
    """(n, w) -> (p, w): row ``i`` is the row of pair ``head[i]``, where
    ``x`` has a row for every ``k`` pairs in a row."""
    return x[head // k]


def _sum_back(y, inverse, k):
    """(p, w) -> (m / k, w): pair ``j`` is row ``inverse[j]`` of ``y``, or
    zero where that is past ``y``'s rows, and ``k`` pairs in a row sum
    to a row. One gather of ``m`` rows, where XLA's gradient of
    :func:`_spread` is a scatter-add; gathered a choice at a time,
    (k, m / k, w), so that the sum runs over whole tiles (a (m / k, k, w)
    view of the rows is a padded copy of them on the TPU)."""
    return y.at[inverse.reshape(-1, k).T].get(
        mode='fill', fill_value=0).sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sorted_rows(x, head, inverse, k):
    """:func:`_spread`, with :func:`_sum_back` for its gradient."""
    return _spread(x, head, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _onto_tokens(y, head, inverse, k):
    """:func:`_sum_back`, with :func:`_spread` for its gradient."""
    return _sum_back(y, inverse, k)


_sorted_rows.defvjp(
    lambda x, head, inverse, k: (_spread(x, head, k), (head, inverse)),
    lambda k, res, g: (_sum_back(g, res[1], k), None, None))
_onto_tokens.defvjp(
    lambda y, head, inverse, k: (_sum_back(y, inverse, k), (head, inverse)),
    lambda k, res, g: (_spread(g, res[0], k), None, None))


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


def _prefix(p, activation, k, routing, tokens, column, leaves):
    """The routed experts over the first ``p`` rows of the sorted buffer,
    which hold every live row: (tokens, U) -> (tokens, U)."""
    order, inverse, sizes, n_live = routing
    with jax.named_scope(f'rows_{p}'):
        head = order[:p]
        rows = _live_rows(_sorted_rows(tokens, head, inverse, k), n_live)
        # the dead rows' weights are masked too: what a grouped product
        # leaves in those rows on the TPU, times the backward's like
        # rows, would be the gradient of an absent pair's weight, and
        # through it the router's
        weight = _live_rows(_sorted_rows(column, head, inverse, 1), n_live)
        # the weight goes onto the narrow side of the down projection
        # (linear, so the same sum): what is kept for the weights'
        # gradient is then X wide, not U
        if activation == 'swiglu':
            gate, up, down = leaves
            hidden = _gated(_grouped(rows, gate, sizes),
                            _grouped(rows, up, sizes), weight)
        else:
            up, down = leaves
            hidden = _squared(_grouped(rows, up, sizes), weight)
        out = _live_rows(_grouped(hidden, down, sizes), n_live)
        return _onto_tokens(out, head, inverse, k)


@functools.lru_cache(maxsize=None)
def _branches(body, ladder, activation, k):
    """``body`` over each prefix of ``ladder``: the same callables for
    every layer of a model, so that JAX traces a rung's body once for
    all the layers of one shape."""
    return tuple(functools.partial(body, p, activation, k) for p in ladder)


def _rung(ladder, n_live):
    """The index of the shortest prefix that holds ``n_live`` rows."""
    return (n_live > jnp.asarray(ladder[:-1], jnp.int32)).sum()


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _shortest_prefix(ladder, activation, k, routing, tokens, column, leaves):
    """:func:`_prefix` over the shortest prefix of ``ladder`` that holds
    the live rows. Differentiated by hand, not as a ``cond``: JAX keeps
    for a differentiated ``cond`` the residuals of all its branches,
    each of its own buffer's size. Kept here: the arguments."""
    return lax.switch(_rung(ladder, routing[3]),
                      _branches(_prefix, ladder, activation, k),
                      routing, tokens, column, leaves)


def _shortest_prefix_fwd(ladder, activation, k, routing, tokens, column,
                         leaves):
    return (_shortest_prefix(ladder, activation, k, routing, tokens, column,
                             leaves), (routing, tokens, column, leaves))


def _prefix_grads(p, activation, k, routing, inputs, g):
    """The body over ``p`` rows made again, and its vjp at ``g``: what
    it kept lives no longer than this branch."""
    _, vjp = jax.vjp(functools.partial(_prefix, p, activation, k, routing),
                     *inputs)
    return vjp(g)


def _shortest_prefix_bwd(ladder, activation, k, res, g):
    routing, *inputs = res
    return (None, *lax.switch(_rung(ladder, routing[3]),
                              _branches(_prefix_grads, ladder, activation, k),
                              routing, inputs, g))


_shortest_prefix.defvjp(_shortest_prefix_fwd, _shortest_prefix_bwd)


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
    leaves = (experts_up, experts_down) if experts_gate is None \
        else (experts_gate, experts_up, experts_down)
    ladder = prefix_ladder(order.shape[0], held, router_weight.shape[0])
    with jax.named_scope(SCOPE):
        column = weights.reshape(-1, 1).astype(x.dtype)
        routing = (order, inverse, sizes, n_live)
        if len(ladder) == 1:
            out = _prefix(ladder[0], activation, k, routing, tokens, column,
                          leaves)
        else:
            out = _shortest_prefix(ladder, activation, k, routing, tokens,
                                   column, leaves)
        return out.reshape(shape).astype(x.dtype)
