"""Sparse-expert layers: ``SparseExperts``, the feed-forward block of a
mixture-of-experts decoder as one chip of an expert-parallel layer holds
it (``ops/experts.py`` is the computation)."""

from .basic_layers import Dense, _op
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ['SparseExperts']


class SparseExperts(HybridBlock):
    """Routed experts plus an optional shared expert.

    The router scores every token against all ``num_experts`` experts and
    chooses ``experts_per_token`` of them; this block holds the experts
    ``held`` (a ``range``; all of them by default) and computes their
    part of the result, dropping no token: what the absent experts would
    add is another chip's to compute. ``shared`` (a Block from ``units``
    to ``units``, as wide as the shared experts together; None for none)
    is what every chip computes alike, added once whatever is held.
    ``activation`` is the routed experts' form: ``'swiglu'``,
    ``down(silu(gate u) * up u)``, or ``'relu2'``, ``down(relu(up u)^2)``
    with no gate (Nemotron-H).

    Leaves: ``router.weight`` (num_experts, units); ``router_bias``
    (num_experts,), ``grad_req='null'``: it only chooses, and is moved,
    if at all, outside the gradient; ``experts_gate``, ``experts_up``
    (held, expert_size, units) and ``experts_down`` (held, units,
    expert_size), each one leaf stacked over the held experts (no
    ``experts_gate`` under ``'relu2'``); the leaves of ``shared``.
    """

    def __init__(self, units, num_experts, experts_per_token, expert_size,
                 shared=None, held=None, score_func='sigmoid',
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 weight_initializer=None, activation='swiglu', **kwargs):
        super().__init__(**kwargs)
        held = range(num_experts) if held is None else held
        if held.step != 1 or held.start < 0 or held.stop > num_experts \
                or not len(held):
            raise ValueError(f'held has to be a range of consecutive experts '
                             f'within 0..{num_experts}, got {held!r}')
        if experts_per_token > num_experts:
            raise ValueError('more experts a token than experts')
        self._held = held
        self._route = dict(experts_per_token=experts_per_token,
                           first_expert=held.start, score_func=score_func,
                           norm_topk_prob=norm_topk_prob,
                           routed_scaling_factor=routed_scaling_factor,
                           activation=activation)
        n = len(held)
        self.router = Dense(num_experts, use_bias=False, flatten=False,
                            in_units=units,
                            weight_initializer=weight_initializer)
        self.router_bias = Parameter('router_bias', shape=(num_experts,),
                                     init='zeros', grad_req='null')
        if activation != 'relu2':
            self.experts_gate = Parameter(
                'experts_gate', shape=(n, expert_size, units),
                init=weight_initializer)
        self.experts_up = Parameter(
            'experts_up', shape=(n, expert_size, units),
            init=weight_initializer)
        self.experts_down = Parameter(
            'experts_down', shape=(n, units, expert_size),
            init=weight_initializer)
        self.shared = shared

    @property
    def held(self):
        return self._held

    def forward(self, x):
        gate = self._reg_params.get('experts_gate')
        out = _op('sparse_experts', x, self.router.weight.data(),
                  self.router_bias.data(),
                  None if gate is None else gate.data(),
                  self.experts_up.data(), self.experts_down.data(),
                  **self._route)
        return out if self.shared is None else out + self.shared(x)

    def __repr__(self):
        return (f'SparseExperts({self.router.weight.shape[1]}, experts '
                f'{self._held.start}..{self._held.stop - 1} of '
                f'{self.router.weight.shape[0]}, '
                f'{self._route["experts_per_token"]} a token)')
