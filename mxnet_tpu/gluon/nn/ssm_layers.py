"""State-space and convolution layers: ``Mamba2Mixer``, the Mamba-2 block
of a hybrid decoder, and ``ShortConv``, LFM2's gated short convolution
(``ops/ssm.py`` is the convolutions and the scan)."""

import math

import jax
import jax.numpy as jnp
import numpy as _np

from .basic_layers import Dense, _op
from ..block import HybridBlock
from ..parameter import Parameter
from ... import initializer
from ...ops.registry import Op, apply_op

__all__ = ['Mamba2Mixer', 'ShortConv']


class _Filled(initializer.Initializer):
    """A leaf filled by ``make(shape) -> numpy array``, whatever its name
    ends in (the base class zeroes a ``*bias``)."""

    def __init__(self, make):
        super().__init__()
        self._make = make

    def __call__(self, desc, arr):
        self._set(arr, self._make(arr.shape))


def _time_step_bias(lo, hi, floor):
    """The inverse softplus of a log-uniform draw of step sizes in
    [lo, hi], floored: softplus(dt_bias) is the draw."""
    def make(shape):
        dt = _np.exp(_np.random.uniform(math.log(lo), math.log(hi), shape))
        dt = _np.maximum(dt, floor)
        return dt + _np.log(-_np.expm1(-dt))
    return _Filled(make)


def _gated_group_rms_norm(y, z, weight, groups, eps):
    """``RMSNorm(y silu(z))`` over each of ``groups`` equal parts of the
    last axis, the gate first; float32 inside."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = gated.reshape(*gated.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt(
        jnp.square(parts).mean(-1, keepdims=True) + eps)
    return (parts.reshape(gated.shape)
            * weight.astype(jnp.float32)).astype(y.dtype)


_GATED_NORM = Op('gated_group_rms_norm', _gated_group_rms_norm)


class _CausalConv(HybridBlock):
    """The depthwise causal convolution over the positions, with silu:
    ``weight`` (channels, kernel), ``bias`` (channels,) or none."""

    def __init__(self, channels, kernel, use_bias=True):
        super().__init__()
        bound = 1.0 / math.sqrt(kernel)     # Conv1d's own, fan-in = kernel
        draw = _Filled(lambda shape: _np.random.uniform(-bound, bound, shape))
        self.weight = Parameter('weight', shape=(channels, kernel), init=draw)
        self.bias = Parameter('bias', shape=(channels,), init=draw) \
            if use_bias else None

    def forward(self, x):
        return _op('ssm_conv', x, self.weight.data(),
                   None if self.bias is None else self.bias.data())


class _GateNorm(HybridBlock):
    """``RMSNorm(y silu(z))`` over groups of the last axis; ``weight``
    (units,)."""

    def __init__(self, units, groups, eps):
        super().__init__()
        self._groups, self._eps = groups, eps
        self.weight = Parameter('weight', shape=(units,),
                                init=initializer.One())

    def forward(self, y, z):
        return apply_op(
            _GATED_NORM, [y, z, self.weight.data()],
            lambda yr, zr, wr: _gated_group_rms_norm(
                yr, zr, wr, self._groups, self._eps))


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer (Dao & Gu 2024) as the ``nemotron_h`` model type
    has it, (B, T, units) -> (B, T, units).

    With H = ``num_heads``, P = ``head_dim``, d = H P, G = ``n_groups``,
    N = ``state_size``::

        [z (d); xBC (d + 2 G N); dt (H)] = in_proj(u)
        xBC = silu(conv1d(xBC))           depthwise, causal, with a bias
        [x (H x P); B (G x N); C (G x N)] = xBC
        delta = softplus(dt + dt_bias)    A = -exp(A_log)
        S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t,  S_0 = 0
        y_t = S_t C_t + D x_t
        out = out_proj(GroupRMSNorm(y silu(z)))    groups of d / G

    The scan is ``npx.ssm_scan`` in chunks of ``chunk_size``; the state
    starts from zero at every row. Leaves: ``in_proj.weight``,
    ``conv1d.weight`` (d + 2 G N, conv_kernel), ``conv1d.bias``,
    ``dt_bias``, ``A_log``, ``D`` (H each), ``norm.weight`` (d),
    ``out_proj.weight``; initialised as the model type does (``A_log =
    log(1..H)``, ``D = 1``, ``dt_bias`` from step sizes log-uniform in
    ``time_step`` = (min, max, floor)).
    """

    def __init__(self, units, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk_size=128, eps=1e-5,
                 time_step=(0.001, 0.1, 1e-4), weight_initializer=None,
                 **kwargs):
        super().__init__(**kwargs)
        if num_heads % n_groups:
            raise ValueError(f'{num_heads} heads do not split into '
                             f'{n_groups} groups')
        self._heads, self._p = num_heads, head_dim
        self._groups, self._n = n_groups, state_size
        self._chunk = chunk_size
        inner = num_heads * head_dim
        conv = inner + 2 * n_groups * state_size
        self.in_proj = Dense(inner + conv + num_heads, use_bias=False,
                             flatten=False, in_units=units,
                             weight_initializer=weight_initializer)
        self.conv1d = _CausalConv(conv, conv_kernel)
        self.dt_bias = Parameter('dt_bias', shape=(num_heads,),
                                 init=_time_step_bias(*time_step))
        self.A_log = Parameter('A_log', shape=(num_heads,), init=_Filled(
            lambda shape: _np.log(_np.arange(1, shape[0] + 1))))
        self.D = Parameter('D', shape=(num_heads,), init=initializer.One())
        self.norm = _GateNorm(inner, n_groups, eps)
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=inner,
                              weight_initializer=weight_initializer)

    def forward(self, u):
        from ... import np as mnp, npx
        b, t, _ = u.shape
        h, p, g, n = self._heads, self._p, self._groups, self._n
        inner = h * p
        zxbcdt = self.in_proj(u)
        z = zxbcdt[..., :inner]
        xbc = self.conv1d(zxbcdt[..., inner:-h])
        dt = npx.softplus(zxbcdt[..., -h:] + self.dt_bias.data())
        y = _op('ssm_scan', xbc[..., :inner].reshape(b, t, h, p), dt,
                -mnp.exp(self.A_log.data()),
                xbc[..., inner:inner + g * n].reshape(b, t, g, n),
                xbc[..., inner + g * n:].reshape(b, t, g, n),
                self.D.data(), chunk_size=self._chunk)
        return self.out_proj(self.norm(y.reshape(b, t, inner), z))


class ShortConv(HybridBlock):
    """LFM2's gated short convolution (Liquid AI 2025), (B, T, units) ->
    (B, T, units)::

        [b; c; x] = in_proj(u)                 three blocks of units
        s_t = sum_k w[:, k] (b x)_{t - K + 1 + k}   depthwise, causal
        out = out_proj(c s)

    no bias anywhere and **no activation**; positions before a row's
    start read as zero. The gates and the taps are ``npx.short_conv``.
    Leaves: ``in_proj.weight`` (3 units, units), ``conv.weight`` (units,
    kernel), uniform in +-1/sqrt(kernel) as ``Conv1d`` draws it, and
    ``out_proj.weight`` (units, units).
    """

    def __init__(self, units, kernel=3, weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self.in_proj = Dense(3 * units, use_bias=False, flatten=False,
                             in_units=units,
                             weight_initializer=weight_initializer)
        self.conv = _CausalConv(units, kernel, use_bias=False)
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=units,
                              weight_initializer=weight_initializer)

    def forward(self, u):
        n = self._units
        bcx = self.in_proj(u)
        return self.out_proj(_op('short_conv', bcx[..., :n],
                                 bcx[..., n:2 * n], bcx[..., 2 * n:],
                                 self.conv.weight.data()))
