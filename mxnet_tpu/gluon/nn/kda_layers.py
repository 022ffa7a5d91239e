"""Linear-attention layers: ``KimiDeltaAttention``, the token mixer of
Kimi Linear (``ops/kda.py`` is the chunked delta rule, ``ops/ssm.py``'s
``ssm_conv`` the short convolutions)."""

import jax
import jax.numpy as jnp
import numpy as _np

from .basic_layers import Dense, _op
from .ssm_layers import _CausalConv, _Filled, _time_step_bias
from ..block import HybridBlock
from ..parameter import Parameter
from ... import initializer
from ...ops.registry import Op, apply_op

__all__ = ['KimiDeltaAttention']

L2_EPS = 1e-6       # of the L2 norm of q and k a head
TIME_STEP = (0.001, 0.1, 1e-4)  # dt_bias's step sizes: min, max, floor


def _sigmoid_gated_head_rms_norm(o, gate, weight, eps):
    """``RMSNorm(o) w sigmoid(gate)`` over the last axis (a head's
    channels); float32 inside."""
    of = o.astype(jnp.float32)
    of = of * jax.lax.rsqrt(jnp.square(of).mean(-1, keepdims=True) + eps)
    return (of * weight.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


_GATED_NORM = Op('sigmoid_gated_head_rms_norm', _sigmoid_gated_head_rms_norm)


class _HeadGateNorm(HybridBlock):
    """``RMSNorm(o) w sigmoid(gate)`` a head; ``weight`` (head_dim,),
    shared by the heads."""

    def __init__(self, head_dim, eps):
        super().__init__()
        self._eps = eps
        self.weight = Parameter('weight', shape=(head_dim,),
                                init=initializer.One())

    def forward(self, o, gate):
        return apply_op(
            _GATED_NORM, [o, gate, self.weight.data()],
            lambda o_, g_, w_: _sigmoid_gated_head_rms_norm(
                o_, g_, w_, self._eps))


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention (Kimi Linear, Moonshot AI 2025) as the
    ``kimi_linear`` model type has it, (B, T, units) -> (B, T, units).

    With H = ``num_heads`` heads of d = ``head_dim`` (keys and values
    alike)::

        q, k, v = silu(conv(W_{q,k,v} u))    depthwise, causal, no bias
        q, k    = q / |q|, k / |k|           a head, eps 1e-6
        log alpha = -exp(A_log_h) softplus(W_fb W_fa u + dt_bias)   a channel
        beta      = sigmoid(W_b u)                                  a head
        o = the gated delta rule over q, k, v, alpha, beta (npx.kda_scan)
        out = o_proj(RMSNorm_head(o) w sigmoid(W_gb W_ga u))

    ``f_a_proj`` and ``g_a_proj`` take ``units`` to d, ``f_b_proj`` and
    ``g_b_proj`` d to H d. The delta rule is ``npx.kda_scan`` in chunks of
    ``chunk_size``; its state starts from zero at every row. Leaves:
    ``q_proj``, ``k_proj``, ``v_proj`` (H d, units), ``q_conv1d.weight``,
    ``k_conv1d.weight``, ``v_conv1d.weight`` (H d, conv_kernel),
    ``f_a_proj``, ``f_b_proj``, ``dt_bias`` (H d,), ``A_log`` (H,),
    ``b_proj`` (H, units), ``g_a_proj``, ``g_b_proj``, ``o_norm.weight``
    (d,), ``o_proj``; ``A_log = log U(1, 16)`` and ``dt_bias`` from step
    sizes log-uniform in ``TIME_STEP`` = (min, max, floor), as Mamba's.
    """

    def __init__(self, units, num_heads, head_dim, conv_kernel=4,
                 chunk_size=64, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._d, self._chunk = num_heads, head_dim, chunk_size
        inner = num_heads * head_dim

        def dense(out, inp):
            return Dense(out, use_bias=False, flatten=False, in_units=inp)

        self.q_proj = dense(inner, units)
        self.k_proj = dense(inner, units)
        self.v_proj = dense(inner, units)
        self.q_conv1d = _CausalConv(inner, conv_kernel, use_bias=False)
        self.k_conv1d = _CausalConv(inner, conv_kernel, use_bias=False)
        self.v_conv1d = _CausalConv(inner, conv_kernel, use_bias=False)
        self.f_a_proj = dense(head_dim, units)
        self.f_b_proj = dense(inner, head_dim)
        self.dt_bias = Parameter('dt_bias', shape=(inner,),
                                 init=_time_step_bias(*TIME_STEP))
        self.A_log = Parameter('A_log', shape=(num_heads,), init=_Filled(
            lambda shape: _np.log(_np.random.uniform(1, 16, shape))))
        self.b_proj = dense(num_heads, units)
        self.g_a_proj = dense(head_dim, units)
        self.g_b_proj = dense(inner, head_dim)
        self.o_norm = _HeadGateNorm(head_dim, eps)
        self.o_proj = dense(units, inner)

    def forward(self, u):
        from ... import np as mnp, npx
        b, t, _ = u.shape
        h, d = self._heads, self._d

        def heads_of(x, normed):
            x = x.reshape(b, t, h, d)
            if normed:
                x = x / mnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)
            return x

        q = heads_of(self.q_conv1d(self.q_proj(u)), True)
        k = heads_of(self.k_conv1d(self.k_proj(u)), True)
        v = heads_of(self.v_conv1d(self.v_proj(u)), False)
        step = npx.softplus(self.f_b_proj(self.f_a_proj(u))
                            + self.dt_bias.data())
        log_alpha = -mnp.exp(self.A_log.data()).reshape(h, 1) \
            * step.reshape(b, t, h, d)
        beta = npx.sigmoid(self.b_proj(u))
        o = _op('kda_scan', q, k, v, log_alpha, beta, chunk_size=self._chunk)
        gate = self.g_b_proj(self.g_a_proj(u)).reshape(b, t, h, d)
        return self.o_proj(self.o_norm(o, gate).reshape(b, t, h * d))
