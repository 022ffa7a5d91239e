"""Kimi-Linear-shaped decoders (``model_type: kimi_linear``): Kimi Delta
Attention and latent attention without rotary embedding in one stack,
sparse experts with a shared expert, for training.

NEW capability over the reference (its zoo is vision-only). Kimi Linear
(Moonshot AI, 2025). A layer, for a token's hidden vector x (``n``:
RMSNorm)::

    h = x + Mixer(n(x))            y = h + FFN(n(h))

The mixer of layer i (1-based, as ``linear_attn_config`` numbers them) is
``gluon.nn.KimiDeltaAttention`` where i is in ``kda_layers`` (the gated
delta rule with a decay per channel, ``npx.kda_scan``: its docstring has
the equations) and ``deepseek_v3.MLAttention`` where it is in
``full_attn_layers``: the expanded latent attention, no query compression,
and under ``mla_use_nope`` **no rotary embedding at all**, the
``qk_rope_head_dim`` columns entering the scores unrotated, scale
``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``.

FFN: a SwiGLU of ``intermediate_size`` below ``first_k_dense_replace``,
else ``gluon.nn.SparseExperts``: sigmoid scores over all ``num_experts``
in float32, the top ``num_experts_per_token`` of score + correction bias,
the weights renormalised over the chosen (``moe_renormalize``) and scaled
by ``routed_scaling_factor``; SwiGLU experts of ``moe_intermediate_size``
and ``num_shared_experts`` shared ones as one SwiGLU, added once.

What is here and what is not:

* training only: the delta rule's state starts from zero at every row
  (no packed rows with resets, no state beside a latent cache);
* ``num_experts`` counts the experts **held** (one chip's share under
  expert parallelism) and ``router_width`` all of them; the exchange
  between chips is not here;
* one expert group only (``num_expert_group = topk_group = 1``); no
  query compression; no multi-token prediction; ``router_bias`` takes no
  gradient and nothing moves it;
* the top-level ``head_dim`` is read by neither mixer.

The decoder is ``deepseek_v3.py``'s, given the mixer of each layer, and
its leaves are named as there (``model.layers{i}.self_attn``, ``.mlp``,
the experts stacked in one leaf a projection), the delta rule's as the
published checkpoint's (``q_conv1d``, ``f_a_proj``, ``A_log``, ...).
"""

from .. import nn
from .deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM, MLAttention

__all__ = ['KimiLinearConfig', 'KimiLinearForCausalLM']


class KimiLinearConfig(DeepseekV3Config):
    """The published keys of a ``kimi_linear`` ``config.json``, held
    under ``DeepseekV3Config``'s names, plus ``router_width`` (all
    experts; default ``num_experts``), ``first_expert`` where
    ``num_experts`` is a chip's share, and ``chunk_size`` of the delta
    rule. The defaults are Kimi-Linear-48B-A3B-Instruct's."""

    model_type = 'kimi_linear'

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 num_hidden_layers=27, num_attention_heads=32,
                 num_experts=256, num_experts_per_token=8,
                 num_shared_experts=1, first_k_dense_replace=1,
                 moe_renormalize=True, moe_router_activation_func='sigmoid',
                 routed_scaling_factor=2.446, use_grouped_topk=True,
                 num_expert_group=1, topk_group=1, num_nextn_predict_layers=0,
                 linear_attn_config=None, rms_norm_eps=1e-5,
                 mla_use_nope=True, chunk_size=64, **published):
        lin = linear_attn_config or {
            'kda_layers': [i for i in range(1, 27) if i % 4],
            'full_attn_layers': [4, 8, 12, 16, 20, 24, 27],
            'num_heads': 32, 'head_dim': 128, 'short_conv_kernel_size': 4}
        kda, full = list(lin['kda_layers']), list(lin['full_attn_layers'])
        for what, ok in (
                ('multi-token prediction (num_nextn_predict_layers)',
                 not num_nextn_predict_layers),
                (f'kda_layers and full_attn_layers that are not layers 1..'
                 f'{num_hidden_layers} once each',
                 sorted(kda + full) == list(range(1, num_hidden_layers + 1)))):
            if not ok:
                raise NotImplementedError(f'{self.model_type}: {what}')
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            num_hidden_layers=num_hidden_layers,
            num_attention_heads=num_attention_heads,
            n_routed_experts=num_experts, n_shared_experts=num_shared_experts,
            num_experts_per_tok=num_experts_per_token,
            first_k_dense_replace=first_k_dense_replace,
            routed_scaling_factor=routed_scaling_factor,
            scoring_func=moe_router_activation_func,
            norm_topk_prob=moe_renormalize,
            n_group=num_expert_group if use_grouped_topk else 1,
            topk_group=topk_group if use_grouped_topk else 1,
            rms_norm_eps=rms_norm_eps, mla_use_nope=mla_use_nope, **published)
        self.kda_layers = frozenset(i - 1 for i in kda)      # 0-based
        self.kda_heads, self.kda_head_dim = lin['num_heads'], lin['head_dim']
        self.conv_kernel = lin['short_conv_kernel_size']
        self.chunk_size = chunk_size


def _mixer(cfg, layer):
    """Kimi Delta Attention where ``linear_attn_config`` lists the layer
    as such, else latent attention."""
    if layer in cfg.kda_layers:
        return nn.KimiDeltaAttention(
            cfg.units, cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel,
            cfg.chunk_size, cfg.rms_norm_eps)
    return MLAttention(cfg)


class KimiLinearForCausalLM(DeepseekV3ForCausalLM):
    """(B, S) token ids -> (B, S, vocab) logits of a ``KimiLinearConfig``;
    the head is untied."""

    def __init__(self, cfg):
        super().__init__(cfg, mixer=_mixer)
