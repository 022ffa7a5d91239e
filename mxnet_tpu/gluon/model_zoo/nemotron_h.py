"""Nemotron-H-shaped hybrid decoders (``model_type: nemotron_h``): Mamba-2
mixers, sparse experts and grouped-query attention in one tower, for
training.

NEW capability over the reference (its zoo is vision-only). The family of
Nemotron-H, Nemotron-Nano and the first tower of Nemotron-Labs-TwoTower.
``hybrid_override_pattern`` gives a layer's kind, a character a layer;
every layer is ``x <- x + mixer(n(x))`` with one RMSNorm ``n`` (``x
rsqrt(mean(x^2) + eps) w``), after the last layer ``norm_f``, then the
untied head. No weight has a bias but the convolution. U =
``hidden_size``.

``M``, a Mamba-2 mixer (``gluon.nn.Mamba2Mixer``): H =
``mamba_num_heads``, P = ``mamba_head_dim``, d = H P (``expand`` does not
set it), G = ``n_groups``, N = ``ssm_state_size``::

    [z (d); xBC (d + 2 G N); dt (H)] = in_proj(u)
    xBC = silu(conv1d(xBC))      depthwise, causal, width conv_kernel, bias
    [x (H x P); B (G x N); C (G x N)] = xBC    head h reads group h // (H/G)
    delta = softplus(dt + dt_bias)             A = -exp(A_log)
    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t     S_0 = 0 a row
    y_t = S_t C_t + D x_t
    out = out_proj(GroupRMSNorm(y silu(z)))    groups of d / G, gate first

``E``, sparse experts (``gluon.nn.SparseExperts(activation='relu2')``):
``s = sigmoid(W_r u)`` over all experts in float32, the top
``num_experts_per_tok`` of ``s + b``, weights ``routed_scaling_factor s_i
/ (sum of the chosen s + 1e-20)``; an expert is ``W_down relu(W_up u)^2``,
**no gate**; one shared expert of the same form,
``moe_shared_expert_intermediate_size`` wide, added for every token.

``*``, attention: ``num_attention_heads`` query heads and
``num_key_value_heads`` key/value heads of ``head_dim``, causal softmax at
``1 / sqrt(head_dim)``, each key/value head shared by heads / kv_heads
query heads. **No rotary embedding is applied**: the model type's
attention takes none (the state-space layers carry position);
``rope_theta`` and ``partial_rotary_factor`` are keys this layer does not
read.

``-``, the family's dense MLP: ``W_down relu(W_up u)^2``,
``intermediate_size`` wide.

What is here and what is not:

* training only: the scan starts from zero at every row (no packed rows
  with resets, no state beside a KV cache; serving's);
* the scan is ``npx.ssm_scan``, the chunked form in XLA; K and V are
  repeated to the query's heads before ``npx.multi_head_attention``, as
  ``llama.py`` does;
* parameters are float32 where the published file says bfloat16;
* ``n_routed_experts`` counts the experts **held** (one chip's share
  under expert parallelism) and ``router_width`` all of them; the
  exchange between chips is not here;
* ``n_group = topk_group = 1`` only; no auxiliary balance loss;
  ``router_bias`` takes no gradient and nothing moves it;
* **the second (denoiser) tower of Nemotron-Labs-TwoTower is not here**:
  the published file has no key for it (block length, noise schedule and
  what its adaLN is conditioned on are not given), and nothing stands in
  for it. This is the tower the published file fixes key by key.

Leaves are named as the published checkpoint's
(``backbone.layers{i}.mixer...``, ``backbone.norm_f``, ``lm_head``), the
experts stacked in one leaf a projection as in ``deepseek_v3.py``.
"""

import math

from ..block import HybridBlock
from .. import nn
from ... import initializer
from .llama import RMSNorm

__all__ = ['NemotronHConfig', 'NemotronHAttention', 'NemotronHBlock',
           'NemotronHModel', 'NemotronHForCausalLM']


class NemotronHConfig:
    """The published keys of a ``nemotron_h`` ``config.json``, plus
    ``router_width`` (all experts; default ``n_routed_experts``) and
    ``first_expert`` where ``n_routed_experts`` is a chip's share. The
    defaults are Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 hybrid_override_pattern='MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*'
                 'EMEMEMEM*EMEMEMEME', num_hidden_layers=52,
                 intermediate_size=1856, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_routed_experts=128, n_shared_experts=1,
                 num_experts_per_tok=6, routed_scaling_factor=2.5,
                 norm_topk_prob=True, n_group=1, topk_group=1,
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                 mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, chunk_size=128,
                 expand=2, layer_norm_epsilon=1e-5, norm_eps=1e-5,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, time_step_limit=(0.0, None),
                 mamba_hidden_act='silu', mlp_hidden_act='relu2',
                 use_bias=False, mlp_bias=False, attention_bias=False,
                 mamba_proj_bias=False, use_conv_bias=True,
                 rescale_prenorm_residual=True, initializer_range=0.02,
                 tie_word_embeddings=False, sliding_window=None,
                 rope_theta=10000.0, partial_rotary_factor=1.0,
                 residual_in_fp32=False, router_width=None, first_expert=0,
                 **ignored):
        limit = tuple(time_step_limit)
        for what, ok in (
                ('grouped choice of experts', n_group == topk_group == 1),
                (f'mamba_hidden_act {mamba_hidden_act!r}',
                 mamba_hidden_act == 'silu'),
                (f'mlp_hidden_act {mlp_hidden_act!r}',
                 mlp_hidden_act == 'relu2'),
                ('a bias on a projection', not (
                    use_bias or mlp_bias or attention_bias
                    or mamba_proj_bias)),
                ('a convolution without its bias', use_conv_bias),
                ('a time_step_limit that clips',
                 not limit[0] and limit[1] in (None, math.inf)),
                ('sliding_window', sliding_window is None),
                ('tie_word_embeddings', not tie_word_embeddings),
                (f'a pattern of {len(hybrid_override_pattern)} layers for '
                 f'{num_hidden_layers}',
                 len(hybrid_override_pattern) == num_hidden_layers),
                ('a layer kind other than M, E, *, -',
                 not set(hybrid_override_pattern) - set('ME*-'))):
            if not ok:
                raise NotImplementedError(f'nemotron_h: {what}')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = hybrid_override_pattern
        self.num_hidden_layers = num_hidden_layers
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.layer_norm_epsilon = layer_norm_epsilon
        self.time_step = (time_step_min, time_step_max, time_step_floor)
        self.rescale_prenorm_residual = rescale_prenorm_residual
        self.initializer_range = initializer_range
        self.router_width = router_width or n_routed_experts
        self.first_expert = first_expert

    def initializers(self):
        """(a matrix's, that of a projection back onto the residual
        stream): N(0, initializer_range), the second divided by
        sqrt(num_hidden_layers) under ``rescale_prenorm_residual``."""
        std = self.initializer_range
        onto = std / math.sqrt(self.num_hidden_layers) \
            if self.rescale_prenorm_residual else std
        return initializer.Normal(std), initializer.Normal(onto)


def _dense(out, inp, init):
    return nn.Dense(out, use_bias=False, flatten=False, in_units=inp,
                    weight_initializer=init)


class Relu2MLP(HybridBlock):
    """``down_proj(relu(up_proj(x))^2)``: the family's dense MLP and its
    shared expert."""

    def __init__(self, cfg, width):
        super().__init__()
        matrix, onto = cfg.initializers()
        self.up_proj = _dense(width, cfg.hidden_size, matrix)
        self.down_proj = _dense(cfg.hidden_size, width, onto)

    def forward(self, x):
        from ... import npx
        return self.down_proj(npx.relu(self.up_proj(x)) ** 2)


class NemotronHAttention(HybridBlock):
    """Grouped-query attention, causal, no rotary embedding."""

    def __init__(self, cfg):
        super().__init__()
        self._heads, self._kv = (cfg.num_attention_heads,
                                 cfg.num_key_value_heads)
        self._hd = cfg.head_dim
        matrix, onto = cfg.initializers()
        self.q_proj = _dense(self._heads * self._hd, cfg.hidden_size, matrix)
        self.k_proj = _dense(self._kv * self._hd, cfg.hidden_size, matrix)
        self.v_proj = _dense(self._kv * self._hd, cfg.hidden_size, matrix)
        self.o_proj = _dense(cfg.hidden_size, self._heads * self._hd, onto)

    def forward(self, x):
        from ... import np as mnp, npx
        b, s, _ = x.shape
        rep = self._heads // self._kv

        def to_every_head(a):
            # query head j reads key/value head j // rep
            a = mnp.repeat(a.reshape(b, s, self._kv, self._hd), rep, axis=2)
            return a.reshape(b, s, -1)

        out = npx.multi_head_attention(
            self.q_proj(x), to_every_head(self.k_proj(x)),
            to_every_head(self.v_proj(x)), self._heads, causal=True)
        return self.o_proj(out)


def _mixer(cfg, kind):
    matrix, onto = cfg.initializers()
    if kind == 'M':
        mixer = nn.Mamba2Mixer(
            cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel,
            cfg.chunk_size, cfg.layer_norm_epsilon, cfg.time_step,
            weight_initializer=matrix)
        mixer.out_proj.weight.init = onto
        return mixer
    if kind == 'E':
        first = cfg.first_expert
        shared = cfg.n_shared_experts \
            * cfg.moe_shared_expert_intermediate_size
        mixer = nn.SparseExperts(
            cfg.hidden_size, cfg.router_width, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size,
            shared=Relu2MLP(cfg, shared) if shared else None,
            held=range(first, first + cfg.n_routed_experts),
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            weight_initializer=matrix, activation='relu2')
        mixer.experts_down.init = onto
        return mixer
    if kind == '*':
        return NemotronHAttention(cfg)
    return Relu2MLP(cfg, cfg.intermediate_size)


class NemotronHBlock(HybridBlock):
    """``x + mixer(norm(x))``, the mixer of the layer's kind."""

    def __init__(self, cfg, kind):
        super().__init__()
        self.norm = RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        self.mixer = _mixer(cfg, kind)

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(HybridBlock):
    """Token embedding, the layers of the pattern, the final norm."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embeddings = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_initializer=cfg.initializers()[0])
        self.layers = []
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            self.layers.append(NemotronHBlock(cfg, kind))
            self.register_child(self.layers[-1], f'layers{i}')
        self.norm_f = RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)

    def forward(self, token_ids):
        x = self.embeddings(token_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm_f(x)


class NemotronHForCausalLM(HybridBlock):
    """(B, S) token ids -> (B, S, vocab) logits; the head is untied."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.backbone = NemotronHModel(cfg)
        self.lm_head = _dense(cfg.vocab_size, cfg.hidden_size,
                              cfg.initializers()[0])

    def forward(self, token_ids):
        return self.lm_head(self.backbone(token_ids))
