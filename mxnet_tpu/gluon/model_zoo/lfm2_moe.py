"""LFM2-MoE decoders (``model_type: lfm2_moe``): gated short convolutions
and grouped-query attention with normalised queries and keys in one
stack, sparse experts, for training.

NEW capability over the reference (its zoo is vision-only). LFM2 and
LFM2-MoE (Liquid AI, 2025). A layer, for a token's hidden vector x
(``n``: RMSNorm, ``x rsqrt(mean(x^2) + eps) w``)::

    h = x + Mixer(operator_norm(x))        y = h + FFN(ffn_norm(h))

and after the last layer ``embedding_norm``, then the head, **tied** to
the embedding. U = ``hidden_size``; no projection has a bias.

The mixer of layer i is given by ``layer_types[i]``. ``conv``,
``gluon.nn.ShortConv`` (``npx.short_conv``)::

    [b; c; x] = W_in u                      W_in (3U, U)
    s_t = sum_{k < K} w[:, k] (b x)_{t - K + 1 + k}   depthwise, causal,
                                            K = conv_L_cache, zeros before
                                            a row's start, no activation
    out = W_out (c s)

``full_attention``: ``q = n_64(W_q u)`` and ``k = n_64(W_k u)`` a head
(``q_layernorm``, ``k_layernorm``: one weight of ``head_dim`` shared by
the heads), ``v = W_v u``; rotary at ``rope_theta`` over all of a head's
channels in the **rotate-half** convention (channel j turns with j +
head_dim/2); causal softmax at ``1 / sqrt(head_dim)``,
``num_attention_heads`` query heads and ``num_key_value_heads`` key/value
heads of ``head_dim = hidden_size / num_attention_heads``; ``out_proj``.

FFN of the first ``num_dense_layers`` layers: ``w2(silu(w1 u) * w3 u)``,
``intermediate_size`` wide. Of the others, ``gluon.nn.SparseExperts``:
``s = sigmoid(W_r u)`` over all experts in float32, the top
``num_experts_per_tok`` of ``s + expert_bias`` (the bias only chooses),
weights ``routed_scaling_factor s_i / sum of the chosen s``
(``norm_topk_prob``); SwiGLU experts of ``moe_intermediate_size``, **no
shared expert**.

What is here and what is not:

* training only: no convolution state or KV cache, no decode;
* ``num_experts`` counts the experts **held** (one chip's share under
  expert parallelism) and ``router_width`` all of them; the exchange
  between chips is not here;
* ``router_bias`` (the published ``expert_bias``) takes no gradient and
  nothing moves it; no auxiliary balance loss;
* the dense FFN's width is ``intermediate_size`` as given: LFM2's
  ``block_auto_adjust_ff_dim`` (two thirds, rounded to
  ``block_multiple_of``) is a key of the dense ``lfm2`` type that
  ``lfm2_moe`` does not publish;
* ``rope_parameters`` of ``rope_type`` ``default`` only; no sliding
  window; no bias on the convolution (``conv_bias``).

Leaves are named as the published checkpoint's where the repo's layers
allow (``model.layers{i}.operator_norm``, ``.conv.in_proj``,
``.conv.conv.weight`` of (U, K) where the checkpoint's is (U, 1, K),
``.self_attn.q_layernorm``, ``.feed_forward.w1``, ``model.embedding_norm``),
the experts stacked in one leaf a projection as in ``deepseek_v3.py``
(``feed_forward.router.weight``, ``router_bias``, ``experts_gate``,
``experts_up``, ``experts_down``).
"""

from ..block import HybridBlock
from .. import nn
from ... import initializer
from .deepseek_v3 import _rotary
from .llama import RMSNorm

__all__ = ['Lfm2MoeConfig', 'Lfm2MoeAttention', 'Lfm2MoeDecoderLayer',
           'Lfm2MoeModel', 'Lfm2MoeForCausalLM']

KINDS = ('conv', 'full_attention')


class Lfm2MoeConfig:
    """The published keys of an ``lfm2_moe`` ``config.json``, plus
    ``router_width`` (all experts; default ``num_experts``) and
    ``first_expert`` where ``num_experts`` is a chip's share. The
    defaults are LFM2-24B-A2B's."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 num_hidden_layers=40, num_attention_heads=32,
                 num_key_value_heads=8, num_experts=64,
                 num_experts_per_tok=4, num_dense_layers=2,
                 layer_types=None, conv_L_cache=3, conv_bias=False,
                 norm_eps=1e-5, rope_parameters=None,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 tie_word_embeddings=True,
                 initializer_range=0.02, router_width=None, first_expert=0,
                 **ignored):
        layer_types = list(layer_types or ['conv', 'conv'] + [
            'full_attention' if i % 4 == 2 else 'conv'
            for i in range(2, 40)])
        rope = dict(rope_parameters or {'rope_theta': 1e6,
                                        'rope_type': 'default'})
        for what, ok in (
                ('a bias on the convolution (conv_bias)', not conv_bias),
                ('an untied head', tie_word_embeddings),
                (f'rope_type {rope.get("rope_type")!r}',
                 rope.get('rope_type', 'default') == 'default'),
                (f'{len(layer_types)} layer_types for {num_hidden_layers} '
                 'layers', len(layer_types) == num_hidden_layers),
                (f'a layer type other than {", ".join(KINDS)}',
                 not set(layer_types) - set(KINDS)),
                (f'{num_attention_heads} heads over '
                 f'{num_key_value_heads} key/value heads',
                 num_attention_heads % num_key_value_heads == 0)):
            if not ok:
                raise NotImplementedError(f'lfm2_moe: {what}')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_dense_layers = num_dense_layers
        self.layer_types = layer_types
        self.conv_L_cache = conv_L_cache
        self.norm_eps = norm_eps
        self.rope_theta = float(rope['rope_theta'])
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.initializer_range = initializer_range
        self.router_width = router_width or num_experts
        self.first_expert = first_expert


def _matrix(cfg):
    return initializer.Normal(cfg.initializer_range)


def _dense(out, inp, init):
    return nn.Dense(out, use_bias=False, flatten=False, in_units=inp,
                    weight_initializer=init)


class Lfm2MoeAttention(HybridBlock):
    """Grouped-query attention with RMS-normalised queries and keys a
    head, rotate-half rotary, causal."""

    def __init__(self, cfg):
        super().__init__()
        self._heads, self._kv = (cfg.num_attention_heads,
                                 cfg.num_key_value_heads)
        self._hd, self._theta = cfg.head_dim, cfg.rope_theta
        init = _matrix(cfg)
        units = cfg.hidden_size
        self.q_proj = _dense(self._heads * self._hd, units, init)
        self.k_proj = _dense(self._kv * self._hd, units, init)
        self.v_proj = _dense(self._kv * self._hd, units, init)
        self.out_proj = _dense(units, self._heads * self._hd, init)
        self.q_layernorm = RMSNorm(self._hd, cfg.norm_eps)
        self.k_layernorm = RMSNorm(self._hd, cfg.norm_eps)

    def forward(self, x):
        from ... import np as mnp, npx
        b, s, _ = x.shape
        rep = self._heads // self._kv

        def rotated(a, heads, norm):
            return _rotary(norm(a.reshape(b, s, heads, self._hd)),
                           self._theta, rotate_half=True)

        q = rotated(self.q_proj(x), self._heads, self.q_layernorm)
        # query head j reads key/value head j // rep
        k = mnp.repeat(rotated(self.k_proj(x), self._kv, self.k_layernorm),
                       rep, axis=2)
        v = mnp.repeat(self.v_proj(x).reshape(b, s, self._kv, self._hd),
                       rep, axis=2)
        out = npx.multi_head_attention(
            q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1),
            self._heads, causal=True)
        return self.out_proj(out)


class Lfm2MoeMLP(HybridBlock):
    """The dense FFN, ``w2(silu(w1 u) * w3 u)``."""

    def __init__(self, cfg):
        super().__init__()
        init, units = _matrix(cfg), cfg.hidden_size
        self.w1 = _dense(cfg.intermediate_size, units, init)
        self.w3 = _dense(cfg.intermediate_size, units, init)
        self.w2 = _dense(units, cfg.intermediate_size, init)

    def forward(self, x):
        from ... import npx
        return self.w2(npx.silu(self.w1(x)) * self.w3(x))


class Lfm2MoeDecoderLayer(HybridBlock):
    """``h = x + mixer(operator_norm(x))``, ``h + FFN(ffn_norm(h))``; the
    mixer is a child named ``conv`` or ``self_attn``, as published."""

    def __init__(self, cfg, layer):
        super().__init__()
        self.operator_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps)
        if cfg.layer_types[layer] == 'conv':
            self._mixer = 'conv'
            self.conv = nn.ShortConv(cfg.hidden_size, cfg.conv_L_cache,
                                     weight_initializer=_matrix(cfg))
        else:
            self._mixer = 'self_attn'
            self.self_attn = Lfm2MoeAttention(cfg)
        self.ffn_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps)
        if layer < cfg.num_dense_layers:
            self.feed_forward = Lfm2MoeMLP(cfg)
        else:
            first = cfg.first_expert
            self.feed_forward = nn.SparseExperts(
                cfg.hidden_size, cfg.router_width, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size, shared=None,
                held=range(first, first + cfg.num_experts),
                score_func='sigmoid', norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                weight_initializer=_matrix(cfg), activation='swiglu')

    def forward(self, x):
        h = x + getattr(self, self._mixer)(self.operator_norm(x))
        return h + self.feed_forward(self.ffn_norm(h))


class Lfm2MoeModel(HybridBlock):
    """Token embedding, the layers of ``layer_types``, ``embedding_norm``."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_initializer=_matrix(cfg))
        self.layers = []
        for i in range(len(cfg.layer_types)):
            self.layers.append(Lfm2MoeDecoderLayer(cfg, i))
            self.register_child(self.layers[-1], f'layers{i}')
        self.embedding_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps)

    def forward(self, token_ids):
        x = self.embed_tokens(token_ids)
        for layer in self.layers:
            x = layer(x)
        return self.embedding_norm(x)


class Lfm2MoeForCausalLM(HybridBlock):
    """(B, S) token ids -> (B, S, vocab) logits; the head is the
    embedding's own matrix (tied), so its gradient is the sum of both
    uses."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = Lfm2MoeModel(cfg)

    def forward(self, token_ids):
        from ... import np as mnp
        return mnp.matmul(self.model(token_ids),
                          self.model.embed_tokens.weight.data().T)
