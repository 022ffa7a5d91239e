"""DeepSeek-V3-shaped decoders (``model_type: deepseek_v3``): multi-head
latent attention and sparse experts with shared experts, for training.

NEW capability over the reference (its zoo is vision-only). The family of
DeepSeek-V3, Kanana-2, Moonlight and kin. A layer, for a token's hidden
vector x (``n``: RMSNorm, ``u``: the normed input of the sub-layer)::

    h = x + MLA(n(x))            y = h + FFN(n(h))

MLA, head i (``q_lora_rank`` null: no query compression)::

    [q_nope_i; q_pe_i] = W_q u          [c; k_pe] = W_kva u
    [k_nope_i; v_i]    = W_kvb n(c)
    rotary on q_pe_i and on the one k_pe all heads share
    scores (q_nope_i . k_nope_i + q_pe_i . k_pe) / sqrt(qk_head_dim),
    causal softmax, o = W_o [P_i v_i]

FFN: a SwiGLU (the first ``first_k_dense_replace`` layers) or
``gluon.nn.SparseExperts`` (sigmoid scores over all experts, top-k of
score + correction bias, weights normalised over the chosen and scaled,
shared experts added once).

What is here and what is not:

* training computes the expanded form above (every head's keys and
  values from the latent); the absorbed form, the latent cache and the
  decode path are serving's and are **not here**;
* ``RMSNorm``, ``_rope`` (pairs interleaved, as ``rope_interleave``
  says) and the SwiGLU are ``llama.py``'s;
* attention is ``npx.multi_head_attention`` with a value head narrower
  than the query's and a given scale. On the TPU v5e it takes the flash
  forward kernel with the values zero-padded from 128 to 192 columns and
  the output sliced (the first of the three branches tried that compiled
  and was correct; PERF.md section 4); its backward recomputes in XLA.
  The (B, H, S, S) scores are never kept for the backward pass;
* ``n_routed_experts`` counts the experts **held** (one chip's share
  under expert parallelism) and ``router_width`` all of them; no ``ep``
  axis over chips yet: the exchange between chips is not here;
* ``n_group = topk_group = 1`` only (no grouped choice); no auxiliary
  balance loss; ``router_bias`` takes no gradient and nothing moves it.
"""

import math
import types

from ..block import HybridBlock
from .. import nn
from ...ops.registry import Op, apply_op
from .llama import LlamaMLP, RMSNorm, _rope

__all__ = ['DeepseekV3Config', 'MLAttention', 'DecoderLayer',
           'DeepseekV3Model', 'DeepseekV3ForCausalLM']


class DeepseekV3Config:
    """The published keys of a ``deepseek_v3`` ``config.json``, plus
    ``router_width`` (all experts; default ``n_routed_experts``) and
    ``first_expert`` where ``n_routed_experts`` is a chip's share, and
    ``kimi_linear``'s ``mla_use_nope`` (no rotary embedding)."""

    model_type = 'deepseek_v3'

    def __init__(self, vocab_size=129280, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=61, num_attention_heads=128,
                 n_routed_experts=256, n_shared_experts=1,
                 num_experts_per_tok=8, first_k_dense_replace=3,
                 moe_layer_freq=1, kv_lora_rank=512, q_lora_rank=None,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rms_norm_eps=1e-6, rope_theta=10000.0,
                 routed_scaling_factor=2.5, scoring_func='sigmoid',
                 norm_topk_prob=True, n_group=1, topk_group=1,
                 hidden_act='silu', attention_bias=False, rope_scaling=None,
                 tie_word_embeddings=False, router_width=None,
                 first_expert=0, mla_use_nope=False, **ignored):
        for what, ok in (
                ('query compression (q_lora_rank)', q_lora_rank is None),
                ('grouped choice of experts', n_group == topk_group == 1),
                (f'hidden_act {hidden_act!r}', hidden_act == 'silu'),
                ('attention_bias', not attention_bias),
                ('rope_scaling', rope_scaling is None),
                ('tie_word_embeddings', not tie_word_embeddings)):
            if not ok:
                raise NotImplementedError(f'{self.model_type}: {what}')
        self.vocab_size = vocab_size
        self.units = hidden_size
        self.hidden_size = intermediate_size        # LlamaMLP's names
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.moe_layer_freq = moe_layer_freq
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.mla_use_nope = mla_use_nope
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring_func = scoring_func
        self.norm_topk_prob = norm_topk_prob
        self.router_width = router_width or n_routed_experts
        self.first_expert = first_expert

    def is_sparse(self, layer):
        return (self.n_routed_experts > 0
                and layer >= self.first_k_dense_replace
                and layer % self.moe_layer_freq == 0)


_ROPE = Op('rope', _rope)


def _rotary(x, theta, rotate_half=False):
    """``_rope`` on an NDArray (B, S, H, d), eager or traced."""
    return apply_op(_ROPE, [x], lambda raw: _rope(
        raw, theta, rotate_half=rotate_half))


class MLAttention(HybridBlock):
    """Multi-head latent attention, expanded form, causal. Under the
    published ``mla_use_nope`` (Kimi Linear) no rotary embedding is
    applied: the ``qk_rope_head_dim`` columns of the queries and of the
    one shared key enter the scores as they are."""

    def __init__(self, cfg):
        super().__init__()
        self._heads = cfg.num_heads
        self._nope, self._pe = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self._vd, self._latent = cfg.v_head_dim, cfg.kv_lora_rank
        self._theta = cfg.rope_theta
        self._rotary = not cfg.mla_use_nope
        dense = lambda out, inp: nn.Dense(out, use_bias=False, flatten=False,
                                          in_units=inp)
        self.q_proj = dense(self._heads * (self._nope + self._pe), cfg.units)
        self.kv_a_proj_with_mqa = dense(self._latent + self._pe, cfg.units)
        self.kv_a_layernorm = RMSNorm(self._latent, cfg.rms_norm_eps)
        self.kv_b_proj = dense(self._heads * (self._nope + self._vd),
                               self._latent)
        self.o_proj = dense(cfg.units, self._heads * self._vd)

    def forward(self, x):
        from ... import np as mnp, npx
        b, s, _ = x.shape
        h, nope, pe, vd = self._heads, self._nope, self._pe, self._vd
        q = self.q_proj(x).reshape(b, s, h, nope + pe)
        q_pe = q[..., nope:]
        if self._rotary:
            q_pe = _rotary(q_pe, self._theta)
        kva = self.kv_a_proj_with_mqa(x)
        k_pe = kva[..., self._latent:].reshape(b, s, 1, pe)
        if self._rotary:
            k_pe = _rotary(k_pe, self._theta)
        kv = self.kv_b_proj(self.kv_a_layernorm(kva[..., :self._latent]))
        kv = kv.reshape(b, s, h, nope + vd)
        q = mnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = mnp.concatenate(
            [kv[..., :nope], mnp.broadcast_to(k_pe, (b, s, h, pe))], axis=-1)
        out = npx.multi_head_attention(
            q.reshape(b, s, -1), k.reshape(b, s, -1),
            kv[..., nope:].reshape(b, s, -1), h, causal=True,
            sm_scale=1.0 / math.sqrt(nope + pe))
        return self.o_proj(out)


class DecoderLayer(HybridBlock):
    """Pre-norm: latent attention, or what ``mixer(cfg, layer)`` makes,
    then a dense or a sparse FFN."""

    def __init__(self, cfg, layer, mixer=None):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.units, cfg.rms_norm_eps)
        self.self_attn = mixer(cfg, layer) if mixer else MLAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.units, cfg.rms_norm_eps)
        if cfg.is_sparse(layer):
            first = cfg.first_expert
            # the shared experts are one SwiGLU as wide as all of them
            shared = types.SimpleNamespace(
                units=cfg.units, hidden_size=cfg.n_shared_experts
                * cfg.moe_intermediate_size)
            self.mlp = nn.SparseExperts(
                cfg.units, cfg.router_width, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size,
                shared=LlamaMLP(shared) if cfg.n_shared_experts else None,
                held=range(first, first + cfg.n_routed_experts),
                score_func=cfg.scoring_func,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor)
        else:
            self.mlp = LlamaMLP(cfg)

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))


class DeepseekV3Model(HybridBlock):
    """Token embedding, the layers, the final norm; ``mixer`` as
    ``DecoderLayer`` takes it."""

    def __init__(self, cfg, mixer=None):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.units)
        self.layers = []
        for i in range(cfg.num_layers):
            self.layers.append(DecoderLayer(cfg, i, mixer))
            self.register_child(self.layers[-1], f'layers{i}')
        self.norm = RMSNorm(cfg.units, cfg.rms_norm_eps)

    def forward(self, token_ids):
        x = self.embed_tokens(token_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class DeepseekV3ForCausalLM(HybridBlock):
    """(B, S) token ids -> (B, S, vocab) logits; the head is untied."""

    def __init__(self, cfg, mixer=None):
        super().__init__()
        self.cfg = cfg
        self.model = DeepseekV3Model(cfg, mixer)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                flatten=False, in_units=cfg.units)

    def forward(self, token_ids):
        return self.lm_head(self.model(token_ids))
