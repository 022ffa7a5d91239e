"""Llama model family (decoder-only causal LM).

NEW capability over the reference (its model zoo is vision-only,
python/mxnet/gluon/model_zoo/vision/, and its longest-sequence asset is the
single-device fused attention ops, src/operator/contrib/transformer.cc:650).
This is the long-context flagship of the TPU build:

* pre-norm blocks with RMSNorm (``npx.rms_norm``), rotary position
  embeddings, grouped-query attention and SwiGLU MLP — the Llama-2/3
  architecture family;
* attention runs through the Pallas flash kernel
  (ops/pallas/flash_attention.py) — causal, no materialized score matrix;
* ``llama_partition_rules()`` gives Megatron-style PartitionSpecs for
  ``mx.parallel.shard_params`` so the same Block trains tensor-parallel
  over a mesh 'tp' axis, and sequence-parallel via
  ``mx.parallel.ring_attention`` at the SPMD layer;
* everything is a HybridBlock: one ``hybridize()`` compiles the whole
  decoder into a single XLA executable.
"""

import math
from functools import partial

from jax.sharding import PartitionSpec as P

from ..block import HybridBlock
from ..parameter import Parameter
from .. import nn
from ... import initializer

__all__ = ['LlamaConfig', 'LlamaModel', 'LlamaForCausalLM', 'llama_tiny',
           'llama2_7b', 'llama3_8b', 'get_llama', 'llama_partition_rules']


class LlamaConfig:
    """Architecture hyperparameters. ``rope_theta`` is 1e4 for Llama-2
    lineage, 5e5 for Llama-3 (long-context)."""

    def __init__(self, vocab_size=32000, units=4096, num_layers=32,
                 num_heads=32, num_kv_heads=None, hidden_size=11008,
                 max_length=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
                 tie_word_embeddings=False):
        self.vocab_size = vocab_size
        self.units = units
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.hidden_size = hidden_size
        self.max_length = max_length
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.tie_word_embeddings = tie_word_embeddings
        assert units % num_heads == 0
        assert self.num_heads % self.num_kv_heads == 0


class RMSNorm(HybridBlock):
    """Root-mean-square norm (no mean subtraction, no bias)."""

    def __init__(self, units, epsilon=1e-5):
        super().__init__()
        self._eps = epsilon
        self.weight = Parameter('weight', shape=(units,),
                                init=initializer.One())

    def forward(self, x):
        from ... import npx
        return npx.rms_norm(x, self.weight.data(), eps=self._eps)


def _rope(x, theta, offset=0, rotate_half=False):
    """Apply rotary position embeddings to (B, S, H, Dh) — interleaved
    even/odd-pair convention (NOT HuggingFace's rotate-half: converting HF
    checkpoints requires their q/k weight permutation). Pure function of
    shape: folds into the jit as constants.

    ``rotate_half`` takes HuggingFace's convention instead: channel j is
    paired with channel j + Dh/2 (the two halves rotate together), as
    LFM2 publishes it.

    ``offset`` is a scalar (shared position shift — prefill / lockstep
    decode) or a ``(B,)`` array of per-row positions (continuous-batching
    decode, where every cache slot sits at its own depth)."""
    import jax.numpy as jnp
    _, S, _, Dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    # offset may be a traced scalar (jitted decode step): keep the arange
    # static and add the offset. atleast_1d makes the scalar and per-row
    # cases share one code path: pos is (1, S) or (B, S).
    pos = jnp.atleast_1d(jnp.asarray(offset, jnp.float32))[:, None] \
        + jnp.arange(S, dtype=jnp.float32)
    ang = pos[..., None] * inv[None, None, :]          # (1|B, S, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if rotate_half:
        x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    else:
        x1, x2 = x[..., ::2], x[..., 1::2]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    r1 = xf1 * cos - xf2 * sin
    r2 = xf2 * cos + xf1 * sin
    if rotate_half:
        out = jnp.concatenate([r1, r2], axis=-1)
    else:
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


class LlamaAttention(HybridBlock):
    """Grouped-query attention with RoPE; causal flash kernel.

    num_kv_heads < num_heads shares each K/V head across a group of Q
    heads (the Llama-2-70B / Llama-3 memory-bandwidth optimization); KV
    heads are broadcast to the full head count right before the kernel —
    XLA keeps the broadcast virtual."""

    def __init__(self, cfg):
        super().__init__()
        self._h = cfg.num_heads
        self._kv = cfg.num_kv_heads
        self._dh = cfg.units // cfg.num_heads
        self._theta = cfg.rope_theta
        self.q_proj = nn.Dense(self._h * self._dh, use_bias=False,
                               flatten=False)
        self.k_proj = nn.Dense(self._kv * self._dh, use_bias=False,
                               flatten=False)
        self.v_proj = nn.Dense(self._kv * self._dh, use_bias=False,
                               flatten=False)
        self.o_proj = nn.Dense(cfg.units, use_bias=False, flatten=False)

    def forward(self, x, cache=None, offset=0, pages=None):
        """cache: optional (k_cache, v_cache) raw arrays of shape
        (B, max_len, kv_heads, dh) for incremental decode — new K/V are
        written at ``offset`` (static-shape ``dynamic_update_slice``, the
        TPU-idiomatic KV cache) and attention runs over the cache with an
        absolute-position causal mask. Returns out, or (out, new_cache).

        When ``offset`` is a ``(B,)`` array (continuous-batching decode,
        ``mx.serve.DecodeServer``) each batch row is an independent cache
        slot at its own depth: S must be 1, the new K/V land at
        ``offset[b]`` per row (vectorized scatter) and row b's query
        attends to cache positions ``<= offset[b]``.

        When ``pages`` is given (paged KV, vLLM-style), ``cache`` is the
        GLOBAL page pool ``(num_pages, page_size, kv_heads, dh)`` shared
        by every sequence and ``pages`` is the int32 block table
        ``(B, pages_per_seq)`` mapping row ``b``'s logical positions
        onto pool pages — a traced VALUE, so re-pointing a slot at
        different pages never retraces. Logical position ``p`` of row
        ``b`` lives at ``pool[pages[b, p // page_size], p % page_size]``.
        New K/V are scattered through the block table, then each row's
        logical cache is gathered back for attention; the causal mask is
        identical to the dense layout, so dead rows (block table full of
        the garbage page) compute garbage nobody reads. Supports the
        per-slot decode case (S == 1, ``offset`` is ``(B,)``) and the
        chunked-prefill case (B == 1, ``offset`` a scalar: queries at
        absolute positions ``offset + i``)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from ...ndarray.ndarray import NDArray
        from ...ops.pallas.flash_attention import flash_attention

        B, S, _ = x.shape
        q = self.q_proj(x)._data.reshape(B, S, self._h, self._dh)
        k = self.k_proj(x)._data.reshape(B, S, self._kv, self._dh)
        v = self.v_proj(x)._data.reshape(B, S, self._kv, self._dh)
        q = _rope(q, self._theta, offset=offset)
        k = _rope(k, self._theta, offset=offset)
        per_slot = getattr(offset, 'ndim', 0) == 1

        if cache is not None:
            k_cache, v_cache = cache
            if pages is not None:
                psz = k_cache.shape[1]
                L = pages.shape[1] * psz
                if per_slot:
                    assert S == 1, \
                        'per-slot offsets decode one token per step'
                    pid = pages[jnp.arange(B), offset // psz]      # (B,)
                    k_cache = k_cache.at[pid, offset % psz].set(
                        k[:, 0].astype(k_cache.dtype))
                    v_cache = v_cache.at[pid, offset % psz].set(
                        v[:, 0].astype(v_cache.dtype))
                    # decode reads the pool THROUGH the block table:
                    # Pallas kernel walks pages[b, i] on TPU, the
                    # original gather math runs off-TPU
                    # (ops/contrib.py: paged_attention_decode)
                    from ...ops.contrib import paged_attention_decode
                    out = paged_attention_decode(
                        q[:, 0], k_cache, v_cache, pages,
                        jnp.asarray(offset, jnp.int32),
                        sm_scale=self._dh ** -0.5)
                    out = out.reshape(B, S, self._h * self._dh)
                    return self.o_proj(NDArray(out)), (k_cache, v_cache)
                else:
                    assert B == 1, 'chunked prefill fills one sequence'
                    pos = jnp.asarray(offset, jnp.int32) + jnp.arange(S)
                    pid = pages[0, pos // psz]                     # (S,)
                    k_cache = k_cache.at[pid, pos % psz].set(
                        k[0].astype(k_cache.dtype))
                    v_cache = v_cache.at[pid, pos % psz].set(
                        v[0].astype(v_cache.dtype))
                # gather each row's logical cache out of the pool
                kf = k_cache[pages].reshape(B, L, self._kv, self._dh)
                vf = v_cache[pages].reshape(B, L, self._kv, self._dh)
            else:
                L = k_cache.shape[1]
                if per_slot:
                    assert S == 1, \
                        'per-slot offsets decode one token per step'
                    rows = jnp.arange(B)
                    k_cache = k_cache.at[rows, offset].set(
                        k[:, 0].astype(k_cache.dtype))
                    v_cache = v_cache.at[rows, offset].set(
                        v[:, 0].astype(v_cache.dtype))
                else:
                    k_cache = lax.dynamic_update_slice(
                        k_cache, k.astype(k_cache.dtype),
                        (0, offset, 0, 0))
                    v_cache = lax.dynamic_update_slice(
                        v_cache, v.astype(v_cache.dtype),
                        (0, offset, 0, 0))
                kf, vf = k_cache, v_cache
            rep = self._h // self._kv
            kf = jnp.repeat(kf, rep, 2) if rep > 1 else kf
            vf = jnp.repeat(vf, rep, 2) if rep > 1 else vf
            scores = jnp.einsum(
                'bshd,blhd->bhsl', q.astype(jnp.float32),
                kf.astype(jnp.float32)) * (self._dh ** -0.5)
            if per_slot:
                # row b's single query (absolute position offset[b]) sees
                # its own slots <= offset[b]
                mask = jnp.arange(L)[None, :] <= offset[:, None]  # (B, L)
                scores = jnp.where(mask[:, None, None, :], scores, -1e30)
            else:
                # query i (absolute position offset+i) sees slots <= it
                qpos = offset + jnp.arange(S)[:, None]
                mask = jnp.arange(L)[None, :] <= qpos        # (S, L)
                scores = jnp.where(mask[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum('bhsl,blhd->bshd', probs,
                             vf.astype(jnp.float32)).astype(x.dtype)
            out = out.reshape(B, S, self._h * self._dh)
            return self.o_proj(NDArray(out)), (k_cache, v_cache)

        if self._kv != self._h:
            rep = self._h // self._kv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        out = flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, self._h * self._dh)
        return self.o_proj(NDArray(out))


class LlamaMLP(HybridBlock):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg):
        super().__init__()
        self.gate_proj = nn.Dense(cfg.hidden_size, use_bias=False,
                                  flatten=False)
        self.up_proj = nn.Dense(cfg.hidden_size, use_bias=False,
                                flatten=False)
        self.down_proj = nn.Dense(cfg.units, use_bias=False, flatten=False)

    def forward(self, x):
        from ... import npx
        return self.down_proj(npx.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(HybridBlock):
    """Pre-norm decoder block."""

    def __init__(self, cfg):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.units, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.units, cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cache=None, offset=0, pages=None):
        if cache is None:
            x = x + self.self_attn(self.input_layernorm(x))
            return x + self.mlp(self.post_attention_layernorm(x))
        att, cache = self.self_attn(self.input_layernorm(x), cache=cache,
                                    offset=offset, pages=pages)
        x = x + att
        return x + self.mlp(self.post_attention_layernorm(x)), cache


class LlamaModel(HybridBlock):
    """Token embedding + decoder stack + final norm → hidden states."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.units)
        self.layers = []
        for i in range(cfg.num_layers):
            blk = LlamaBlock(cfg)
            self.register_child(blk, f'layers{i}')
            self.layers.append(blk)
        self.norm = RMSNorm(cfg.units, cfg.rms_norm_eps)

    def forward(self, token_ids, caches=None, offset=0, pages=None):
        x = self.embed_tokens(token_ids)
        if caches is None:
            for blk in self.layers:
                x = blk(x)
            return self.norm(x)
        new_caches = []
        for blk, cache in zip(self.layers, caches):
            x, cache = blk(x, cache=cache, offset=offset, pages=pages)
            new_caches.append(cache)
        return self.norm(x), new_caches


class LlamaForCausalLM(HybridBlock):
    """Decoder LM head: (B, S) int tokens → (B, S, vocab) logits."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    flatten=False)

    def forward(self, token_ids, caches=None, offset=0, pages=None):
        from ... import np as mnp
        if caches is None:
            h = self.model(token_ids)
        else:
            h, caches = self.model(token_ids, caches=caches, offset=offset,
                                   pages=pages)
        if self.cfg.tie_word_embeddings:
            emb = self.model.embed_tokens.weight.data()
            logits = mnp.matmul(h, emb.T)
        else:
            logits = self.lm_head(h)
        return logits if caches is None else (logits, caches)

    def init_caches(self, batch_size, max_length=None, dtype='float32'):
        """Allocate per-layer KV caches: list of (k, v), each
        (B, max_length, kv_heads, dh).

        ``batch_size`` is a free parameter, not hard-wired to one value:
        re-initializing at a different *bucketed* batch size reuses the
        per-step compiled fn as long as the bucket matches — callers with
        varying live batch sizes pad rows up to a bucket (see
        ``generate(batch_bucket=...)``) or hand slots out of a fixed-size
        pool (``mx.serve.DecodeServer``), masking/ignoring retired rows
        instead of retracing."""
        import jax.numpy as jnp
        cfg = self.cfg
        L = max_length or cfg.max_length
        dh = cfg.units // cfg.num_heads
        shape = (batch_size, L, cfg.num_kv_heads, dh)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                for _ in range(cfg.num_layers)]

    def init_paged_pool(self, num_pages, page_size, dtype='float32'):
        """Allocate the paged-KV pool: per layer, (k, v) arrays of shape
        ``(num_pages, page_size, kv_heads, dh)``. Unlike
        :meth:`init_caches` there is no batch dimension — every
        sequence's cache is a set of pages it names through its block
        table (``forward(..., pages=...)``), so pool bytes are a memory
        budget decoupled from both the decode batch shape and any
        per-sequence ``max_length`` reservation."""
        import jax.numpy as jnp
        cfg = self.cfg
        dh = cfg.units // cfg.num_heads
        shape = (num_pages, page_size, cfg.num_kv_heads, dh)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                for _ in range(cfg.num_layers)]

    def _param_run(self):
        """The decode-step closure shared by :meth:`generate` and
        ``mx.serve.DecodeServer``: a pure ``run(praws, tok_raw, caches,
        offset) -> (logits_raw, caches)`` over raw parameter arrays
        (traceable — swaps the raws into the Parameters for the span of
        one forward), plus the current praws mapping."""
        from ... import _tape
        from ...ndarray.ndarray import NDArray

        params = self.collect_params()
        praws = {name: p.data()._data for name, p in params.items()}

        def run(praws_, tok, caches, offset, pages=None):
            saved = []
            prev = _tape.set_recording(False)
            try:
                for name, p in params.items():
                    saved.append((p, p._data))
                    p._data = {c: NDArray(praws_[name]) for c in p._data}
                logits, caches = self.forward(NDArray(tok), caches=caches,
                                              offset=offset, pages=pages)
                return logits._data, caches
            finally:
                for p, d in saved:
                    p._data = d
                _tape.set_recording(prev)

        return run, praws

    def generate(self, token_ids, max_new_tokens=32, max_length=None,
                 temperature=0.0, seed=0, batch_bucket=None):
        """Autoregressive generation with a static-shape KV cache.

        TPU design: prefill is one jitted call over the whole prompt; each
        decode step is ONE jitted call reused for every position (the
        offset enters as a traced scalar, so there is exactly one compile
        for the prefill shape and one for the (B, 1) decode shape — no
        per-position retracing). Greedy when ``temperature == 0``, else
        temperature sampling.

        token_ids: (B, S) NDArray / array of prompt tokens.
        Returns (B, S + max_new_tokens) NDArray.

        ``batch_bucket`` pads the batch dim up to a declared bucket size
        (dummy rows, sliced off the result) so varying live batch sizes
        share ONE set of compiled prefill/decode programs and one cache
        shape — re-running at a different B within the bucket neither
        re-traces the per-step fn nor reallocates a differently-shaped
        cache. Batch rows are independent under causal attention, so the
        dummy rows cannot perturb the real ones.
        """
        import jax
        import jax.numpy as jnp
        from ...ndarray.ndarray import NDArray

        toks = token_ids._data if isinstance(token_ids, NDArray) \
            else jnp.asarray(token_ids)
        toks = toks.astype(jnp.int32)
        B_req, S = toks.shape
        if batch_bucket is not None:
            if batch_bucket < B_req:
                raise ValueError(
                    f'batch_bucket={batch_bucket} smaller than the '
                    f'actual batch {B_req}')
            if batch_bucket > B_req:
                toks = jnp.concatenate(
                    [toks, jnp.zeros((batch_bucket - B_req, S),
                                     jnp.int32)])
        B = toks.shape[0]
        # default cache length is sized from the power-of-two-rounded
        # decode budget (not the tight S + max_new_tokens), so
        # varying-length generate() calls land on a handful of compiled
        # (cache-shape, scan-length) programs instead of one per n
        n_pow2 = 1
        while n_pow2 < max(max_new_tokens - 1, 1):
            n_pow2 *= 2
        L = max_length or min(self.cfg.max_length, S + n_pow2 + 1)
        assert S + max_new_tokens <= L, 'max_length too small'

        run, praws = self._param_run()

        def pick(logits, key):
            last = logits[:, -1, :].astype(jnp.float32)
            if temperature <= 0.0:
                return jnp.argmax(last, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, last / temperature, axis=-1).astype(jnp.int32)

        # compiled steps are cached so repeat generate() calls skip
        # tracing; cache buffers are donated (≙ static_alloc's buffer
        # reuse). The whole decode loop is ONE lax.scan program: no
        # per-token host dispatch at all — the Python-loop equivalent
        # pays a dispatch round-trip per token, which at ~1 ms/token
        # decode speed is a measurable tax. The prefill key excludes
        # n_new (it doesn't depend on it); the scan length does enter
        # the decode key, so n_new is rounded up to a power of two and
        # excess tokens are computed-and-dropped — varying-length
        # generate() calls hit a handful of compiled programs instead of
        # one per distinct n.
        n_rest = max_new_tokens - 1
        n_pad = min(n_pow2, L - S - 1)
        psig = (B, S, L, float(temperature))
        dsig = psig + (n_pad,)
        steps = getattr(self, '_gen_steps', None)
        if steps is None:
            steps = self._gen_steps = {}
        if len(steps) > 16:    # bound compiled-executable growth
            steps.pop(next(iter(steps)))
        if psig in steps:
            prefill = steps[psig]
        else:
            @jax.jit
            def prefill(praws_, tok, caches, key):
                logits, caches = run(praws_, tok, caches, 0)
                return pick(logits, key), caches

            steps[psig] = prefill
        if dsig in steps:
            decode_n = steps[dsig]
        else:
            @partial(jax.jit, donate_argnums=(2,))
            def decode_n(praws_, tok, caches, offset, key):
                def body(carry, _):
                    nxt, ch, off, k = carry
                    k, sub = jax.random.split(k)
                    logits, ch = run(praws_, nxt[:, None], ch, off)
                    nxt = pick(logits, sub)
                    return (nxt, ch, off + 1, k), nxt

                (_, caches_, _, _), toks_out = jax.lax.scan(
                    body, (tok, caches, offset, key), None, length=n_pad)
                return toks_out, caches_    # (n_pad, B)

            steps[dsig] = decode_n

        key = jax.random.PRNGKey(seed)
        caches = self.init_caches(B, L)
        key, sub = jax.random.split(key)
        nxt, caches = prefill(praws, toks, caches, sub)
        out = [toks, nxt[:, None]]
        if max_new_tokens > 1:
            rest, caches = decode_n(praws, nxt, caches,
                                    jnp.asarray(S, jnp.int32), key)
            out.append(rest[:n_rest].T)   # drop pad-to-power-of-2 excess
        full = jnp.concatenate(out, axis=1)
        return NDArray(full[:B_req])      # drop batch-bucket dummy rows


def llama_partition_rules(axis='tp'):
    """(predicate, PartitionSpec) rules for ``mx.parallel.shard_params``:
    Megatron layout — q/k/v/gate/up sharded on the output (head) dim,
    o/down on the input dim, embeddings on the vocab dim, norms replicated.
    gluon Dense stores weight as (units_out, units_in), so the output dim
    is axis 0.

    Derived from the ``mx.sharding`` registry's ``('llama', 'tp')``
    table — one source of truth for every sharded surface — and exposed
    as legacy ``pred(name, shape)`` callables for existing
    ``shard_params`` callers. ``axis`` renames the mesh axis in the
    returned specs ('tp' in the registry)."""
    import re as _re
    from ...sharding import rules_for

    def _remap(spec):
        if axis == 'tp':
            return spec
        out = []
        for e in tuple(spec):
            if isinstance(e, tuple):
                out.append(tuple(axis if a == 'tp' else a for a in e))
            else:
                out.append(axis if e == 'tp' else e)
        return P(*out)

    rules = []
    for pattern, spec in rules_for('llama', 'tp'):
        if callable(pattern) and not isinstance(pattern, _re.Pattern):
            pred = pattern
        else:
            creg = _re.compile(pattern) if isinstance(pattern, str) \
                else pattern

            def pred(name, shape, _c=creg):
                return _c.search(name) is not None
            pred.__name__ = getattr(creg, 'pattern', str(pattern))
        rules.append((pred, _remap(spec)))
    return rules


_LLAMA_CONFIGS = {
    # test-scale config (CI, unit tests)
    'llama_tiny': dict(vocab_size=256, units=64, num_layers=2, num_heads=4,
                       num_kv_heads=2, hidden_size=128, max_length=128,
                       rope_theta=10000.0),
    'llama2_7b': dict(vocab_size=32000, units=4096, num_layers=32,
                      num_heads=32, num_kv_heads=32, hidden_size=11008,
                      max_length=4096, rope_theta=10000.0),
    'llama3_8b': dict(vocab_size=128256, units=4096, num_layers=32,
                      num_heads=32, num_kv_heads=8, hidden_size=14336,
                      max_length=8192, rope_theta=500000.0),
}


def get_llama(name, **kwargs):
    cfg = dict(_LLAMA_CONFIGS[name])
    cfg.update(kwargs)
    return LlamaForCausalLM(LlamaConfig(**cfg))


def llama_tiny(**kwargs):
    """2-layer test-scale Llama (unit tests / smoke runs)."""
    return get_llama('llama_tiny', **kwargs)


def llama2_7b(**kwargs):
    """Llama-2-7B shapes."""
    return get_llama('llama2_7b', **kwargs)


def llama3_8b(**kwargs):
    """Llama-3-8B shapes (GQA 32/8, 500k rope theta)."""
    return get_llama('llama3_8b', **kwargs)


def _hf_to_interleaved(w, num_heads, head_dim):
    """Permute q/k projection rows from HF rotate-half RoPE layout to the
    interleaved even/odd-pair layout `_rope` uses: per head, interleaved
    row 2j is HF row j, row 2j+1 is HF row j + head_dim/2 (both conventions
    then rotate pair j with the same frequency theta^(-2j/d))."""
    import numpy as np
    w = np.asarray(w)
    half = head_dim // 2
    perm = np.empty(head_dim, np.int64)
    perm[0::2] = np.arange(half)
    perm[1::2] = np.arange(half) + half
    w = w.reshape(num_heads, head_dim, -1)[:, perm]
    return w.reshape(num_heads * head_dim, -1)


def load_hf_state_dict(net, state_dict):
    """Load HuggingFace-Transformers Llama weights into an initialized
    :class:`LlamaForCausalLM` (the model-zoo pretrained-load surface, ≙
    model_store.py — local weights only, no downloads).

    ``state_dict``: mapping of HF parameter names to arrays (torch tensors
    or numpy). q/k projections are re-permuted for the interleaved RoPE
    convention (see ``_rope``); everything else maps 1:1.
    """
    import numpy as np

    cfg = net.cfg
    dh = cfg.units // cfg.num_heads

    def to_np(v):
        if hasattr(v, 'detach'):
            v = v.detach().cpu().float().numpy()
        return np.asarray(v, np.float32)

    params = net.collect_params()
    loaded = set()
    for hf_name, value in state_dict.items():
        name = hf_name
        # HF 'model.layers.0.' → gluon child name 'model.layers0.'
        while '.layers.' in name:
            head, rest = name.split('.layers.', 1)
            idx, rest = rest.split('.', 1)
            name = f'{head}.layers{idx}.{rest}'
        if name not in params:
            raise KeyError(f'{hf_name} has no target parameter ({name})')
        v = to_np(value)
        if name.endswith('self_attn.q_proj.weight'):
            v = _hf_to_interleaved(v, cfg.num_heads, dh)
        elif name.endswith('self_attn.k_proj.weight'):
            v = _hf_to_interleaved(v, cfg.num_kv_heads, dh)
        p = params[name]
        if tuple(p.shape) != v.shape:
            raise ValueError(
                f'{hf_name}: shape {v.shape} vs parameter {tuple(p.shape)}')
        p.set_data(v)
        loaded.add(name)
    missing = set(params) - loaded
    if missing:
        raise ValueError(f'checkpoint missing parameters: {sorted(missing)}')
    return net


def from_hf_pretrained(model_path, **config_overrides):
    """Build a LlamaForCausalLM from a local HuggingFace checkpoint
    directory (config.json + weights). Gated on the ``transformers``
    package; never downloads."""
    import json
    import os

    with open(os.path.join(model_path, 'config.json')) as f:
        hf_cfg = json.load(f)
    cfg = dict(
        vocab_size=hf_cfg['vocab_size'], units=hf_cfg['hidden_size'],
        num_layers=hf_cfg['num_hidden_layers'],
        num_heads=hf_cfg['num_attention_heads'],
        num_kv_heads=hf_cfg.get('num_key_value_heads',
                                hf_cfg['num_attention_heads']),
        hidden_size=hf_cfg['intermediate_size'],
        max_length=hf_cfg.get('max_position_embeddings', 4096),
        rope_theta=hf_cfg.get('rope_theta', 10000.0),
        rms_norm_eps=hf_cfg.get('rms_norm_eps', 1e-5),
        tie_word_embeddings=hf_cfg.get('tie_word_embeddings', False))
    cfg.update(config_overrides)
    net = LlamaForCausalLM(LlamaConfig(**cfg))
    net.initialize()
    import numpy as np
    B = 1
    net(__import__('mxnet_tpu').np.zeros((B, 2)))   # materialize params

    import transformers
    hf = transformers.AutoModelForCausalLM.from_pretrained(
        model_path, local_files_only=True)
    load_hf_state_dict(net, hf.state_dict())
    return net
