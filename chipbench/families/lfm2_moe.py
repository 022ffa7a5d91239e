"""The ``lfm2_moe`` family: gated short convolutions and grouped-query
attention with normalised queries and keys, with sparse experts and a
tied head, trained through the zoo's ``Lfm2MoeForCausalLM`` on
next-token rows.

The job is ``families/deepseek_v3.py``'s (the pool of full rows, the
calls of one step as a user writes them, the readings ``correct`` rests
on; the convolution reads zeros before each row's start), its methods
taken where they name nothing of the family, over this family's net,
reference and count of operations. This file knows the names the zoo
gives the parameters that ``reference/lfm2_moe.py`` makes; the head is
the embedding, one leaf. ``__init__.py`` says what a family owns.
"""

import copy
import importlib
import time

from .. import check
from . import deepseek_v3 as _decoder
from .deepseek_v3 import UPDATE_PROGRAM, make_pool  # noqa: F401

# reference leaf -> the zoo's name ({i}: the layer)
_LAYER = 'model.layers{i}.'
_NAMES = {
    'embed': 'model.embed_tokens.weight',
    'norm_f': 'model.embedding_norm.weight',
    'op_norm': _LAYER + 'operator_norm.weight',
    'ffn_norm': _LAYER + 'ffn_norm.weight',
    'in_w': _LAYER + 'conv.in_proj.weight',
    'conv_w': _LAYER + 'conv.conv.weight',
    'out_w': _LAYER + 'conv.out_proj.weight',
    'q_w': _LAYER + 'self_attn.q_proj.weight',
    'k_w': _LAYER + 'self_attn.k_proj.weight',
    'v_w': _LAYER + 'self_attn.v_proj.weight',
    'o_w': _LAYER + 'self_attn.out_proj.weight',
    'q_norm': _LAYER + 'self_attn.q_layernorm.weight',
    'k_norm': _LAYER + 'self_attn.k_layernorm.weight',
    'w1': _LAYER + 'feed_forward.w1.weight',
    'w3': _LAYER + 'feed_forward.w3.weight',
    'w2': _LAYER + 'feed_forward.w2.weight',
    'router_w': _LAYER + 'feed_forward.router.weight',
    'router_b': _LAYER + 'feed_forward.router_bias',
    'experts_gate': _LAYER + 'feed_forward.experts_gate',
    'experts_up': _LAYER + 'feed_forward.experts_up',
    'experts_down': _LAYER + 'feed_forward.experts_down',
}


def program_name(leaf):
    """``l1/q_w`` -> ``model.layers1.self_attn.q_proj.weight``."""
    layer, _, tail = leaf.rpartition('/')
    return _NAMES[tail].format(i=layer[1:])


def by_program_name(tree):
    return {program_name(leaf): a for leaf, a in tree.items()}


def norms_by_program_name(norms):
    """The reference's leaf norms under the names ``check.norms_of``
    gives the program's: ``name`` or, for a leaf read in parts,
    ``name[j]``."""
    return check.named_parts(by_program_name(norms))


def _sibling(kind):
    return importlib.import_module(
        f'{__package__.rsplit(".", 1)[0]}.{kind}.lfm2_moe')


def tiny(cell, cfg):
    """(cell, config) at a size a test on the CPU can hold: every width
    of the configuration and every length of the cell shrunk, and two
    layers, every kind of mixer and FFN once (the short convolution with
    the dense FFN, attention with experts). Four of eight experts are
    held, two a token; rows of 10."""
    cell, cfg = copy.deepcopy(cell), copy.deepcopy(cfg)
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, num_dense_layers=1,
               layer_types=['conv', 'full_attention'],
               num_experts=4, router_width=8, num_experts_per_tok=2,
               vocab_size=128, max_position_embeddings=64)
    cell.update(batch=4, positions=10, pool=4, reference_block_rows=1)
    return cell, cfg


class Job:
    """One cell's training job on ``ctx``, weights and batches from
    ``seed``."""

    # deepseek_v3's job where a method names no leaf, configuration key
    # or sibling file (each a method of this class, as the contract's
    # tests take one away at a time)
    scope = _decoder.Job.scope
    upload = _decoder.Job.upload
    forward = _decoder.Job.forward
    loss = _decoder.Job.loss
    tokens = _decoder.Job.tokens
    step_flops = _decoder.Job.step_flops
    update_bytes = _decoder.Job.update_bytes
    param_raws = _decoder.Job.param_raws
    first_gradient_raws = _decoder.Job.first_gradient_raws
    reference_batches = _decoder.Job.reference_batches
    free = _decoder.Job.free
    reference_forward = _decoder.Job.reference_forward

    def __init__(self, cfg, cell, seed, ctx):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo.lfm2_moe import (
            Lfm2MoeConfig, Lfm2MoeForCausalLM)
        from mxnet_tpu.ndarray.ndarray import NDArray

        t = [time.perf_counter()]

        def lap():
            t.append(time.perf_counter())
            return t[-1] - t[-2]

        self.cfg, self.cell, self.seed, self.ctx = cfg, cell, seed, ctx
        self._mx = mx
        self.reference = _sibling('reference')
        self.flops = _sibling('flops')
        self.pool = make_pool(cell, cfg['vocab_size'], seed)
        self.timing = {'pool_s': lap()}

        setup = cell.get('job') or {}
        self.net = Lfm2MoeForCausalLM(Lfm2MoeConfig(**cfg))
        # zeros, on the device: every leaf is set from the seed below
        self.net.initialize(mx.initializer.Zero(), ctx=ctx)
        # every leaf's shape is given: no eager forward is needed
        self.timing['initialize_s'] = lap()
        # the benchmark's own weights, made on the device from the seed
        weights = by_program_name(self.reference.init_params(cfg, seed))
        params = self.net.collect_params()
        if set(weights) != set(params):
            raise RuntimeError(
                'the zoo model and the reference name different leaves: '
                f'{sorted(set(weights) ^ set(params))}')
        for name, p in params.items():
            p.set_data(NDArray(weights.pop(name)))
        self.timing['weights_s'] = lap()
        self.net.hybridize(static_alloc=True,
                           remat=bool(setup.get('remat', False)))
        self.trainer = gluon.Trainer(
            params, cfg['optimizer'],
            {'learning_rate': cell['learning_rate']},
            kvstore=setup.get('kvstore', 'device'))
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def part_flops(self, batch):
        """Attention's and the short convolution's operations, and beside
        them the bytes the short convolution moves (its bound)."""
        rows, length = batch['tokens'].shape
        remat = bool((self.cell.get('job') or {}).get('remat', False))
        return {'attention': self.flops.attention_flops(
                    self.cfg, rows, length - 1),
                'short_conv': self.flops.short_conv_flops(
                    self.cfg, rows, length - 1),
                'short_conv_bytes': self.flops.short_conv_bytes(
                    self.cfg, rows, length - 1, remat=remat)}

    def leaf_parts(self):
        """The experts' leaves, read an expert at a time, and the short
        convolution's ``in_proj``, read as its b, c and x blocks."""
        parts = {}
        for name in self.net.collect_params():
            if name.rsplit('.', 1)[-1] in self.reference.STACKED:
                parts[name] = self.cfg['num_experts']
            elif name.endswith('conv.in_proj.weight'):
                parts[name] = self.reference.FUSED['in_w']
        return parts

    def initial_raws(self, like):
        import jax
        w = by_program_name(self.reference.init_params(self.cfg, self.seed))
        names = sorted(w)
        placed = jax.device_put([w[n] for n in names],
                                [like[n].sharding for n in names])
        return dict(zip(names, placed))

    def follow_reference(self, batches, dtype='float32'):
        r = self.reference.follow(
            self.cfg, self.seed, self.reference_batches(batches),
            self.cell['learning_rate'], dtype=dtype,
            block_rows=self.cell['reference_block_rows'])
        return {'losses': r['losses'],
                'grad_norms': norms_by_program_name(r['grad_norms']),
                'change_norms': norms_by_program_name(r['change_norms'])}

    def reference_loss_and_gradients(self, batch):
        import jax
        ref = self.reference
        moved, held = ref.split(ref.init_params(self.cfg, self.seed))
        with jax.default_matmul_precision('highest'):
            loss, grad = jax.value_and_grad(ref.loss_fn)(
                moved, held, self.cfg, jax.numpy.asarray(batch['tokens']))
        return loss, by_program_name(grad)
