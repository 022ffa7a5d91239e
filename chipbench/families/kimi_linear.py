"""The ``kimi_linear`` family: Kimi Delta Attention and latent attention
without rotary embedding, with sparse experts, trained through the zoo's
``KimiLinearForCausalLM`` on next-token rows.

The zoo's net is ``deepseek_v3``'s decoder with a mixer chosen a layer,
so the job is ``families/deepseek_v3.py``'s (the pool of full rows, the
calls of one step as a user writes them, the readings ``correct`` rests
on; the delta rule starts from zero at each row), its methods taken
where they name nothing of the family, over this family's net,
reference and count of operations. This file knows the names the
zoo gives the parameters that ``reference/kimi_linear.py`` makes: the
latent attention's and the FFNs' as there, the delta rule's beside
them. ``__init__.py`` says what a family owns.
"""

import copy
import importlib
import time

from .. import check
from . import deepseek_v3 as _decoder
from .deepseek_v3 import UPDATE_PROGRAM, make_pool  # noqa: F401

# reference leaf -> the zoo's name ({i}: the layer)
_MIXER = _decoder._LAYER + 'self_attn.'
_NAMES = dict(_decoder._NAMES, **{
    'k_w': _MIXER + 'k_proj.weight',
    'v_w': _MIXER + 'v_proj.weight',
    'q_conv': _MIXER + 'q_conv1d.weight',
    'k_conv': _MIXER + 'k_conv1d.weight',
    'v_conv': _MIXER + 'v_conv1d.weight',
    'fa_w': _MIXER + 'f_a_proj.weight',
    'fb_w': _MIXER + 'f_b_proj.weight',
    'dt_bias': _MIXER + 'dt_bias',
    'A_log': _MIXER + 'A_log',
    'b_w': _MIXER + 'b_proj.weight',
    'ga_w': _MIXER + 'g_a_proj.weight',
    'gb_w': _MIXER + 'g_b_proj.weight',
    'o_norm': _MIXER + 'o_norm.weight',
})


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
        f'{__package__.rsplit(".", 1)[0]}.{kind}.kimi_linear')


def tiny(cell, cfg):
    """(cell, config) at a size a test on the CPU can hold: every width
    of the configuration and every length of the cell shrunk, and two
    layers, every kind of mixer and FFN once (KDA with the dense FFN,
    latent attention with experts). Four of eight experts are held, two a
    token; chunks of 8 positions and rows of 10, so that the last chunk is
    padded."""
    cell, cfg = copy.deepcopy(cell), copy.deepcopy(cfg)
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, kv_lora_rank=16, head_dim=8,
               num_hidden_layers=2, num_experts=4, router_width=8,
               num_experts_per_token=2, vocab_size=128, chunk_size=8,
               model_max_length=64)
    cfg['linear_attn_config'] = dict(
        cfg['linear_attn_config'], num_heads=2, head_dim=8,
        kda_layers=[1], full_attn_layers=[2])
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
        from mxnet_tpu.gluon.model_zoo.kimi_linear import (
            KimiLinearConfig, KimiLinearForCausalLM)
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
        self.net = KimiLinearForCausalLM(KimiLinearConfig(**cfg))
        # zeros, on the device: every leaf is set from the seed below
        self.net.initialize(mx.initializer.Zero(), ctx=ctx)
        self.timing['initialize_s'] = lap()
        # deferred shapes resolve in an eager forward over a few tokens
        self.net(mx.np.array(self.pool[0]['tokens'][:1, :8], ctx=ctx))
        self.timing['eager_forward_s'] = lap()
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
        rows, length = batch['tokens'].shape
        return {'attention': self.flops.attention_flops(
                    self.cfg, rows, length - 1),
                'kda': self.flops.kda_flops(self.cfg, rows, length - 1)}

    def leaf_parts(self):
        """The experts' leaves, read an expert at a time."""
        return {name: self.cfg['num_experts']
                for name in self.net.collect_params()
                if name.rsplit('.', 1)[-1] in self.reference.STACKED}

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
