"""The ``nemotron_h`` family: Mamba-2 mixers, un-gated sparse experts and
grouped-query attention in one tower, trained through the zoo's
``NemotronHForCausalLM`` on next-token rows.

What the harness gets is a :class:`Job`: the hybridized net with its
Trainer, the pool of host batches (rows of ``positions + 1`` ids from the
configuration's slice of the vocabulary; every row is full, so every seed
gives the same work; the scan starts from zero at each row) and the calls
of one training step as a user writes them. A cell's ``job`` says how the
job is set up: ``remat`` (the graph recomputed in the backward pass,
``hybridize(remat=True)``) and ``kvstore`` (``null``: the Trainer makes no
kvstore).

This file is also what knows the program's surface: the names the zoo
gives the parameters that ``reference/nemotron_h.py`` makes, the
Trainer's Adam slots, and the XLA module name of the fused update.
``__init__.py`` says what a family owns.
"""

import contextlib
import copy
import importlib
import time

import numpy as np

from .. import check

UPDATE_PROGRAM = 'jit_fused'    # gluon/trainer.py: jax.jit(fused)
ADAM_BETA1 = 0.9                # the program's default, stated per config

# reference leaf -> the zoo's name ({i}: the layer)
_MIXER = 'backbone.layers{i}.mixer.'
_NAMES = {
    'embed': 'backbone.embeddings.weight',
    'norm_f': 'backbone.norm_f.weight',
    'head': 'lm_head.weight',
    'norm': 'backbone.layers{i}.norm.weight',
    'in_w': _MIXER + 'in_proj.weight',
    'conv_w': _MIXER + 'conv1d.weight',
    'conv_b': _MIXER + 'conv1d.bias',
    'dt_bias': _MIXER + 'dt_bias',
    'A_log': _MIXER + 'A_log',
    'D': _MIXER + 'D',
    'gate_norm': _MIXER + 'norm.weight',
    'out_w': _MIXER + 'out_proj.weight',
    'router_w': _MIXER + 'router.weight',
    'router_b': _MIXER + 'router_bias',
    'experts_up': _MIXER + 'experts_up',
    'experts_down': _MIXER + 'experts_down',
    'shared_up': _MIXER + 'shared.up_proj.weight',
    'shared_down': _MIXER + 'shared.down_proj.weight',
    'q_w': _MIXER + 'q_proj.weight',
    'k_w': _MIXER + 'k_proj.weight',
    'v_w': _MIXER + 'v_proj.weight',
    'o_w': _MIXER + 'o_proj.weight',
    'up_w': _MIXER + 'up_proj.weight',
    'down_w': _MIXER + 'down_proj.weight',
}


def program_name(leaf):
    """``l1/router_w`` -> ``backbone.layers1.mixer.router.weight``."""
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
        f'{__package__.rsplit(".", 1)[0]}.{kind}.nemotron_h')


def tiny(cell, cfg):
    """(cell, config) at a size a test on the CPU can hold: every width
    of the configuration and every length of the cell shrunk, the
    pattern kept. Four of eight experts are held, two a token; chunks of
    4 positions and rows of 10, so that the last chunk is padded."""
    cell, cfg = copy.deepcopy(cell), copy.deepcopy(cfg)
    cfg.update(hidden_size=32, intermediate_size=16,
               moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=24,
               num_attention_heads=4, num_key_value_heads=2, head_dim=8,
               mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
               ssm_state_size=8, chunk_size=4, n_routed_experts=4,
               router_width=8, num_experts_per_tok=2, vocab_size=128,
               max_position_embeddings=64)
    cell.update(batch=4, positions=10, pool=4, reference_block_rows=1)
    return cell, cfg


def make_pool(cell, vocab_size, seed):
    """``cell['pool']`` batches, each ``{'tokens': (batch, positions + 1)
    int32}``, ids uniform over the slice of the vocabulary held here: a
    row's first ``positions`` go in, each predicts the next. Rows are
    full, so every seed gives the same work."""
    if cell.get('lengths') is not None:
        raise ValueError('this family takes full rows only')
    rng = np.random.default_rng(int(seed))
    return [{'tokens': rng.integers(
        0, vocab_size, (cell['batch'], cell['positions'] + 1))
        .astype(np.int32)} for _ in range(cell['pool'])]


class Job:
    """One cell's training job on ``ctx``, weights and batches from
    ``seed``."""

    def __init__(self, cfg, cell, seed, ctx):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo.nemotron_h import (
            NemotronHConfig, NemotronHForCausalLM)
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
        self.net = NemotronHForCausalLM(NemotronHConfig(**cfg))
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

    # ------------------------------------------------------ one step's calls
    def scope(self):
        if self.cell.get('mesh'):
            raise NotImplementedError('this family has no mesh cell yet')
        return contextlib.nullcontext()

    def upload(self, batch):
        arr = lambda a, dt: self._mx.np.array(a.astype(dt), ctx=self.ctx)
        rows = batch['tokens']
        return {'tokens': arr(rows[:, :-1], 'int32'),
                'labels': arr(rows[:, 1:].reshape(-1), 'float32')}

    def forward(self, dev):
        return self.net(dev['tokens'])

    def loss(self, out, dev):
        """A position a row of the loss, the rows of the batch in order:
        the first half of them are the first half of the batch."""
        return self.loss_fn(out.reshape(-1, out.shape[-1]),
                            dev['labels']).mean()

    # ------------------------------------------------- what a batch is worth
    def tokens(self, batch):
        rows, length = batch['tokens'].shape
        return rows * (length - 1)

    def step_flops(self, batch):
        rows, length = batch['tokens'].shape
        return self.flops.step_flops(self.cfg, rows, length - 1)

    def part_flops(self, batch):
        rows, length = batch['tokens'].shape
        return {'attention': self.flops.attention_flops(
                    self.cfg, rows, length - 1),
                'ssm_scan': self.flops.scan_flops(
                    self.cfg, rows, length - 1)}

    def update_bytes(self):
        return self.flops.update_bytes(self.cfg)

    # --------------------------------------------- readings for ``correct``
    def leaf_parts(self):
        """The experts' leaves, read an expert at a time."""
        return {name: self.cfg['n_routed_experts']
                for name in self.net.collect_params()
                if name.rsplit('.', 1)[-1] in self.reference.STACKED}

    def param_raws(self):
        return {n: p.data()._data
                for n, p in self.net.collect_params().items()}

    def first_gradient_raws(self):
        """After exactly one step Adam's first slot is (1 - beta1) g; a
        leaf the Trainer holds no slot for (the routers' correction
        biases) has no gradient to read."""
        idx = {id(p): i for i, p in enumerate(self.trainer._params)}
        states = self.trainer._states
        return {n: states[idx[id(p)]][0]._data
                for n, p in self.net.collect_params().items()
                if idx[id(p)] in states}, 1.0 / (1.0 - ADAM_BETA1)

    def initial_raws(self, like):
        import jax
        w = by_program_name(self.reference.init_params(self.cfg, self.seed))
        names = sorted(w)
        placed = jax.device_put([w[n] for n in names],
                                [like[n].sharding for n in names])
        return dict(zip(names, placed))

    def reference_batches(self, batches):
        return [b['tokens'] for b in batches]

    def follow_reference(self, batches, dtype='float32'):
        r = self.reference.follow(
            self.cfg, self.seed, self.reference_batches(batches),
            self.cell['learning_rate'], dtype=dtype,
            block_rows=self.cell['reference_block_rows'])
        return {'losses': r['losses'],
                'grad_norms': norms_by_program_name(r['grad_norms']),
                'change_norms': norms_by_program_name(r['change_norms'])}

    def free(self):
        """Drop the program's state so the reference has the chip: the
        net and its Trainer, and the eager engine's cached plans, which
        keep what they were traced over alive (on the chip, where eager
        ops are bulked, all four copies of every leaf: 8.4 GB here)."""
        from mxnet_tpu import _bulk
        self.net = self.trainer = self.loss_fn = None
        _bulk.reset()

    # ------------------- the reference's side of the agreement tests
    def reference_forward(self, batch):
        import jax
        ref = self.reference
        with jax.default_matmul_precision('highest'):
            return [ref.logits_of(ref.init_params(self.cfg, self.seed),
                                  self.cfg,
                                  jax.numpy.asarray(batch['tokens'][:, :-1]))]

    def reference_loss_and_gradients(self, batch):
        import jax
        ref = self.reference
        moved, held = ref.split(ref.init_params(self.cfg, self.seed))
        with jax.default_matmul_precision('highest'):
            loss, grad = jax.value_and_grad(ref.loss_fn)(
                moved, held, self.cfg, jax.numpy.asarray(batch['tokens']))
        return loss, by_program_name(grad)
