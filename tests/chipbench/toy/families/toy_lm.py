"""The ``toy_lm`` family: the guard that the harness stays generic until
a real second family lands. It is found as ``bert`` is, by the name in
its configuration's file (``chipbench_tiny`` puts this directory on the
path of ``chipbench.families``), and gives what
``chipbench/families/__init__.py`` says a family owns. It has what the
first family lacks: its own pool (rows of tokens, a next-token loss),
leaves stacked over experts that the reference reads in parts, a leaf
the optimizer never moves (``grad_req='null'``), and widths that are
none of BERT's keys. Entered in no BENCHMARK.json; never on a chip.
"""

import contextlib
import copy
import importlib
import time

import numpy as np

from chipbench import check

UPDATE_PROGRAM = 'jit_fused'    # gluon/trainer.py: jax.jit(fused)
ADAM_BETA1 = 0.9

# reference leaf -> the program's name ({i}: the block)
_NAMES = {
    'embed': 'embed.weight',
    'ln_f_g': 'ln_f.gamma', 'ln_f_b': 'ln_f.beta',
    'head_w': 'head.weight',
    'ln1_g': 'block{i}.ln1.gamma', 'ln1_b': 'block{i}.ln1.beta',
    'qkv_w': 'block{i}.qkv.weight', 'qkv_b': 'block{i}.qkv.bias',
    'proj_w': 'block{i}.proj.weight', 'proj_b': 'block{i}.proj.bias',
    'ln2_g': 'block{i}.ln2.gamma', 'ln2_b': 'block{i}.ln2.beta',
    'router_w': 'block{i}.router.weight',
    'router_b': 'block{i}.router_bias',
    'experts_in': 'block{i}.experts_in',
    'experts_out': 'block{i}.experts_out',
}


def program_name(leaf):
    """``l1/qkv_w`` -> ``block1.qkv.weight``."""
    block, _, tail = leaf.rpartition('/')
    return _NAMES[tail].format(i=block[1:])


def by_program_name(tree):
    return {program_name(leaf): a for leaf, a in tree.items()}


def norms_by_program_name(norms):
    """The reference's leaf norms under the names ``check.norms_of``
    gives the program's: ``name`` or, for a leaf read in parts,
    ``name[j]``."""
    return check.named_parts(by_program_name(norms))


def _sibling(kind):
    return importlib.import_module(f'chipbench.{kind}.toy_lm')


def tiny(cell, cfg):
    """(cell, config) at a size a test on the CPU can hold."""
    cell, cfg = copy.deepcopy(cell), copy.deepcopy(cfg)
    cfg.update(hidden_size=32, num_attention_heads=2, expert_size=48,
               vocab_size=512)
    cell.update(batch=8, positions=16, pool=4, reference_block_rows=4)
    return cell, cfg


def make_pool(cell, vocab_size, seed):
    """``cell['pool']`` batches, each ``{'tokens': (batch, positions + 1)
    int32}``: a row's first ``positions`` go in, each predicts the
    next."""
    rng = np.random.default_rng(int(seed))
    return [{'tokens': rng.integers(
        0, vocab_size, (cell['batch'], cell['positions'] + 1))
        .astype(np.int32)} for _ in range(cell['pool'])]


class Job:
    """One cell's training job on ``ctx``, weights and batches from
    ``seed``."""

    def __init__(self, cfg, cell, seed, ctx):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, npx
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.parameter import Parameter
        from mxnet_tpu.ndarray.ndarray import NDArray

        t0 = time.perf_counter()
        self.cfg, self.cell, self.seed, self.ctx = cfg, cell, seed, ctx
        self._mx = mx
        self.reference = _sibling('reference')
        self.flops = _sibling('flops')
        self.pool = make_pool(cell, cfg['vocab_size'], seed)
        u, heads = cfg['hidden_size'], cfg['num_attention_heads']
        e, x = cfg['num_experts'], cfg['expert_size']
        eps = cfg['layer_norm_eps']

        class Block(gluon.nn.HybridBlock):
            def __init__(self):
                super().__init__()
                self.ln1 = nn.LayerNorm(epsilon=eps, in_channels=u)
                self.qkv = nn.Dense(3 * u, flatten=False, in_units=u)
                self.proj = nn.Dense(u, flatten=False, in_units=u)
                self.ln2 = nn.LayerNorm(epsilon=eps, in_channels=u)
                self.router = nn.Dense(e, flatten=False, use_bias=False,
                                       in_units=u)
                self.router_bias = Parameter('router_bias', shape=(e,),
                                             grad_req='null')
                self.experts_in = Parameter('experts_in', shape=(e, x, u))
                self.experts_out = Parameter('experts_out', shape=(e, u, x))
                self.act = nn.SiLU()

            def forward(self, h):
                q, k, v = npx.split(self.qkv(self.ln1(h)), 3, axis=-1)
                h = h + self.proj(npx.multi_head_attention(
                    q, k, v, heads, causal=True))
                n = self.ln2(h)
                gate = mx.np.softmax(
                    self.router(n) + self.router_bias.data(), axis=-1)
                act = self.act(mx.np.einsum('btu,exu->btex', n,
                                            self.experts_in.data()))
                out = mx.np.einsum('btex,eux->bteu', act,
                                   self.experts_out.data())
                return h + (out * mx.np.expand_dims(gate, -1)).sum(axis=2)

        class ToyLM(gluon.nn.HybridBlock):
            def __init__(self):
                super().__init__()
                self.embed = nn.Embedding(cfg['vocab_size'], u)
                self.blocks = []
                for i in range(cfg['num_hidden_layers']):
                    self.blocks.append(Block())
                    self.register_child(self.blocks[-1], f'block{i}')
                self.ln_f = nn.LayerNorm(epsilon=eps, in_channels=u)
                self.head = nn.Dense(cfg['vocab_size'], flatten=False,
                                     use_bias=False, in_units=u)

            def forward(self, tokens):
                h = self.embed(tokens)
                for block in self.blocks:
                    h = block(h)
                return self.head(self.ln_f(h))

        self.net = ToyLM()
        self.net.initialize(mx.initializer.Normal(cfg['initializer_range']),
                            ctx=ctx)
        weights = by_program_name(self.reference.init_params(cfg, seed))
        params = self.net.collect_params()
        if set(weights) != set(params):
            raise RuntimeError(
                'the program and the reference name different leaves: '
                f'{sorted(set(weights) ^ set(params))}')
        for name, p in params.items():
            p.set_data(NDArray(weights[name]))
        self.net.hybridize(static_alloc=True)
        self.trainer = gluon.Trainer(
            params, cfg['optimizer'],
            {'learning_rate': cell['learning_rate']})
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.timing = {'build_s': time.perf_counter() - t0}

    # ------------------------------------------------------ one step's calls
    def scope(self):
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
            self.cfg, rows, length - 1)}

    def update_bytes(self):
        return self.flops.update_bytes(self.cfg)

    # --------------------------------------------- readings for ``correct``
    def leaf_parts(self):
        return {name: self.cfg['num_experts']
                for name in self.net.collect_params()
                if name.rsplit('.', 1)[-1] in self.reference.STACKED}

    def param_raws(self):
        return {n: p.data()._data
                for n, p in self.net.collect_params().items()}

    def first_gradient_raws(self):
        """After exactly one step Adam's first slot is (1 - beta1) g; a
        leaf the Trainer holds no slot for has no gradient to read."""
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
        self.net = self.trainer = self.loss_fn = None

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
