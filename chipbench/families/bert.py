"""The ``bert`` family: the system under test, built through the zoo.

What the harness gets is a :class:`Job`: the hybridized net with its
Trainer, the pool of host batches, and the calls of one training step as
a user writes them (``upload``, ``forward``, ``loss``; the harness does
``backward()`` and ``trainer.step(1)`` itself). A cell's ``job.kind``
picks the head and the loss: ``classify`` is the loop of
``examples/bert_finetune.py``, ``mlm_nsp`` the pre-training loss.

This file is also what knows the program's surface: the names the zoo
gives the parameters that ``reference/bert.py`` makes, the Trainer's Adam
slots, and the XLA module name of the fused update. ``__init__.py`` says
what a family owns.
"""

import contextlib
import copy
import importlib
import time

import numpy as np

from .. import check

UPDATE_PROGRAM = 'jit_fused'    # gluon/trainer.py: jax.jit(fused)
ADAM_BETA1 = 0.9                # the program's default, stated per config

# reference leaf -> the zoo's name ({i}: the layer of a stacked leaf)
_NAMES = {
    'word': 'bert.word_embed.weight',
    'type': 'bert.token_type_embed.weight',
    'pos': 'bert.position_weight',
    'emb_ln_g': 'bert.embed_ln.gamma', 'emb_ln_b': 'bert.embed_ln.beta',
    'pooler_w': 'bert.pooler.weight', 'pooler_b': 'bert.pooler.bias',
    'layers/qkv_w': 'bert.encoder.cell{i}.attention.qkv.weight',
    'layers/qkv_b': 'bert.encoder.cell{i}.attention.qkv.bias',
    'layers/proj_w': 'bert.encoder.cell{i}.attention.proj.weight',
    'layers/proj_b': 'bert.encoder.cell{i}.attention.proj.bias',
    'layers/ln1_g': 'bert.encoder.cell{i}.ln1.gamma',
    'layers/ln1_b': 'bert.encoder.cell{i}.ln1.beta',
    'layers/ffn1_w': 'bert.encoder.cell{i}.ffn1.weight',
    'layers/ffn1_b': 'bert.encoder.cell{i}.ffn1.bias',
    'layers/ffn2_w': 'bert.encoder.cell{i}.ffn2.weight',
    'layers/ffn2_b': 'bert.encoder.cell{i}.ffn2.bias',
    'layers/ln2_g': 'bert.encoder.cell{i}.ln2.gamma',
    'layers/ln2_b': 'bert.encoder.cell{i}.ln2.beta',
    'head_w': 'head.weight', 'head_b': 'head.bias',
    'dec_w': 'bert.decoder_transform.weight',
    'dec_b': 'bert.decoder_transform.bias',
    'dec_ln_g': 'bert.decoder_ln.gamma', 'dec_ln_b': 'bert.decoder_ln.beta',
    'dec_bias': 'bert.decoder_bias',
    'nsp_w': 'bert.classifier.weight', 'nsp_b': 'bert.classifier.bias',
}


def by_program_name(tree):
    """A reference tree ({leaf: array, stacked over layers under
    ``layers/``}) as {the zoo's name: array}."""
    out = {}
    for leaf, a in tree.items():
        if leaf.startswith('layers/'):
            for i in range(a.shape[0]):
                out[_NAMES[leaf].format(i=i)] = a[i]
        else:
            out[_NAMES[leaf]] = a
    return out


def norms_by_program_name(norms):
    """The reference's leaf norms under the names ``check.norms_of``
    gives the program's: ``name`` or, for a leaf read in parts,
    ``name[j]``."""
    return check.named_parts(by_program_name(norms))


def _sibling(kind):
    return importlib.import_module(
        f'{__package__.rsplit(".", 1)[0]}.{kind}.bert')


def tiny(cell, cfg):
    """(cell, config) at a size a test on the CPU can hold: every width
    of the configuration and every length of the cell shrunk, nothing
    else changed."""
    cell, cfg = copy.deepcopy(cell), copy.deepcopy(cfg)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=2,
               num_hidden_layers=2, vocab_size=2000,
               max_position_embeddings=32)
    cell.update(batch=8, positions=32 if cell['lengths'] is None else 16,
                pool=4, reference_block_rows=4)
    if cell.get('lengths'):
        cell['lengths'].update(median=8, min=3, max=16)
    if cell.get('mlm_predicted'):
        cell['mlm_predicted'] = 5
    return cell, cfg


class Job:
    """One cell's training job on ``ctx``, weights and batches from
    ``seed``."""

    def __init__(self, cfg, cell, seed, ctx):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo.bert import get_bert_model
        from mxnet_tpu.ndarray.ndarray import NDArray
        from .. import traffic

        t = [time.perf_counter()]

        def lap():
            t.append(time.perf_counter())
            return t[-1] - t[-2]

        self.cfg, self.cell, self.seed, self.ctx = cfg, cell, seed, ctx
        self.kind = cell['job']['kind']
        self._mx = mx
        self.reference = _sibling('reference')
        self.flops = _sibling('flops')
        self.pool = traffic.make_pool(cell, cfg['vocab_size'], seed)

        self.timing = {'pool_s': lap()}

        pretrain = self.kind == 'mlm_nsp'
        mx.random.seed(int(seed) % (2 ** 31))
        bert = get_bert_model(
            cfg['zoo_name'], vocab_size=cfg['vocab_size'],
            token_type_vocab_size=cfg['type_vocab_size'],
            units=cfg['hidden_size'], hidden_size=cfg['intermediate_size'],
            num_layers=cfg['num_hidden_layers'],
            num_heads=cfg['num_attention_heads'],
            max_length=cfg['max_position_embeddings'],
            dropout=cfg['hidden_dropout_prob'],
            use_decoder=pretrain, use_classifier=pretrain)

        class Classifier(gluon.nn.HybridBlock):
            """examples/bert_finetune.py's head on the pooled output."""

            def __init__(self, classes):
                super().__init__()
                self.bert = bert
                self.head = gluon.nn.Dense(classes)

            def forward(self, tokens, types, valid_length=None):
                _, pooled = self.bert(tokens, types, valid_length)
                return self.head(pooled)

        class Pretrainer(gluon.nn.HybridBlock):
            def __init__(self):
                super().__init__()
                self.bert = bert

            def forward(self, tokens, types):
                _, _, mlm, nsp = self.bert(tokens, types)
                return mlm, nsp

        self.net = Pretrainer() if pretrain else \
            Classifier(cell['job']['num_classes'])
        self.net.initialize(mx.initializer.Normal(cfg['initializer_range']),
                            ctx=ctx)
        self.timing['initialize_s'] = lap()
        # deferred shapes resolve in an eager forward, as in the example
        one = {k: v[:1] if isinstance(v, np.ndarray) else v
               for k, v in self.pool[0].items()}
        self.forward(self.upload(one))
        self.timing['eager_forward_s'] = lap()
        # the benchmark's own weights, made on the device from the seed
        weights = by_program_name(
            self.reference.init_params(cfg, cell['job'], seed))
        params = self.net.collect_params()
        if set(weights) != set(params):
            raise RuntimeError(
                'the zoo model and the reference name different leaves: '
                f'{sorted(set(weights) ^ set(params))}')
        for name, p in params.items():
            p.set_data(NDArray(weights[name]))
        del weights
        self.timing['weights_s'] = lap()
        self.net.hybridize(static_alloc=True)
        self.trainer = gluon.Trainer(
            params, cfg['optimizer'],
            {'learning_rate': cell['learning_rate']})
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # ------------------------------------------------------ one step's calls
    def scope(self):
        """The context the whole loop runs in: the cell's mesh, or none.
        Rules the zoo's table lacks are stated by the cell."""
        mesh = self.cell.get('mesh')
        if not mesh:
            return contextlib.nullcontext()
        from jax.sharding import PartitionSpec as P
        sharding = self._mx.sharding
        rules = None
        if mesh.get('extra_rules'):
            rules = list(sharding.rules_for('bert', 'fsdp')) + [
                (pat, P(*spec)) for pat, spec in mesh['extra_rules']]
        return sharding.mesh(dp=mesh['dp'], rules=rules)

    def upload(self, batch):
        """Host batch -> device arrays, one ``mx.np.array`` each, as the
        example's loop does."""
        arr = lambda a, dt: self._mx.np.array(a.astype(dt), ctx=self.ctx)
        dev = {'tokens': arr(batch['tokens'], 'int32'),
               'types': arr(batch['types'], 'int32')}
        if batch['masked']:
            dev['valid_length'] = arr(batch['lengths'], 'int32')
        dev['labels'] = arr(batch['labels'], 'float32')
        if self.kind == 'mlm_nsp':
            b, t = batch['tokens'].shape
            labels = np.zeros((b, t), 'float32')
            weight = np.zeros((b, t), 'float32')
            rows = np.arange(b)[:, None]
            labels[rows, batch['mlm_positions']] = batch['mlm_labels']
            weight[rows, batch['mlm_positions']] = 1.0
            dev['mlm_labels'] = arr(labels.reshape(-1), 'float32')
            dev['mlm_weight'] = arr(weight.reshape(-1), 'float32')
            dev['mlm_count'] = float(weight.sum())
        return dev

    def forward(self, dev):
        if self.kind == 'mlm_nsp':
            return self.net(dev['tokens'], dev['types'])
        if 'valid_length' in dev:
            return self.net(dev['tokens'], dev['types'], dev['valid_length'])
        return self.net(dev['tokens'], dev['types'])

    def loss(self, out, dev):
        """The eager loss ops (they go through the bulking engine)."""
        if self.kind == 'classify':
            return self.loss_fn(out, dev['labels']).mean()
        mlm, nsp = out
        mlm = self.loss_fn(mlm.reshape(-1, mlm.shape[-1]),
                           dev['mlm_labels'], dev['mlm_weight'])
        return mlm.sum() / dev['mlm_count'] + \
            self.loss_fn(nsp, dev['labels']).mean()

    # ------------------------------------------------- what a batch is worth
    def tokens(self, batch):
        return int(batch['lengths'].sum())

    def step_flops(self, batch):
        return self.flops.step_flops(
            self.cfg, self.cell['job'], batch['lengths'],
            self.cell.get('mlm_predicted', 0))

    def part_flops(self, batch):
        return {'attention': self.flops.attention_flops(
            self.cfg, batch['lengths'])}

    def update_bytes(self):
        return self.flops.update_bytes(self.cfg, self.cell['job'])

    # --------------------------------------------- readings for ``correct``
    def leaf_parts(self):
        """Program leaves that the reference reads as equal parts."""
        fused = {_NAMES[k].split('{i}')[-1]: n
                 for k, n in self.reference.FUSED.items()}
        return {name: n for name in self.net.collect_params()
                for tail, n in fused.items() if name.endswith(tail)}

    def param_raws(self):
        return {n: p.data()._data
                for n, p in self.net.collect_params().items()}

    def first_gradient_raws(self):
        """After exactly one step Adam's first slot is (1 - beta1) g."""
        params = self.net.collect_params()
        idx = {id(p): i for i, p in enumerate(self.trainer._params)}
        return {n: self.trainer._states[idx[id(p)]][0]._data
                for n, p in params.items()}, 1.0 / (1.0 - ADAM_BETA1)

    def initial_raws(self, like):
        """The seed's weights again, each laid out as ``like[name]``."""
        import jax
        w = by_program_name(self.reference.init_params(
            self.cfg, self.cell['job'], self.seed))
        names = sorted(w)
        placed = jax.device_put([w[n] for n in names],
                                [like[n].sharding for n in names])
        return dict(zip(names, placed))

    def reference_batches(self, batches):
        """Host batches as the reference takes them."""
        out = []
        for b in batches:
            r = {'tokens': b['tokens'], 'types': b['types']}
            if b['masked']:
                r['valid_length'] = b['lengths']
            if self.kind == 'classify':
                r['labels'] = b['labels']
            else:
                r['mlm_positions'] = b['mlm_positions']
                r['mlm_labels'] = b['mlm_labels']
                r['nsp_labels'] = b['labels']
            out.append(r)
        return out

    def follow_reference(self, batches, dtype='float32'):
        """The reference's readings over ``batches``, by the zoo's names."""
        r = self.reference.follow(
            self.cfg, self.cell['job'], self.seed,
            self.reference_batches(batches), self.cell['learning_rate'],
            dtype=dtype, block_rows=self.cell['reference_block_rows'])
        return {'losses': r['losses'],
                'grad_norms': norms_by_program_name(r['grad_norms']),
                'change_norms': norms_by_program_name(r['change_norms'])}

    def free(self):
        """Drop the program's state so the reference has the chip."""
        self.net = self.trainer = self.loss_fn = None

    # ------------------- the reference's side of the agreement tests
    def _reference_batch(self, batch):
        import jax.numpy as jnp
        return {k: jnp.asarray(v) for k, v in
                self.reference_batches([batch])[0].items()}

    def reference_forward(self, batch):
        """What the reference gives for each output of ``forward``: the
        classifier's logits, or (None, the next-sentence logits): the
        reference makes the MLM logits at the predicted positions only."""
        import jax
        import jax.numpy as jnp
        ref = self.reference
        p = ref.init_params(self.cfg, self.cell['job'], self.seed)
        b = self._reference_batch(batch)
        with jax.default_matmul_precision('highest'):
            seq = ref.encode(p, self.cfg, b['tokens'], b['types'],
                             b.get('valid_length'))
            pooled = jnp.tanh(ref.linear(seq[:, 0], p['pooler_w'],
                                         p['pooler_b']))
            if self.kind == 'classify':
                return [ref.linear(pooled, p['head_w'], p['head_b'])]
            return [None, ref.linear(pooled, p['nsp_w'], p['nsp_b'])]

    def reference_loss_and_gradients(self, batch):
        import jax
        ref = self.reference
        p = ref.init_params(self.cfg, self.cell['job'], self.seed)
        with jax.default_matmul_precision('highest'):
            loss, grad = jax.value_and_grad(ref.loss_fn)(
                p, self.cfg, self.cell['job'], self._reference_batch(batch))
        return loss, by_program_name(grad)
