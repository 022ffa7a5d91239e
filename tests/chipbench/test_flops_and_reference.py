"""chipbench/flops/bert.py against closed forms (BERT's own, by name),
and every family's reference against its program at a tiny size on the
CPU: outputs, loss, gradients, one Adam step, the reference in blocks of
rows. The agreement tests find the family from the cell and name none."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from chipbench import check, run
from chipbench.flops import bert as flops
from chipbench.reference import bert as ref

from chipbench_tiny import (ALL_CELLS, CELLS, family_of, load_cell, tiny,
                            tiny_job)

BASE = run.load_json(run.HERE, 'configs', 'bert_base.json')
LARGE = run.load_json(run.HERE, 'configs', 'bert_large.json')
CLASSIFY = {'kind': 'classify', 'num_classes': 2}
MLM = {'kind': 'mlm_nsp'}


def test_one_layer_by_hand():
    cfg = dict(hidden_size=4, intermediate_size=8, num_hidden_layers=1,
               vocab_size=10)
    # one row of 3 tokens: QKV 2*3*4*12, scores and context 2*3*3*4 each,
    # projection 2*3*4*4, FFN 2*2*3*4*8, pooler 2*4*4, classifier 2*4*2
    want = 288 + 72 + 72 + 96 + 384 + 32 + 16
    assert flops.forward_flops(cfg, CLASSIFY, [3]) == want
    assert flops.step_flops(cfg, CLASSIFY, [3]) == 3 * want
    # two predicted positions: transform 2*2*4*4, decoder 2*2*4*10, NSP
    want_mlm = 288 + 72 + 72 + 96 + 384 + 32 + 64 + 160 + 16
    assert flops.forward_flops(cfg, MLM, [3], predicted=2) == want_mlm


def test_padding_earns_nothing():
    full = flops.step_flops(BASE, CLASSIFY, [128] * 4)
    half = flops.step_flops(BASE, CLASSIFY, [64] * 4)
    assert half < full / 2            # attention falls with the square


def test_bert_base_is_six_times_85m_a_token_plus_attention():
    rows, n = 32, 128
    matmul_params = 12 * (4 * 768 * 768 + 2 * 768 * 3072)   # 84.9 M
    attention = 12 * 3 * (4 * n * n * 768)                  # a row
    want = rows * (n * 6 * matmul_params + attention)
    got = flops.step_flops(BASE, CLASSIFY, [n] * rows)
    assert abs(got - want) / want < 0.01


@pytest.mark.parametrize('cfg, job, published', [
    (BASE, CLASSIFY, 109_483_778),      # chip_smoke's count, PR 25
    (BASE, MLM, None), (LARGE, MLM, None)])
def test_param_count_is_the_sum_of_the_leaves(cfg, job, published):
    leaves = sum(int(np.prod(shape))
                 for shape, _ in ref.leaf_specs(cfg, job).values())
    assert flops.param_count(cfg, job) == leaves
    if published:
        assert leaves == published
    assert flops.update_bytes(cfg, job) == 28 * leaves


def test_large_seed_makes_a_key():
    a = ref.init_params(*_tiny_cfg_job(), seed=2 ** 31 + 5)
    b = ref.init_params(*_tiny_cfg_job(), seed=2 ** 31 + 5)
    c = ref.init_params(*_tiny_cfg_job(), seed=5)
    assert np.array_equal(a['word'], b['word'])
    assert not np.array_equal(a['word'], c['word'])


def _tiny_cfg_job():
    cell, cfg = tiny(next(c for c in CELLS
                          if load_cell(c)[1]['family'] == 'bert'))
    return cfg, cell['job']


def test_attention_is_its_part_of_the_step():
    """Scores and context, 2 n^2 U each, forward and the two gradients of
    each, in every layer; what a row's padding would cost is not in it."""
    cfg = dict(hidden_size=4, intermediate_size=8, num_hidden_layers=1,
               vocab_size=10)
    assert flops.attention_flops(cfg, [3]) == 3 * (72 + 72)
    assert flops.attention_flops(cfg, [3, 2]) == 3 * (72 + 72 + 32 + 32)
    # the issue's arithmetic for the pre-train cell: 232 GFLOP a step
    assert flops.attention_flops(BASE, [512] * 8) == \
        12 * 8 * (4 * 512 ** 2 * 768) * 3
    # a part of the step's count: what is left of a layer are its four
    # projections and the two FFN products, 2 n U (4 U + 2 H) forward
    rest = dict(BASE, num_hidden_layers=0)
    layers = flops.step_flops(BASE, MLM, [512] * 8, 76) \
        - flops.step_flops(rest, MLM, [512] * 8, 76)
    assert layers - flops.attention_flops(BASE, [512] * 8) == \
        3 * 12 * 8 * 2 * 512 * 768 * (4 * 768 + 2 * 3072)


def _families_first(cells, key):
    """The first of ``cells`` of each family whose file has ``key``."""
    first = {}
    for name in cells:
        cell, cfg = load_cell(name)
        if cell.get(key):
            first.setdefault(cfg['family'], name)
    return sorted(first.values())


@pytest.fixture(scope='module', params=[
    c for c in ALL_CELLS if not load_cell(c)[0].get('mesh')])
def job(request):
    """A tiny job of the program; its family's reference makes its own
    weights from the same seed."""
    return tiny_job(request.param, 7, mx.cpu(0))


def test_logits_agree(job):
    out = job.forward(job.upload(job.pool[0]))
    got = list(out) if isinstance(out, (tuple, list)) else [out]
    want = job.reference_forward(job.pool[0])
    assert len(got) == len(want) and any(w is not None for w in want)
    for g, w in zip(got, want):
        if w is not None:
            np.testing.assert_allclose(g.asnumpy(), np.asarray(w),
                                       atol=2e-5)


def test_loss_gradients_and_one_adam_step_agree(job):
    first = {k: np.array(v) for k, v in job.param_raws().items()}
    dev = job.upload(job.pool[0])
    with autograd.record():
        loss = job.loss(job.forward(dev), dev)
    loss.backward()
    want_loss, want_grad = job.reference_loss_and_gradients(job.pool[0])
    assert float(loss.asnumpy()) == pytest.approx(float(want_loss),
                                                  rel=1e-5)
    params = job.net.collect_params()
    held = {n for n, p in params.items() if p.grad_req == 'null'}
    # the reference takes a gradient for every leaf the program does
    assert set(want_grad) == set(params) - held
    for name, w in want_grad.items():
        np.testing.assert_allclose(params[name].grad().asnumpy(),
                                   np.asarray(w), atol=1e-5, err_msg=name)
    # one Adam step of the Trainer against the reference's
    job.trainer.step(1)
    follow = job.follow_reference(job.pool[:1])
    moved = check.norms_of(job.param_raws(), minus=first,
                           parts=job.leaf_parts())
    # a leaf the optimizer holds no slot for: in no gradient the family
    # reads, in none of the reference's norms, and where it was
    still = set(moved) - set(follow['change_norms'])
    read = set(check.norms_of(*job.first_gradient_raws()[:1],
                              parts=job.leaf_parts()))
    assert read == set(follow['grad_norms']) == set(follow['change_norms'])
    assert {n.split('[')[0] for n in still} == held
    assert all(moved[n] == 0.0 for n in still)
    for name, w in follow['change_norms'].items():
        # abs: the key's bias moves by round-off alone
        assert moved[name] == pytest.approx(w, rel=1e-3, abs=1e-6), name


@pytest.mark.parametrize('name', _families_first(ALL_CELLS,
                                                 'reference_block_rows'))
def test_the_reference_in_blocks_of_rows_is_the_reference(name):
    """Once a family, on its first cell that states the blocks; a job
    reads them from its cell when it is asked to follow."""
    cell, cfg = tiny(name)
    job = family_of(name).Job(cfg, cell, 3, mx.cpu(0))
    pool = job.pool[:2]
    cell['reference_block_rows'] = cell['batch']
    whole = job.follow_reference(pool)
    cell['reference_block_rows'] = cell['batch'] // 4
    blocks = job.follow_reference(pool)
    assert blocks['losses'] == pytest.approx(whole['losses'], rel=1e-6)
    for k, v in whole['change_norms'].items():
        np.testing.assert_allclose(blocks['change_norms'][k], v, rtol=1e-3,
                                   atol=1e-7)
