"""chipbench/flops/bert.py against closed forms, and
chipbench/reference/bert.py against the zoo's model at a tiny size on
the CPU: logits, both losses, gradients, one Adam step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from chipbench import check, run
from chipbench.families import bert as family
from chipbench.flops import bert as flops
from chipbench.reference import bert as ref

from chipbench_tiny import CELLS, tiny

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
    cell, cfg = tiny(CELLS[0])
    return cfg, cell['job']


@pytest.fixture(scope='module', params=[
    c for c in CELLS if not run.load_cell(c)[0].get('mesh')])
def pair(request):
    """A tiny job of the program and the reference's own weights."""
    cell, cfg = tiny(request.param)
    job = family.Job(cfg, cell, 7, mx.cpu(0))
    return job, ref.init_params(cfg, cell['job'], 7)


def _ref_batch(job, i=0):
    return {k: jnp.asarray(v)
            for k, v in job.reference_batches(job.pool[i:i + 1])[0].items()}


def test_logits_agree(pair):
    job, p = pair
    out = job.forward(job.upload(job.pool[0]))
    b = _ref_batch(job)
    with jax.default_matmul_precision('highest'):
        seq = ref.encode(p, job.cfg, b['tokens'], b['types'],
                         b.get('valid_length'))
        pooled = jnp.tanh(ref.linear(seq[:, 0], p['pooler_w'],
                                     p['pooler_b']))
        if job.kind == 'classify':
            want = [ref.linear(pooled, p['head_w'], p['head_b'])]
            got = [out]
        else:
            want = [ref.linear(pooled, p['nsp_w'], p['nsp_b'])]
            got = [out[1]]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.asnumpy(), np.asarray(w), atol=2e-5)


def test_loss_gradients_and_one_adam_step_agree(pair):
    job, p = pair
    first = {k: jnp.array(v) for k, v in job.param_raws().items()}
    dev = job.upload(job.pool[0])
    with autograd.record():
        loss = job.loss(job.forward(dev), dev)
    loss.backward()
    b = _ref_batch(job)
    with jax.default_matmul_precision('highest'):
        want_loss, want_grad = jax.value_and_grad(ref.loss_fn)(
            p, job.cfg, job.cell['job'], b)
    assert float(loss.asnumpy()) == pytest.approx(float(want_loss),
                                                  rel=1e-5)
    want_grad = family.by_program_name(want_grad)
    params = job.net.collect_params()
    for name, w in want_grad.items():
        np.testing.assert_allclose(params[name].grad().asnumpy(),
                                   np.asarray(w), atol=1e-5, err_msg=name)
    # one Adam step of the Trainer against the reference's
    job.trainer.step(1)
    follow = job.follow_reference(job.pool[:1])
    moved = check.norms_of(job.param_raws(), minus=first,
                           parts=job.leaf_parts())
    assert set(moved) == set(follow['change_norms'])
    for name, w in follow['change_norms'].items():
        # abs: the key's bias moves by round-off alone
        assert moved[name] == pytest.approx(w, rel=1e-3, abs=1e-6), name


def test_the_reference_in_blocks_of_rows_is_the_reference():
    cell, cfg = tiny(CELLS[-1])
    job = family.Job(cfg, cell, 3, mx.cpu(0))
    pool = job.reference_batches(job.pool[:2])
    whole = ref.follow(cfg, cell['job'], 3, pool, 1e-4, block_rows=8)
    blocks = ref.follow(cfg, cell['job'], 3, pool, 1e-4, block_rows=2)
    assert blocks['losses'] == pytest.approx(whole['losses'], rel=1e-6)
    for k, v in whole['change_norms'].items():
        np.testing.assert_allclose(blocks['change_norms'][k], v, rtol=1e-3,
                                   atol=1e-7)
