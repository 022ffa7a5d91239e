"""What the tests of chipbench/ share. Tiny sizes for the CPU: every
width of a configuration and every length of a cell shrunk, nothing else
changed; the chip runs the files as they are. And the hand-built trace."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


CELLS = [w['name'] for w in load_bench()['workloads']]
PEAKS = {'flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}     # not a device's


def tiny(name):
    """(cell, config) of a cell of BENCHMARK.json at a size a test can
    hold."""
    from chipbench import run
    cell, cfg = run.load_cell(name)
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


def small_trace():
    """trace_small.json as ``trace_reduce.load`` would give it."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'trace_small.json')) as f:
        raw = json.load(f)
    return {'devices': {int(k): {kk: [tuple(e) for e in vv]
                                 for kk, vv in v.items()}
                        for k, v in raw['devices'].items()},
            'spans': {k: [tuple(e) for e in v]
                      for k, v in raw['spans'].items()}}
