"""The one generator of training batches: a cell's file of parameters
and a seed in, a pool of host batches out.

A cell's file (``chipbench/workloads/<cell>.json``) gives ``batch``,
``positions``, ``pool`` and

* ``lengths``: null for full-length rows with no mask, or a log-normal
  (``median``, ``sigma``) clipped to ``min``..``max`` real tokens a row,
  the rest padding (id 0) that an attention mask hides;
* ``mlm_predicted``: how many positions of each row carry a masked-LM
  label (0 for none).

Every seed gets the same multiset of row lengths (the distribution's
quantiles, one for each row of the pool) in another order, so the work of
a run does not depend on the seed, only its order and its values do.
"""

import math
import statistics

import numpy as np

FIRST_WORD_ID = 1000     # below it the published vocabulary keeps its
                         # specials and unused slots; 0 is [PAD]


def row_lengths(spec, rows, positions):
    """``rows`` lengths: the quantiles of the clipped log-normal."""
    if spec is None:
        return np.full(rows, positions, np.int32)
    if spec.get('dist') != 'lognormal':
        raise ValueError(f'unknown length distribution {spec!r}')
    normal = statistics.NormalDist(math.log(spec['median']), spec['sigma'])
    q = (np.arange(rows) + 0.5) / rows
    lens = np.exp([normal.inv_cdf(float(x)) for x in q])
    return np.clip(np.rint(lens), spec['min'],
                   min(spec['max'], positions)).astype(np.int32)


def make_pool(cell, vocab_size, seed):
    """``cell['pool']`` batches, each a dict of numpy arrays:

    ``tokens``, ``types`` (B, T) int32; ``lengths`` (B,) int32, the real
    tokens of each row; ``masked`` (bool: rows are padded and need a
    mask); ``labels`` (B,) int32, a two-way label for each row; with
    ``mlm_predicted``: ``mlm_positions`` (B, P) int32, ascending and
    distinct, and ``mlm_labels`` (B, P) int32.
    """
    rng = np.random.default_rng(int(seed))
    b, t, n = cell['batch'], cell['positions'], cell['pool']
    predicted = cell.get('mlm_predicted', 0)
    lens = rng.permutation(row_lengths(cell.get('lengths'), b * n, t))
    pool = []
    for i in range(n):
        ln = lens[i * b:(i + 1) * b]
        real = np.arange(t)[None, :] < ln[:, None]
        tokens = rng.integers(FIRST_WORD_ID, vocab_size, (b, t))
        # a sentence pair: the second segment starts somewhere inside
        cut = rng.integers(1, np.maximum(ln, 2))
        types = (np.arange(t)[None, :] >= cut[:, None]) & real
        batch = {
            'tokens': np.where(real, tokens, 0).astype(np.int32),
            'types': types.astype(np.int32),
            'lengths': ln.astype(np.int32),
            'masked': cell.get('lengths') is not None,
            'labels': rng.integers(0, 2, b).astype(np.int32),
        }
        if predicted:
            pos = np.stack([np.sort(rng.choice(int(k), predicted,
                                               replace=False))
                            for k in ln])
            batch['mlm_positions'] = pos.astype(np.int32)
            batch['mlm_labels'] = rng.integers(
                FIRST_WORD_ID, vocab_size, (b, predicted)).astype(np.int32)
        pool.append(batch)
    return pool
