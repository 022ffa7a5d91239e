"""How ``correct`` is decided for a training cell.

Set-up drives the one compiled step through its first ``STEPS`` steps,
through the window's own call and feed, and keeps three readings of it:
each step's loss, the norm of every leaf of step 1's gradient as the
optimizer got it (from its first slot after one step), and the norm of
every leaf's change over the steps. After the window the plain reference
follows the same steps from the same seed, and :func:`compare` turns the
two sets of readings into three numbers, each held to a limit of the
cell's file:

``loss_gap``    the widest relative gap of a step's loss;
``grad_gap``    the widest gap, over the leaves, between the program's
                gradient norm and the reference's, over the reference's
                norm of that leaf or of the median leaf, whichever is
                larger;
``change_gap``  the same for the parameters' change, over the leaves that
                the reference's gradient moves (a leaf whose reference
                gradient is under a thousandth of the median leaf's moves
                under Adam by round-off alone and is left out).
"""

import statistics

import numpy as np

STEPS = 3
DEAD_LEAF = 1e-3       # of the median leaf's gradient norm


def fault_rows(cell):
    """{fault: the rows of a batch it keeps}. A cell whose batch leaves
    a fault no row is refused: the reference on an empty batch is a NaN,
    not a reading, and the fault cannot be told from a sound run."""
    rows = {'half_batch': cell['batch'] // 2}
    if cell['chips'] > 1:
        rows['no_exchange'] = cell['batch'] // cell['chips']
    for fault, kept in rows.items():
        if kept < 1:
            raise ValueError(
                f'a batch of {cell["batch"]} row(s) on {cell["chips"]} '
                f'chip(s) leaves the fault {fault} no row to keep: the '
                'cell needs at least two rows, and one a chip')
    return rows


def norms_of(raws, minus=None, scale=1.0, parts=None):
    """{name: raw array} -> {name: float norm}, in one jitted call; of
    ``raws[name] - minus[name]`` where ``minus`` is given. A leaf named in
    ``parts`` ({name: n}) is read as n equal parts along its first axis,
    ``name[0]`` .. ``name[n-1]``: a fused leaf whose parts the reference
    tells apart."""
    import jax
    import jax.numpy as jnp
    names = sorted(raws)
    parts = parts or {}
    split = [parts.get(n, 1) for n in names]

    @jax.jit
    def norms(a, b):
        out = []
        for x, y, k in zip(a, b, split):
            d = x.astype(jnp.float32)
            if y is not None:
                d = d - y.astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(jnp.square(d.reshape(k, -1)), -1)))
        return jnp.concatenate(out)

    got = np.asarray(norms(
        [raws[n] for n in names],
        [minus[n] if minus else None for n in names])) * scale
    keys = [n if k == 1 else f'{n}[{j}]'
            for n, k in zip(names, split) for j in range(k)]
    return dict(zip(keys, got.tolist()))


def named_parts(norms):
    """{name: a norm, or a vector of them, one a part} -> {name: float}
    under the names :func:`norms_of` gives: ``name`` or ``name[j]``."""
    out = {}
    for name, a in norms.items():
        a = np.asarray(a)
        if a.ndim == 0:
            out[name] = float(a)
        else:
            out.update({f'{name}[{j}]': float(x) for j, x in enumerate(a)})
    return out


def worst_leaf(got, want, leaves):
    """Widest |got - want| over max(want of the leaf, median want)."""
    floor = statistics.median(want[n] for n in leaves)
    worst, where = 0.0, None
    for n in leaves:
        gap = abs(got[n] - want[n]) / max(want[n], floor)
        if gap != gap:               # a NaN is the widest there is
            return float('inf'), n
        if gap > worst:
            worst, where = gap, n
    return float(worst), where


def compare(got, want):
    """Two sets of readings -> ({number: value}, {number: leaf})."""
    if len(got['losses']) != len(want['losses']):
        raise ValueError('readings of different lengths')
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got['losses'], want['losses']))
    if not np.isfinite(loss_gap):
        loss_gap = float('inf')
    leaves = sorted(want['grad_norms'])
    grad_gap, grad_leaf = worst_leaf(got['grad_norms'], want['grad_norms'],
                                     leaves)
    alive_over = DEAD_LEAF * statistics.median(
        want['grad_norms'][n] for n in leaves)
    alive = [n for n in leaves if want['grad_norms'][n] >= alive_over]
    change_gap, change_leaf = worst_leaf(got['change_norms'],
                                         want['change_norms'], alive)
    return ({'loss_gap': float(loss_gap), 'grad_gap': grad_gap,
             'change_gap': change_gap},
            {'grad_gap': grad_leaf, 'change_gap': change_leaf,
             'left_out': [n for n in leaves if n not in alive]})


def verdict(numbers, limits):
    """({number: {'value', 'limit'}}, correct). Every limit has to be
    there; a number that is not finite is not correct."""
    table = {k: {'value': v, 'limit': limits[k]} for k, v in numbers.items()}
    ok = all(np.isfinite(e['value']) and e['value'] <= e['limit']
             for e in table.values())
    return table, bool(ok)
