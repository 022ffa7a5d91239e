"""Readings that a cell's limits are set from (PERF.md section 2 says
how). Not part of a benchmark run.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 [--control] [--faults]

For each seed, in one process: the program's first steps against the
reference (the lower readings); with ``--control`` the reference in
bfloat16, put in the program's place, against the reference (the upper
readings); with ``--faults`` the reference with half of each batch left
out and the mean taken over the rest, and, for a cell on a mesh, with the
rows of one chip only (the exchange between chips left out). One JSON
line a seed. Refuses without the cell's chips, as ``run.py`` does.
"""

import argparse
import gc
import json
import statistics
import sys

from run import first_steps, load_cell, place_compile_cache

from chipbench import check


def cut(batches, rows):
    """The first ``rows`` rows of each host batch."""
    return [{k: v[:rows] if hasattr(v, 'shape') else v for k, v in b.items()}
            for b in batches]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--control', action='store_true')
    ap.add_argument('--faults', action='store_true')
    args = ap.parse_args(argv)
    cell, cfg = load_cell(args.workload)
    if args.faults:
        try:
            kept = check.fault_rows(cell)
        except ValueError as e:
            print(f'{args.workload}: {e}', file=sys.stderr)
            return 2

    import jax
    devs = jax.devices()
    if devs[0].platform != 'tpu' or len(devs) != cell['chips']:
        print(f'need {cell["chips"]} TPU chip(s), found {len(devs)} '
              f'{devs[0].platform}', file=sys.stderr)
        return 1
    import mxnet_tpu as mx
    from chipbench import families
    place_compile_cache()
    family = families.load(cfg['family'])
    for seed in (int(s) for s in args.seeds.split(',')):
        job = family.Job(cfg, cell, seed, mx.tpu(0))
        with job.scope():
            got = first_steps(job, check.STEPS)
        pool = job.pool[:check.STEPS]
        job.free()
        gc.collect()
        want = job.follow_reference(pool)
        numbers, where = check.compare(got, want)
        tail = {}
        for key in ('grad_norms', 'change_norms'):
            floor = statistics.median(want[key].values())
            gaps = sorted(((abs(got[key][n] - w) / max(w, floor), n)
                           for n, w in want[key].items()), reverse=True)
            tail[key] = [[n, round(g, 5), want[key][n]] for g, n in gaps[:4]]
        line = {'seed': seed, 'program': numbers, 'where': where,
                'tail': tail, 'losses': got['losses'], 'reference_losses': want['losses']}
        if args.control:
            line['control_bfloat16'] = check.compare(
                job.follow_reference(pool, dtype='bfloat16'), want)[0]
        if args.faults:
            for fault, rows in kept.items():
                line['fault_' + fault] = check.compare(
                    job.follow_reference(cut(pool, rows)), want)[0]
        print(json.dumps(line), flush=True)
        del job, got, want
        gc.collect()
    return 0


if __name__ == '__main__':
    sys.exit(main())
