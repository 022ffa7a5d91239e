"""Forward + backward programs: device time a step in every program of
the step other than the fused update. Worst device."""

from . import worst_device


def read(run):
    steps = run['trace']['steps']
    skip = run['update_program']
    got = worst_device(run, lambda d: sum(
        v for k, v in d['program_s'].items() if k != skip) or None)
    return got / steps * 1e3 if got and steps else None
