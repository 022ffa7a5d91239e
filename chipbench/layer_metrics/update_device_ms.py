"""Update + kernels: device time a step inside the Trainer's fused-update
program, found by the XLA module's name (the family states it), whatever
runs inside it. Worst device."""

from . import worst_device


def read(run):
    steps = run['trace']['steps']
    name = run['update_program']
    got = worst_device(run, lambda d: d['program_s'].get(name))
    return got / steps * 1e3 if got and steps else None
