"""Forward + backward programs: device time a step of the operations
traced under the program's scope ``mx.attention`` (ops/contrib.py, round
every branch of multi_head_attention: the flash kernel, XLA's masked
attention, the recomputing backward), forward and backward together.
Worst device. Where no operation of the window carries a scope at all
(an executable from a compile cache that an older tree filled), the
kernels named ``mx_flash_attention*`` instead: the forward's kernel
alone, which the run's notes then say (``scoped`` false). Nothing where
neither can be read, never 0.0."""

from . import worst_device

SCOPE = 'mx.attention'
KERNEL = 'mx_flash_attention'


def device_seconds(run):
    """Seconds of the traced window, worst device, or None."""
    if run['trace']['scoped']:
        return worst_device(run, lambda d: d['scope_s'].get(SCOPE))
    return worst_device(run, lambda d: sum(
        v for k, v in d['kernel_s'].items() if k.startswith(KERNEL)) or None)


def read(run):
    steps = run['trace']['steps']
    got = device_seconds(run)
    return got / steps * 1e3 if got and steps else None
