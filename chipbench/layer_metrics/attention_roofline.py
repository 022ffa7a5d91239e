"""Forward + backward programs: the least time for the attention products
the traced steps require (flops/<family>.py: scores and context of the
real tokens, forward and the two gradients of each, a chip's share of
them, nothing recomputed) at the chip's peak, over the device time under
the scope ``mx.attention``. Bound: MXU. Read only where the window's
operations carry scopes: a kernel's name would give the forward alone."""

from .attention_device_ms import device_seconds


def read(run):
    flops = run['window']['traced']['part_flops'].get('attention')
    if not flops or not run['trace']['scoped']:
        return None
    got = device_seconds(run)
    if not got:
        return None
    least_s = flops / run['chips'] / run['peaks']['flops_per_s']
    return 100.0 * least_s / got
