"""Update + kernels: the least time for Adam's bytes (read w, g, m, v;
write w, m, v: 28 B a float32 parameter; a chip's share of them under a
mesh) at the HBM peak, over the update program's device time. Bound:
HBM. Reads the same work whether 152 kernels or one fusion do it."""

from . import update_device_ms


def read(run):
    ms = update_device_ms.read(run)
    if not ms:
        return None
    least_s = run['update_bytes'] / run['chips'] / \
        run['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / (ms * 1e-3)
