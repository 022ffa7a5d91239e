"""Device: 1 - (union of the device's operations) / traced window, on
the device that idles most."""

from . import worst_device


def read(run):
    window = run['trace']['window_s']
    return worst_device(run, lambda d: 100.0 * (1 - d['busy_s'] / window))
