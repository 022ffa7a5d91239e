"""90th percentile of the time between successive completed steps, over
all steps of the window. A step completes when its loss is ready; the
loop stamps the clock then (two steps stay in flight)."""

import numpy as np


def read(run):
    stamps = run['window']['stamps']
    if len(stamps) < 2:
        return None
    return float(np.percentile(np.diff(stamps), 90)) * 1e3
