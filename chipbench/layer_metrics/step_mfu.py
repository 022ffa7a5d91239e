"""Whole step: the FLOPs the forward and backward passes require for the
window's non-padding tokens (flops/<family>.py, from shapes) over window
seconds x chips x the chip's peak (peaks.json)."""


def read(run):
    win = run['window']
    if not win['flops']:
        return None
    peak = run['peaks']['flops_per_s'] * run['chips']
    return 100.0 * win['flops'] / (win['seconds'] * peak)
