"""Device: memory_stats()'s peak_bytes_in_use over bytes_limit on the
fullest device, read after the window and before the reference runs."""


def read(run):
    mem = run['memory']
    if not mem['peak_bytes'] or not mem['limit_bytes']:
        return None
    return 100.0 * mem['peak_bytes'] / mem['limit_bytes']
