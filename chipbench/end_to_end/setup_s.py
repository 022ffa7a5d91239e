"""Process start to the first timed dispatch: imports, weights from the
seed, placement, the first steps with their compilation (or the compile
cache's reads), and the readings ``correct`` rests on."""


def read(run):
    return run['seconds_to_window']
