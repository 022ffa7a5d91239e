"""One reader for each end-to-end metric of BENCHMARK.json, found by the
metric's name: ``read(run) -> number``. ``run`` is what ``run.run_cell``
gathered; ``run['window']`` is the measured loop's record."""
