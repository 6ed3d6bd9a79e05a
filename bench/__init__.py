"""The chip benchmark: one cell of BENCHMARK.json per run.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

* ``bench/configs/<config>.json`` -- the sizes as run, with
  ``bench/configs/<config>.py`` beside it: the objective as a user brings
  it to the library, and the benchmark's own plain copy of its formula
  (the reference);
* ``bench/traffic/<traffic>.json`` -- the parameters of a traffic mix,
  read by the general driver its ``driver`` key names (``bench/batch.py``
  or ``bench/served.py``);
* ``bench/metrics/<metric>.py`` -- the reader of one per-layer metric.

The shared yardstick -- peaks, the trace reduction, the work count, the
reference comparison -- lives in the other modules of this package.
"""
