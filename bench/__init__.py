"""The on-chip benchmark: one data-driven harness for every cell.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything that belongs to one
configuration, traffic mix or metric lives in a file of its own, found by
the name the manifest gives:

* ``bench/configs/<config>.json`` — a deployment's sizes and guarantees; its
  ``system`` key names the adapter ``bench/systems/<system>.py`` and the
  plain reference ``bench/systems/<system>_ref.py`` beside it.
* ``bench/traffic/<traffic>.json`` — arrivals and the query mix, read by the
  one generator in ``bench/loadgen.py``.
* ``bench/metrics/<metric>.py`` — a reader with ``read(ctx)`` that returns
  the metric's value, or ``None`` where the run holds nothing to read. A
  quantity split by the end-to-end metric it moves (``<metric>.<part>``)
  may share the reader ``bench/metrics/<metric>.py``.

The harness imports the program under test (``src/repro``) only through the
system adapters; the references import nothing of it.
"""
