"""On-chip benchmark of the one-shot distributed join.

``python3 -m joinbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix or per-layer metric is a file
of its own, found by the name that ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: one deployment (source, generator, rows,
  columns, chips, what was cut from the source);
- ``traffic/<mix>.json``: one traffic mix, read by the closed
  load loop in ``run.py``;
- ``data/<generator>.py``: a table generator, named by a config;
- ``layers/<metric>.py``: the reader of one per-layer metric.

The yardstick lives here and imports nothing of the program under test:
the generators, the oracle (``oracle.py``), the trace reduction
(``trace.py``), the bytes a join must move (``work.py``) and the chip
peaks (``peaks.py``). From the program the benchmark takes only
``distributed_inner_join``, its program cache and its communicator.
"""
