"""The repository's benchmark: one command, cells driven by data.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` measures one cell of ``BENCHMARK.json`` on the chip the
process finds and prints one JSON line. Everything that belongs to one
model family, one source, one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own under this directory, found
by the name in ``BENCHMARK.json``; ``PERF.md`` says what each measures
and why. A new configuration is new files and entries, never an edit:

- ``families/<family>.py``: all that the harness, the kinds and the
  readers know of a model's shape (parameter tree, the program's config,
  engine and trainer hooks, the plain reference, the control, needed
  operations and bytes, which keys are widths and which may be cut). A
  configuration's file names it under ``"family"``.
- ``reference/<family>.py``: the family's plain float32 reference, which
  imports nothing of the program.
- ``published/<source>.json``: the source's own ``config.json`` keys,
  found by its ``source``; added with the first configuration of that
  source and never edited after.
- ``configs/<name>.json``: the configuration as it is run: the published
  keys, ``family``, and ``published`` / ``reduced`` / ``assumed`` (and the
  deployment it is one chip's share of, where it is).
- ``traffic/<mix>.json``, ``workloads/<cell>.json``: the mix's parameters
  and the cell's kind, sizes and limits of ``correct``.
- ``metrics/<metric>.py``: one reader a per-layer metric.
- ``kinds/``, ``reduce/``, ``harness.py``, ``run.py``, ``readings.py``: the
  generic machinery; none of it names a model.
"""
