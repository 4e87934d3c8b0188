"""The repository's benchmark: one command, cells driven by data.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` measures one cell of ``BENCHMARK.json`` on the chip the
process finds and prints one JSON line. Everything that belongs to one
configuration, one traffic mix, one cell or one per-layer metric is a
file of its own under this directory, found by the name in
``BENCHMARK.json``; ``PERF.md`` says what each measures and why.
"""
