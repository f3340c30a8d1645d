"""The benchmark of modalities-tpu: one command, `python benchmark/run.py`, that runs
one cell of BENCHMARK.json on the TPU it is started on and prints one JSON line.

Everything that decides a number lives here, where a PR that claims a gain cannot
reach it: traffic generation, seeded weights, the reduction from trace and counters
to metrics, the table of peaks, the shape functions, the plain reference and the
comparison that decides `correct`. From the program it takes the system under test
(`modalities_tpu`), what it publishes and counts, and the names in its trace.

The harness is driven by data: a cell, a configuration, a traffic mix, a per-layer
metric, a metric reader, a shape function and a traffic generator are each a file
found by the name BENCHMARK.json gives it (see `manifest.py`)."""
