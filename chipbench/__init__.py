"""chipbench — the on-chip benchmark of BENCHMARK.json (PR 23).

One command runs one cell once (`python3 -m chipbench.run`, see run.py). The
yardstick lives here, where later PRs cannot change it: data generation
(datagen.py), the one traffic generator (loadgen.py), the plain references
(queries/), the comparison that decides `correct` (check.py, limits.json), the
reduction from a profiler trace to busy/idle/breakdown (trace.py), the bytes a
query needs and the chip's peaks (roofline.py, peaks.json). engine.py is the
only module that touches the program under test.

Adding to the benchmark needs new files and new BENCHMARK.json entries only:
  a configuration   configs/<name>.json   (+ an entry under "configs"); its
                    `tables` say what it holds: rows, column generators,
                    storage (manifest.py, datagen.py)
  a traffic mix     traffic/<name>.json   (read by loadgen.py); a template
                    entry may carry `params`, one entry a parameter set
  a cell            an entry under "workloads" naming a config and a mix
  a query template  queries/<name>.py     (COLUMNS by table, build, reference)
  a metric          metrics/<name>.json   {"reader": ..., "args": {...}}
  a reader          readers/<name>.py     read(ctx, **args) -> number or None

Tools, none of them part of a benchmark run: selftest.py (CPU self-tests),
limits.py (readings the limit is set from), sweep.py (an open-loop cell's
knee), sets.py (the two sets of runs the bounds are set from), probe_cache.py
(persistent compile cache across processes), record_small_trace.py (the
recorded trace of testdata/).
"""
