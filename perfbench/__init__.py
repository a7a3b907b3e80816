"""perfbench — the repository's benchmark (BENCHMARK.json at the root).

One command runs one cell once:

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by name (perfbench/registry.py); the
yardstick (traffic generation, trace reduction, peaks, operation and byte
counts, plain references, the comparison behind `correct`) lives here and
imports nothing from the program except the system under test."""
