"""The benchmark's own core: cell resolution, traffic generation, weights,
the plain reference, the correctness comparison, the end-to-end arithmetic,
the trace reduction, the byte/FLOP models and the table of peaks.

Everything that belongs to one configuration, traffic mix, cell,
per-layer metric or model family lives in a data file or module of its
own under `bench/configs`, `bench/traffic`, `bench/cells`,
`bench/metrics` and `bench/families`, found by the name that
`BENCHMARK.json` or the configuration file gives it.
"""
