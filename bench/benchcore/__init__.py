"""The benchmark's own core: cell resolution, traffic generation, weights,
the plain reference, the correctness comparison, the end-to-end arithmetic,
the trace reduction, the byte/FLOP models and the table of peaks.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a data file or reader of its own under
`bench/configs`, `bench/traffic`, `bench/cells` and `bench/metrics`, found
by the name that `BENCHMARK.json` gives it.
"""
