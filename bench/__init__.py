"""The on-chip benchmark: one command runs one cell of BENCHMARK.json
(`bench/run.py`); configurations, traffic mixes and per-layer metrics are
files found by name under `bench/configs`, `bench/traffic` and
`bench/metrics`."""
