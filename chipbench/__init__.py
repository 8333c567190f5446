"""chipbench — the benchmark of record: cells of one model configuration
under one traffic mix, served through `run in=http out=jax`, timed at the
client, reduced from a device trace. See PERF.md and BENCHMARK.json."""
