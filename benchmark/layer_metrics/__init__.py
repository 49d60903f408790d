"""Per-layer metrics, one reader each, found by the name in BENCHMARK.json.
`read(ctx)` returns a number, or None where there is nothing to read."""
