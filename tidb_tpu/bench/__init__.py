"""`tpchlike`: the data and statements of `chip_smoke.py`. The benchmark is
`benchmark/run.py` (`BENCHMARK.json`), outside the package."""
