"""End-to-end metrics, one reader each, found by the name in BENCHMARK.json.
All are taken on the client's side with the host clock."""
