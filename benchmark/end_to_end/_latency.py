"""End-to-end latencies, one reader each, found by the name in BENCHMARK.json.
All are taken on the client's side with the host clock, send to last row."""
import numpy as np


def percentile_ms(statements, q: float, template: str | None = None):
    ms = [(s["t1"] - s["t0"]) * 1e3 for s in statements if template is None or s["template"] == template]
    return float(np.percentile(ms, q)) if ms else None
