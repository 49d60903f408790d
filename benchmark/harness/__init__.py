"""The benchmark's harness: general over configurations, traffic mixes,
statement templates and per-layer metrics, all of which it finds by name."""
