"""`frontend_ms` in a cell of several clients, where it holds each client's waiting for
the others' host work and moves the rate, not one statement's latency."""
from layer_metrics.frontend_ms import UNIT, read  # noqa: F401
