"""wire + session + planner: per connection of the clients, the end of one `tidb:conn.command`
to the start of the next: the client (here the benchmark's, a thread of the same interpreter),
the socket both ways and the server thread's wake-up. No span is open across the read: the
reference's `tidb_server_conn_idle_duration_seconds`. Per analytic statement
of the traced window (`harness/span_tree.py`)."""
from harness import span_tree

UNIT = "ms"


def read(ctx):
    tree = span_tree.of_run(ctx)
    return None if tree is None else tree.turnaround_ms()
