"""The traffic generator's own arithmetic: the cycle per client, the fixed
pools in the seed's order, and the refresh stream's volume."""

import threading
import time

from harness.spans import Recorder
from harness.traffic import Mix, Writer


def test_cycle_starts_k_places_in_for_client_k_and_every_seed_holds_the_same_pool():
    a, b = Mix("q1q6_4c", 1), Mix("q1q6_4c", 2**31 + 11)
    firsts = [[t for t, _, _ in (next(s) for s in [a.schedule(k)] * 4)] for k in range(4)]
    assert firsts[0] == ["q6", "q1", "q6", "q1"] and firsts[1] == ["q1", "q6", "q1", "q6"]
    for t in a.templates:
        assert sorted(a.templates[t].texts) == sorted(b.templates[t].texts)  # the same work, another order
        assert a.templates[t].first_text == b.templates[t].first_text
    assert any(a.templates[t].texts != b.templates[t].texts for t in a.templates)


class FakeConn:
    def __init__(self):
        self.sent = []

    def query(self, sql):
        self.sent.append(sql)
        return []


def test_refresh_stream_writes_its_volume_per_statement_and_no_more():
    mix = Mix("rf1_q1q6", 5)
    assert abs(mix.writes_per_statement(1.0) - 1500 / 22) < 1e-9  # clause 2.6.2 over one stream's 22 statements
    conn = FakeConn()
    w = Writer(conn, [{"sql": ["INSERT 1"], "rows": {}} for _ in range(100)], 2.5, Recorder())
    w.commit_next()  # set-up's transaction is outside the volume
    start, stop = threading.Barrier(2), threading.Event()
    th = threading.Thread(target=w.loop, args=(start, stop))
    th.start()
    start.wait()
    time.sleep(0.2)
    assert len(w.log) == 1  # no statement sent yet: nothing owed
    for _ in range(3):
        w.statement_sent()
    deadline = time.time() + 5
    while len(w.log) < 8 and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.2)
    assert len(w.log) == 1 + 7  # int(3 * 2.5)
    stop.set()
    th.join()
    assert conn.sent[:3] == ["BEGIN", "INSERT 1", "COMMIT"] and not w.errors
