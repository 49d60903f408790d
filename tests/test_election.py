"""Quorum-replicated owner election with fenced leases (kv/election.py —
the PD/etcd analog; ISSUE 2 tentpole).

In-process topology: a ShardedStore over three MemStores, each hosting one
ElectionReplica. Shard death is simulated by swapping a store for a proxy
that raises ConnectionError on every verb — the same surface a SIGKILLed
remote store presents after its retry budget (the multi-process analog
lives in test_chaos_election.py)."""

import threading
import time

import pytest

from tidb_tpu.kv.kv import UndeterminedError
from tidb_tpu.kv.memstore import MemStore
from tidb_tpu.kv.owner import OwnerManager
from tidb_tpu.kv.sharded import ShardedStore
from tidb_tpu.kv.txn import Txn
from tidb_tpu.session.session import DB
from tidb_tpu.utils import metrics


class DeadStore:
    """Every verb raises ConnectionError — an in-process SIGKILLed shard."""

    nonce = "dead"

    def __getattr__(self, name):
        def _down(*a, **k):
            raise ConnectionError("injected: store down")

        return _down


def fleet(n=3) -> ShardedStore:
    return ShardedStore([MemStore(region_split_keys=1000) for _ in range(n)])


def test_campaign_renew_resign_and_fencing_token():
    st = fleet()
    assert st.owner_campaign("ddl", "node-a", lease_s=5.0)
    assert st.owner_of("ddl") == "node-a"
    t1 = st.owner_term("ddl")
    assert t1 == 1
    # a live lease keeps competitors out
    assert not st.owner_campaign("ddl", "node-b", lease_s=5.0)
    # renewal under the fencing token refreshes without burning the term
    assert st.owner_campaign("ddl", "node-a", lease_s=5.0, term=t1)
    assert st.owner_term("ddl") == t1
    # resign vacates without a lease wait (a term+1 tombstone, so a partial
    # resign can never leave a ghost lease); the next grant bumps again
    st.owner_resign("ddl", "node-a")
    assert st.owner_of("ddl") is None
    assert st.owner_term("ddl") == t1 + 1  # the tombstone's term
    assert st.owner_campaign("ddl", "node-b", lease_s=5.0)
    assert st.owner_term("ddl") == t1 + 2
    assert metrics.ELECTION_FAILOVER.get(key="ddl") >= 1


def test_expired_lease_grants_new_term_and_fences_the_old_owner():
    st = fleet()
    assert st.owner_campaign("gc", "node-a", lease_s=0.1)
    t1 = st.owner_term("gc")
    time.sleep(0.15)
    assert st.owner_of("gc") is None  # expired
    assert st.owner_campaign("gc", "node-b", lease_s=5.0)
    t2 = st.owner_term("gc")
    assert t2 > t1, "the fencing token must move on every ownership grant"
    # the deposed owner's renewal carries its stale token → rejected, even
    # though node-a WAS the last owner (this is the split-brain guard)
    assert st.owner_campaign("gc", "node-a", lease_s=5.0, term=t1) is False
    # ... and an expired lease may not be same-term-refreshed by anyone
    assert st.owner_of("gc") == "node-b"


def test_any_single_shard_loss_including_shard0_keeps_elections_running():
    for dead in range(3):
        st = fleet()
        assert st.owner_campaign("stats", "node-a", lease_s=0.15)
        t1 = st.owner_term("stats")
        st.stores[dead] = DeadStore()
        # renewals keep working against the surviving majority
        assert st.owner_campaign("stats", "node-a", lease_s=0.15, term=t1)
        assert st.owner_of("stats") == "node-a"
        # and after expiry a survivor wins a HIGHER term
        time.sleep(0.2)
        assert st.owner_campaign("stats", "node-b", lease_s=5.0)
        assert st.owner_term("stats") == t1 + 1


def test_minority_partition_can_neither_grant_nor_refresh():
    st = fleet()
    assert st.owner_campaign("ttl", "node-a", lease_s=0.1)
    t1 = st.owner_term("ttl")
    st.stores[0] = DeadStore()
    st.stores[1] = DeadStore()
    with pytest.raises(ConnectionError, match="below quorum"):
        st.owner_campaign("ttl", "node-b", lease_s=1.0)
    with pytest.raises(ConnectionError, match="below quorum"):
        st.owner_campaign("ttl", "node-a", lease_s=1.0, term=t1)
    with pytest.raises(ConnectionError):
        st.owner_of("ttl")


def test_returning_replica_is_read_repaired_to_the_fleet_term():
    st = fleet()
    shard0 = st.stores[0]
    st.stores[0] = DeadStore()  # down BEFORE any grant: replica stays at term 0
    assert st.owner_campaign("ddl", "node-a", lease_s=5.0)
    t1 = st.owner_term("ddl")
    assert shard0.election_read("ddl")[0] == 0  # missed everything
    st.stores[0] = shard0  # the shard returns
    st.election._clear_cooldowns()  # the dead-shard cooldown (≤1 s here) would re-probe on its own; skip the wait
    assert st.owner_term("ddl") == t1  # the sweep repairs it
    term, owner, deadline = shard0.election_read("ddl")
    assert (term, owner) == (t1, "node-a") and deadline > time.time()


def test_same_term_split_vote_resolves_to_the_majority_owner():
    """Two candidates race to the same new term; one wins a majority, the
    loser's straggler record (with a LATER deadline) lands on a minority
    replica. The majority record must win resolution — otherwise owner_of
    misreports the loser and the real winner's renewals get fenced."""
    st = fleet()
    now = time.time()
    # hand-build the split: node-a granted on replicas 0+1, node-b's losing
    # proposal (later deadline) accepted only on replica 2
    for i in (0, 1):
        assert st.stores[i].election_propose("k", "node-a", 1, now + 5.0)[0]
    assert st.stores[2].election_propose("k", "node-b", 1, now + 8.0)[0]
    assert st.owner_of("k") == "node-a"
    assert st.owner_term("k") == 1
    # the majority winner renews under its token; the loser cannot
    assert st.owner_campaign("k", "node-a", lease_s=5.0, term=1)
    assert st.owner_campaign("k", "node-b", lease_s=5.0) is False


def test_below_quorum_raises_within_the_budget_even_with_slow_dead_shards():
    """Sweep wall time charges the election budget (the nested-budget rule
    _authority_call already enforces): dead shards whose probes burn their
    own reconnect budgets must not multiply into unbounded stalls."""
    from tidb_tpu.kv.election import QuorumElection

    class SlowDead:
        nonce = "slowdead"

        def __getattr__(self, name):
            def _down(*a, **k):
                time.sleep(0.2)  # a remote probe burning its boRPC budget
                raise ConnectionError("slow death")

            return _down

    el = QuorumElection([SlowDead(), SlowDead(), SlowDead()], budget_ms=300.0)
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="below quorum"):
        el.owner("k")
    # budget 300 ms + at most ~one extra sweep (0.6 s) + one backoff sleep
    assert time.monotonic() - t0 < 2.5


def test_dead_shard_cooldown_skips_reprobes_then_recovers():
    st = fleet()
    probes = {"n": 0}

    class CountingDead:
        nonce = "cdead"

        def __getattr__(self, name):
            def _down(*a, **k):
                probes["n"] += 1
                raise ConnectionError("down")

            return _down

    st.stores[0] = CountingDead()
    assert st.owner_campaign("cd", "node-a", lease_s=5.0)
    after_first = probes["n"]
    assert after_first >= 1  # the grant paid the probe once
    # inside the cooldown window the dead shard is NOT re-probed: renewals
    # stay cheap (this is what keeps keepalives inside the lease cadence)
    for _ in range(3):
        assert st.owner_campaign("cd", "node-a", lease_s=5.0, term=1)
    assert probes["n"] == after_first
    # ... but a below-quorum sweep re-probes cooled shards before giving up
    st.stores[1] = CountingDead()
    with pytest.raises(ConnectionError, match="below quorum"):
        st.owner_of("cd")
    assert probes["n"] > after_first


def test_losing_campaigns_never_regress_the_token():
    st = fleet()
    seen = []
    for i in range(6):
        st.owner_campaign("k", f"node-{i % 2}", lease_s=0.03)
        seen.append(st.owner_term("k"))
        time.sleep(0.04)  # every round expires → every grant bumps
    assert seen == sorted(seen), f"fencing token regressed: {seen}"
    assert seen[-1] > seen[0]


def test_meta_commit_tolerates_replica_that_missed_prewrite():
    """A meta replica that was down at prewrite (tolerated minority) and
    restarted EMPTY before commit answers commit with TxnAbortedError ("no
    lock") — that is a replica gap, not a transaction verdict: the quorum
    decided, and misreporting abort would invite re-running a committed
    transaction."""
    from tidb_tpu.kv.txn import Txn

    st = fleet()
    dead = st.stores[2]
    st.stores[2] = DeadStore()  # down through prewrite
    txn = Txn(st)
    txn.put(b"m:repl-gap", b"v1")  # meta key: fans to every replica
    # restart the shard EMPTY between prewrite and commit: memstore commit
    # will find no lock there
    orig_prewrite = st.prewrite

    def prewrite_then_restart(muts, primary, start_ts):
        orig_prewrite(muts, primary, start_ts)
        st.stores[2] = MemStore(region_split_keys=1000)

    st.prewrite = prewrite_then_restart
    try:
        cts = txn.commit()  # must succeed: quorum of replicas committed
    finally:
        st.prewrite = orig_prewrite
    assert cts > 0
    assert st.get_snapshot(st.current_ts()).get(b"m:repl-gap") == b"v1"
    # ... while a GENUINE abort (every replica agrees) still surfaces
    from tidb_tpu.kv.kv import TxnAbortedError

    txn2 = Txn(st)
    txn2.put(b"m:repl-gap2", b"v2")
    st.prewrite(txn2.membuf.mutations(), b"m:repl-gap2", txn2.start_ts)
    st.rollback([b"m:repl-gap2"], txn2.start_ts)  # raced resolver rolled it back
    with pytest.raises(TxnAbortedError):
        st.commit([b"m:repl-gap2"], txn2.start_ts, st.current_ts())


def test_owner_manager_term_checked_grant_path():
    """kv/owner.py's local backend enforces the same fencing rule, so an
    embedded store rejects a stale owner's renewals after failover too."""
    om = OwnerManager(lease_s=0.1)
    assert om.campaign("ddl", "node-a")
    t1 = om.term("ddl")
    assert om.campaign("ddl", "node-a", term=t1)  # live same-term renewal
    time.sleep(0.15)
    assert om.campaign("ddl", "node-b")  # expired → new owner, term bump
    assert om.term("ddl") == t1 + 1
    assert om.campaign("ddl", "node-a", term=t1) is False  # fenced
    assert om.owner("ddl") == "node-b"
    snap = om.snapshot()
    assert snap["ddl"]["owner"] == "node-b" and snap["ddl"]["term"] == t1 + 1


def test_owner_gated_sweep_self_fences_when_deposed(thread_hygiene):
    """A deposed owner observably self-fences mid-sweep: the keepalive's
    fenced renewal fails, owner_fenced(key) trips, and the sweep's result
    comes back wrapped — never a silent double-run."""
    st = fleet()
    db = DB(store=st)
    db.owner_lease_s = 0.3

    def sweep():
        ev = db._owner_fences["job"]
        deadline = time.time() + 5.0
        while not ev.is_set() and time.time() < deadline:
            time.sleep(0.02)
        return "swept"

    def depose():
        # a higher term appearing on the replicas == another node won after
        # this node was partitioned away (the proposal is the partition)
        time.sleep(0.25)
        t = st.owner_term("job")
        for s in st.stores:
            s.election_propose("job", "node-x", t + 1, time.time() + 1.0)

    th = threading.Thread(target=depose)
    th.start()
    out = db._owner_gated("job", sweep)
    th.join()
    assert isinstance(out, dict) and "fenced" in out, out
    assert out["result"] == "swept"
    assert db.owner_fenced("job")
    assert st.owner_of("job") == "node-x"


def test_owner_gated_keepalive_interval_derives_from_lease(thread_hygiene):
    """The keepalive refreshes at lease/3 (not the old hardcoded 2.0 s): a
    sweep 3× longer than a sub-second lease keeps ownership throughout."""
    st = fleet()
    db = DB(store=st)
    db.owner_lease_s = 0.5

    def slow_sweep():
        time.sleep(1.2)  # 2.4 leases long — only keepalives keep it alive
        return "done"

    out = db._owner_gated("slow", slow_sweep)
    assert out == "done", out  # never fenced: renewals kept the lease live
    assert not db.owner_fenced("slow")


def test_background_loops_leave_no_stray_threads(thread_hygiene):
    db = DB(store=fleet())
    db.owner_lease_s = 0.5
    db.start_background(ttl_interval_s=0.05, analyze_interval_s=0.05, gc_interval_s=0.05)
    time.sleep(0.4)  # a few owner-gated sweeps run
    db.stop_background()
    # thread_hygiene teardown asserts no owner-ka-*/timer-runtime remain


def test_election_status_endpoint_and_metrics():
    from urllib.request import urlopen

    from tidb_tpu.server.status import StatusServer

    st = fleet()
    db = DB(store=st)
    assert st.owner_campaign("ddl", "node-a", lease_s=5.0)
    srv = StatusServer(db, port=0)
    port = srv.start()
    try:
        import json

        snap = json.loads(urlopen(f"http://127.0.0.1:{port}/election").read())
        assert snap["ddl"]["owner"] == "node-a"
        assert snap["ddl"]["term"] == st.owner_term("ddl")
        assert snap["ddl"]["lease_remaining_s"] > 0
        body = urlopen(f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "tidb_tpu_election_term" in body
        assert "tidb_tpu_election_campaign_total" in body
    finally:
        srv.close()


def test_resolve_undetermined_reports_commit_and_rollback():
    """The check_txn_status-driven resolver (ROADMAP: undetermined-commit
    resolution). Wire-level UndeterminedError coverage lives in
    test_chaos.py; this exercises the status mapping on both outcomes."""
    st = MemStore(region_split_keys=1000)
    # committed: the 'lost reply' case where the store DID commit
    txn = Txn(st)
    txn.put(b"zz-res-1", b"v")
    cts = txn.commit()
    assert txn.resolve_undetermined() == ("committed", cts)
    # rolled back: prewrite landed, commit never did, lock expired
    from tidb_tpu.kv.memstore import OP_PUT, Mutation

    txn2 = Txn(st)
    txn2.membuf.put(b"zz-res-2", b"v")
    st.prewrite([Mutation(OP_PUT, b"zz-res-2", b"v")], b"zz-res-2", txn2.start_ts)
    txn2._primary = b"zz-res-2"
    st.rollback([b"zz-res-2"], txn2.start_ts)
    assert txn2.resolve_undetermined() == ("rolled_back", 0)
    # nothing committed phase-wise → resolver refuses
    txn3 = Txn(st)
    with pytest.raises(RuntimeError, match="never reached the commit phase"):
        txn3.resolve_undetermined()
    # an unbound error explains itself
    with pytest.raises(RuntimeError, match="no resolver bound"):
        UndeterminedError("x").resolve()
