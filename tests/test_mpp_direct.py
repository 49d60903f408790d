"""The fragment's sort-free kernels (PR 29): the direct-address lookup join
against the sort-merge lookup and against numpy on seeded keys, the choice
between the two at the edge of the domain limit, and the by-slot aggregate
against `_segment_partial`; (PR 34) the probe answered by blocks of rows in key
order against the element gather, and which of the two a lane takes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tidb_tpu.parallel import mpp

DOMAIN = 500


def _keys(case: str, seed: int):
    """(probe keys, probe valid, build keys, build valid): build keys unique among its valid rows."""
    rng = np.random.default_rng(seed)
    n, m = 256, 128
    rkey = rng.permutation(DOMAIN)[:m].astype(np.int64)
    rvalid = np.ones(m, bool)
    lkey = rng.integers(0, DOMAIN, n).astype(np.int64)  # about a quarter hit
    lvalid = np.ones(n, bool)
    if case == "misses":
        lkey = np.where(rng.random(n) < 0.5, rng.choice(rkey, n), lkey)
    elif case == "padded":
        # invalid rows hold 0, as padded lanes do, while key 0 is a real build row
        rkey[0] = 0
        rvalid[m // 2:] = False
        rkey[m // 2:] = 0
        lvalid[n // 2:] = False
        lkey[n // 2:] = 0
        lkey[:8] = 0
    elif case == "duplicate_probe":
        lkey = rng.choice(rkey[:5], n)
    elif case == "empty_build":
        rvalid[:] = False
    else:
        assert case == "every_key_hits"
        lkey = rng.choice(rkey, n)
    return lkey, lvalid, rkey, rvalid


def _numpy_lookup(lkey, lvalid, rkey, rvalid):
    at = {int(k): i for i, k in enumerate(rkey) if rvalid[i]}
    return np.array([at.get(int(k), -1) if ok else -1 for k, ok in zip(lkey, lvalid)])


CASES = ["misses", "padded", "duplicate_probe", "empty_build", "every_key_hits"]


@pytest.mark.parametrize("case", CASES)
def test_direct_lookup_is_numpys_and_the_sort_merge_joins(case):
    lkey, lvalid, rkey, rvalid = _keys(case, 7)
    want = _numpy_lookup(lkey, lvalid, rkey, rvalid)
    got = np.asarray(jax.jit(lambda *a: mpp._direct_lookup(jnp, *a, DOMAIN))(lkey.astype(np.int32), lvalid, rkey.astype(np.int32), rvalid))
    assert got.tolist() == want.tolist()
    rows = jnp.arange(len(rkey))
    (merged,), match = mpp._local_unique_join(
        jax, jnp, jnp.asarray(lkey), [jnp.asarray(lkey)], jnp.asarray(lvalid), jnp.asarray(rkey), [jnp.asarray(rkey)], [rows],
        jnp.asarray(rvalid), dead_build=DOMAIN + 1, dead_probe=DOMAIN)
    assert np.asarray(match).tolist() == (want >= 0).tolist()
    assert np.asarray(merged)[want >= 0].tolist() == want[want >= 0].tolist()


def _fold(lkey, lvalid, rkey, rvalid, hi):
    """One unique inner join through `_fold_join` on one shard, key bounds (0, hi)."""
    join = mpp.DistJoinSpec(left_keys=[0], right_keys=[0], kind="inner", exchange="hash", unique=True,
                            left_key_valid=(1,), right_key_valid=(1,), key_bounds=((0, hi),))
    acc = [jnp.asarray(lkey), jnp.ones(len(lkey), bool), jnp.asarray(lvalid)]
    rcols = [jnp.asarray(rkey), jnp.ones(len(rkey), bool), jnp.arange(len(rkey)), jnp.ones(len(rkey), bool), jnp.asarray(rvalid)]
    took: dict = {}
    acc, mask, dropped, overflow, _ = mpp._fold_join(mpp._Exchange(jax, 1), jnp, join, acc, jnp.asarray(lvalid), rcols, jnp.asarray(rvalid), None, took)
    assert int(dropped) == 0 and int(overflow) == 0
    return np.asarray(mask), np.asarray(acc[3 + 2]), took


@pytest.mark.parametrize("case", CASES)
def test_the_bounds_choose_the_lookup_at_the_edge_of_the_domain_limit(case, monkeypatch):
    """A domain of exactly the limit takes the direct table; one code more
    takes the sort-merge lookup. Both answer as numpy does."""
    monkeypatch.setattr(mpp, "DIRECT_DOMAIN_MAX", DOMAIN)
    lkey, lvalid, rkey, rvalid = _keys(case, 11)
    want = _numpy_lookup(lkey, lvalid, rkey, rvalid)
    for hi, direct in ((DOMAIN - 1, True), (DOMAIN, False)):
        mask, row, took = _fold(lkey, lvalid, rkey, rvalid, hi)
        assert bool(took) is direct  # the slot lane exists only where the table was built
        assert mask.tolist() == (want >= 0).tolist()
        assert row[mask].tolist() == want[want >= 0].tolist()
        if direct:  # a probe row carries its key's code; the table holds the code's build row
            code, table = np.asarray(took["code"]), np.asarray(took["table"])
            assert code.tolist() == np.where(want >= 0, lkey, -1).tolist()
            assert table[code[code >= 0]].tolist() == want[want >= 0].tolist()
            assert np.asarray(took["probe"]).tolist() == [len(lkey)] * 2  # 500 codes lie in one row of the bitmap, in order or not


def test_unbounded_keys_keep_the_sort_merge_lookup():
    lkey, lvalid, rkey, rvalid = _keys("misses", 3)
    join = mpp.DistJoinSpec(left_keys=[0], right_keys=[0], unique=True, left_key_valid=(1,), right_key_valid=(1,), key_bounds=(None,))
    acc = [jnp.asarray(lkey), jnp.ones(len(lkey), bool), jnp.asarray(lvalid)]
    rcols = [jnp.asarray(rkey), jnp.ones(len(rkey), bool), jnp.asarray(rvalid)]
    took: dict = {}
    _, mask, _, _, _ = mpp._fold_join(mpp._Exchange(jax, 1), jnp, join, acc, jnp.asarray(lvalid), rcols, jnp.asarray(rvalid), None, took)
    assert not took and np.asarray(mask).tolist() == (_numpy_lookup(lkey, lvalid, rkey, rvalid) >= 0).tolist()


def _probe_lanes(case: str):
    """(probe codes, live, build codes, n_codes, blocked?) for `_probe_match`:
    4 probe rows an order, order keys 8 of every 320 values (TPC-H's are 8 of
    every 32): ten rows of the bitmap, a block of 128 rows spans 1,280 codes."""
    rng = np.random.default_rng(13)
    n_codes, n = 41_000, 4096
    orders = np.arange(1024)
    okey = orders // 8 * 320 + orders % 8
    lkey = np.repeat(okey, 4).astype(np.int32)
    live = rng.random(n) < 0.6
    blocked = True
    if case == "in_order_dead_rows_padded_tail":
        live[3000:] = False
        lkey[3000:] = 0  # padding holds code 0 and lies in no block's span
        lkey[rng.random(n) < 0.1] = 0
        live &= lkey > 0
    elif case == "a_block_with_no_live_row":
        live[256:512] = False
    elif case == "blocks_at_the_domains_ends":
        n_codes = int(okey[-1]) + 1  # 40,648: not a multiple of 32, and the last code is a live build row's
        lkey[:4], live[:4], live[-4:] = 0, True, True
    elif case == "a_lane_shorter_than_a_block":
        lkey, live, n = lkey[:48], live[:48], 48
    elif case == "a_lane_of_whole_blocks_and_a_rest":
        lkey, live, n = lkey[:1000], live[:1000], 1000
    elif case == "one_block_overflows_its_window":
        lkey[700], live[700], blocked = 40_999, True, False  # one live row far off its block's: 128 rows span ten rows of the bitmap
    elif case == "a_dead_row_far_off_counts_in_no_span":
        lkey[700], live[700] = 40_999, False
    else:
        assert case == "shuffled"
        perm = rng.permutation(n)
        lkey, live, blocked = lkey[perm], live[perm], False
    rkey = okey[rng.random(len(okey)) < 0.5].astype(np.int32)
    if case == "blocks_at_the_domains_ends":
        rkey = np.union1d(rkey, [0, n_codes - 1]).astype(np.int32)
    return lkey, live, rkey, n_codes, blocked


@pytest.mark.parametrize("case", [
    "in_order_dead_rows_padded_tail", "a_block_with_no_live_row", "blocks_at_the_domains_ends", "a_lane_shorter_than_a_block",
    "a_lane_of_whole_blocks_and_a_rest", "one_block_overflows_its_window", "a_dead_row_far_off_counts_in_no_span", "shuffled"])
def test_the_probe_by_blocks_is_the_element_gathers_and_numpys(case):
    """`_probe_match` == `_direct_lookup(...) >= 0` == numpy, bit for bit, and
    it says which way it went: by blocks where every block's live codes lie in
    two adjacent rows of the bitmap (4,096 codes each), else the whole lane by
    the element gather."""
    lkey, live, rkey, n_codes, blocked = _probe_lanes(case)
    rvalid = np.ones(len(rkey), bool)
    want = _numpy_lookup(lkey, live, rkey, rvalid)

    def both(lkey, live, rkey, rvalid):
        table = mpp._direct_table(jnp, rkey, rvalid, n_codes)
        return mpp._probe_match(jax, jnp, table, lkey, live), mpp._direct_lookup(jnp, lkey, live, rkey, rvalid, n_codes)

    (match, slot, by_blocks), ref = jax.jit(both)(lkey, live, rkey, rvalid)
    assert np.asarray(ref).tolist() == want.tolist() and want.max() >= 0
    assert np.asarray(match).tolist() == (want >= 0).tolist()
    assert np.asarray(slot).tolist() == want.tolist()  # the build row, where something reads it
    assert bool(by_blocks) is blocked


@pytest.fixture
def four_shards():
    from tidb_tpu.parallel import mesh as mesh_mod

    mesh_mod.FORCE_NDEV = 4
    yield
    mesh_mod.FORCE_NDEV = None


def test_a_local_join_on_four_shards_probes_by_blocks_with_its_slivers_in(four_shards):
    """A dimension in key order and its fact table in the same order over four
    shards: the cuts do not line up, so a shard is sent slivers of its
    neighbours' build rows; the bitmap is taken from the shard's own table
    after they are scattered in, and every shard probes by blocks."""
    import tidb_tpu
    from tidb_tpu.executor.load import bulk_load
    from tidb_tpu.utils import metrics

    db = tidb_tpu.open()
    db.execute("CREATE TABLE dim (id BIGINT PRIMARY KEY, grp BIGINT)")
    db.execute("CREATE TABLE fact (id BIGINT, v BIGINT)")
    ids = np.arange(4000, dtype=np.int64)
    bulk_load(db, "dim", [ids, ids % 7])
    fk = np.repeat(ids, np.where(ids // 1000 % 2 == 0, 5, 3))
    v = (fk * 31 + np.arange(len(fk))) % 1000
    bulk_load(db, "fact", [fk, v])
    want: dict = {}
    for k, x in zip(fk.tolist(), v.tolist()):
        if k % 7 != 3:
            want[k] = want.get(k, 0) + x
    s = db.session()
    s.execute("SET tidb_enforce_mpp = 1")
    before = {how: metrics.MPP_PROBE_ROWS.get(how=how) for how in ("blocked", "gather")}
    got = {int(r[0]): int(r[1]) for r in s.query("SELECT fact.id, SUM(v) FROM fact JOIN dim ON fact.id = dim.id WHERE grp <> 3 GROUP BY fact.id")}
    assert got == want
    (d,) = s.mpp_details
    assert d.ndev == 4 and d.exchange == "local" and d.xchg_rows > 0 and d.probe == "blocked"
    assert "probe: blocked" in d.render()
    assert metrics.MPP_PROBE_ROWS.get(how="blocked") - before["blocked"] == 4 * 4096  # 4,000 fact rows a shard, padded
    assert metrics.MPP_PROBE_ROWS.get(how="gather") == before["gather"]


def _groups(keys, sums, cnt):
    keys, cnt = np.asarray(keys), np.asarray(cnt)
    return {int(keys[i]): (tuple(int(np.asarray(s)[i]) for s in sums), int(cnt[i])) for i in range(len(cnt)) if cnt[i] > 0}


@pytest.mark.parametrize("order", ["in_slot_order", "shuffled", "told_by_key_code"])
@pytest.mark.parametrize("cap", [64, 16])
def test_by_slot_aggregate_is_segment_partials(order, cap):
    """Sums by build slot against the sort-based grouped partial, over probe
    rows in slot order (reduced in place) and shuffled (sorted first); with a
    capacity that holds the ~40 groups and one that does not (the same
    overflow count, which makes the gather build a bigger program).
    `told_by_key_code`: the lane names a row's group by its join key's code,
    as `_fold_join` hands it (sparse codes in order; the groups come out the
    same, a group's build row is the table's at its code)."""
    rng = np.random.default_rng(5)
    n, m = 512, 48
    slot = np.sort(rng.integers(-1, m, n)).astype(np.int32)  # -1: a probe row with no build row
    if order == "shuffled":
        rng.shuffle(slot)
    if order == "told_by_key_code":
        slot = np.where(slot >= 0, 5 * slot + 2, -1).astype(np.int32)
        m = 5 * m
    mask = rng.random(n) < 0.8
    vals = [rng.integers(-10**12, 10**12, n), rng.integers(0, 2, n)]
    run = jax.jit(lambda s, k, a, b: mpp._slot_partial(jax, jnp, s, k, [a, b], cap))
    gslot, grow, sums, cnt, overflow = run(slot, mask, *vals)
    live = mask & (slot >= 0)
    keys, want_sums, want_cnt, want_overflow = mpp._segment_partial(
        jnp, [jnp.asarray(slot, jnp.int64)], [jnp.asarray(v) for v in vals], jnp.asarray(live), cap, bounds=((0, m - 1),))
    assert int(overflow) == int(want_overflow) == max(len(set(slot[live].tolist())) - cap, 0)
    if not int(overflow):
        assert _groups(gslot, sums, cnt) == _groups(keys[0], want_sums, want_cnt)
        first = {int(g): int(r) for g, r, c in zip(np.asarray(gslot), np.asarray(grow), np.asarray(cnt)) if c > 0}
        assert all(live[r] and slot[r] == g for g, r in first.items())  # a row of the group, to read probe lanes at


def test_by_slot_aggregate_of_no_live_row():
    n = 64
    out = mpp._slot_partial(jax, jnp, jnp.full(n, -1, jnp.int32), jnp.ones(n, bool), [jnp.ones(n, jnp.int64)], 8)
    assert int(out[4]) == 0 and not np.asarray(out[3]).any() and not np.asarray(out[2][0]).any()
