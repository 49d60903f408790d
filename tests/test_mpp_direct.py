"""The fragment's sort-free kernels (PR 29): the direct-address lookup join
against the sort-merge lookup and against numpy on seeded keys, the choice
between the two at the edge of the domain limit, and the by-slot aggregate
against `_segment_partial`."""

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
        if direct:
            assert np.asarray(took["slot"]).tolist() == want.tolist()


def test_unbounded_keys_keep_the_sort_merge_lookup():
    lkey, lvalid, rkey, rvalid = _keys("misses", 3)
    join = mpp.DistJoinSpec(left_keys=[0], right_keys=[0], unique=True, left_key_valid=(1,), right_key_valid=(1,), key_bounds=(None,))
    acc = [jnp.asarray(lkey), jnp.ones(len(lkey), bool), jnp.asarray(lvalid)]
    rcols = [jnp.asarray(rkey), jnp.ones(len(rkey), bool), jnp.asarray(rvalid)]
    took: dict = {}
    _, mask, _, _, _ = mpp._fold_join(mpp._Exchange(jax, 1), jnp, join, acc, jnp.asarray(lvalid), rcols, jnp.asarray(rvalid), None, took)
    assert not took and np.asarray(mask).tolist() == (_numpy_lookup(lkey, lvalid, rkey, rvalid) >= 0).tolist()


def _groups(keys, sums, cnt):
    keys, cnt = np.asarray(keys), np.asarray(cnt)
    return {int(keys[i]): (tuple(int(np.asarray(s)[i]) for s in sums), int(cnt[i])) for i in range(len(cnt)) if cnt[i] > 0}


@pytest.mark.parametrize("order", ["in_slot_order", "shuffled"])
@pytest.mark.parametrize("cap", [64, 16])
def test_by_slot_aggregate_is_segment_partials(order, cap):
    """Sums by build slot against the sort-based grouped partial, over probe
    rows in slot order (reduced in place) and shuffled (sorted first); with a
    capacity that holds the ~40 groups and one that does not (the same
    overflow count, which makes the gather build a bigger program)."""
    rng = np.random.default_rng(5)
    n, m = 512, 48
    slot = np.sort(rng.integers(-1, m, n)).astype(np.int32)  # -1: a probe row with no build row
    if order == "shuffled":
        rng.shuffle(slot)
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
