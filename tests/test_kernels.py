"""Per-kernel interpret=True allclose sweeps against the pure-jnp oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.hash_join import (direct_slots, hash_build, hash_keys,
                                     hash_keys_np, hashed_slots,
                                     probe_lengths_np)
from repro.kernels.mamba_scan import mamba_scan, mamba_scan_ref
from repro.kernels.radix_groupby import radix_groupby, radix_groupby_ref
from repro.kernels.segment_sum import segment_sum, segment_sum_ref

RNG = np.random.default_rng(1234)


# ------------------------------------------------------------- segment_sum
@pytest.mark.parametrize("n,c,g,tile", [
    (100, 1, 8, 32), (1000, 4, 37, 128), (513, 3, 64, 256),
    (2048, 8, 128, 512), (7, 2, 4, 512),
])
def test_segment_sum_sweep(n, c, g, tile):
    seg = RNG.integers(-1, g, n).astype(np.int32)
    vals = RNG.normal(size=(n, c)).astype(np.float32)
    ref = segment_sum_ref(jnp.array(seg), jnp.array(vals), g)
    got = segment_sum(jnp.array(seg), jnp.array(vals), g,
                      impl="interpret", rows_tile=tile)
    np.testing.assert_allclose(np.array(got), np.array(ref),
                               rtol=1e-5, atol=1e-5)


def test_segment_sum_all_padding():
    seg = np.full(64, -1, np.int32)
    vals = RNG.normal(size=(64, 2)).astype(np.float32)
    got = segment_sum(jnp.array(seg), jnp.array(vals), 8, impl="interpret")
    np.testing.assert_array_equal(np.array(got), np.zeros((8, 2)))


def test_segment_sum_matches_paper_groupby(ssb_tiny):
    """The kernel computes the paper's block component (Fig-11 groupby_sum)."""
    lo = ssb_tiny.lineorder
    year = lo["lo_orderdate"] // 10000 - 1992
    profit = (lo["lo_revenue"] - lo["lo_supplycost"]).astype(np.float32)
    got = segment_sum(jnp.array(year.astype(np.int32)),
                      jnp.array(profit[:, None]), 7, impl="interpret")
    expect = np.zeros(7)
    np.add.at(expect, year, profit)
    np.testing.assert_allclose(np.array(got)[:, 0], expect, rtol=1e-5)


# ----------------------------------------------------------------- hash join
def _probe_oracle(key_rows, probe_rows):
    """First-occurrence membership oracle: (index, found) per probe row."""
    lut = {}
    for i, row in enumerate(map(tuple, key_rows)):
        lut.setdefault(row, i)
    found = np.array([tuple(r) in lut for r in probe_rows])
    idx = np.array([lut.get(tuple(r), 0) for r in probe_rows], np.int64)
    return idx, found


def _rows(slot_keys, slot_idx, val_cols, max_probes, base):
    """Each probe row's build row, traced: the table's slot function
    (``direct_slots`` where ``base`` is an int, else ``hashed_slots``),
    then one gather of ``slot_idx``, the slot-addressed column of row
    numbers (-1 in an empty slot).  Returns ``(row, found)``, row 0 on a
    miss."""
    if base is None:
        slot, found = hashed_slots(slot_keys, slot_idx, val_cols, max_probes)
    else:
        slot, found = direct_slots(val_cols[0], base, slot_idx.shape[0])
    row = jnp.where(found, jnp.take(slot_idx, slot, mode="clip"), -1)
    return jnp.maximum(row, 0), row >= 0


_rows_jit = jax.jit(_rows, static_argnames=("max_probes", "base"))


def _probe(built, cols):
    return _rows_jit(
        tuple(jnp.asarray(k) for k in built["slot_keys"]),
        jnp.asarray(built["slot_idx"]), tuple(jnp.asarray(c) for c in cols),
        max_probes=built["max_probes"], base=built["base"])


def _home(built, key_cols):
    """Each row's home slot under the table's own slot function: ``key -
    base`` on a direct table, the fmix32 hash otherwise."""
    if built["base"] is None:
        h = hash_keys_np(key_cols)
    else:
        h = (np.asarray(key_cols[0]).astype(np.uint32)
             - np.uint32(built["base"] % (1 << 32)))
    return h.astype(np.int64) & (built["table_size"] - 1)


def test_hash_keys_host_device_identical():
    """The host (build-time) and traced (probe-time) hash must agree bit for
    bit — open addressing falls apart on any mismatch."""
    for dt in (np.int64, np.int32, np.uint32, np.int16):
        k1 = RNG.integers(0, np.iinfo(dt).max, 500).astype(dt)
        k2 = RNG.integers(0, 100, 500).astype(dt)
        h_np = hash_keys_np((k1, k2))
        h_j = hash_keys((jnp.asarray(k1), jnp.asarray(k2)))
        np.testing.assert_array_equal(h_np, np.asarray(h_j))


@pytest.mark.parametrize("d,n,key_range", [
    (1, 16, 50),                 # tiny table (min size floor)
    (500, 2_000, 3_000),         # ~17% hit rate, misses exercised
    (1000, 1_500, 1_000),        # dense: most probes hit
    (997, 777, 100_000),         # sparse keys
])
def test_hash_probe_sweep(d, n, key_range):
    keys = np.sort(RNG.choice(key_range, size=min(d, key_range),
                              replace=False)).astype(np.int64)
    built = hash_build((keys,))
    probes = RNG.integers(0, key_range + 10, n).astype(np.int64)
    oi, of = _probe_oracle(keys[:, None], probes[:, None])
    idx, found = _probe(built, (probes,))
    idx, found = np.asarray(idx), np.asarray(found)
    np.testing.assert_array_equal(found, of)
    np.testing.assert_array_equal(idx[of], oi[of])


def test_hash_probe_arbitrary_key_order():
    """Unlike searchsorted, the hash table needs NO key ordering: a shuffled
    build probes identically (modulo the first-occurrence index mapping)."""
    keys = RNG.choice(10_000, size=800, replace=False).astype(np.int64)
    shuffled = keys.copy()
    RNG.shuffle(shuffled)
    built = hash_build((shuffled,))
    probes = RNG.integers(0, 11_000, 2_500).astype(np.int64)
    oi, of = _probe_oracle(shuffled[:, None], probes[:, None])
    idx, found = _probe(built, (probes,))
    np.testing.assert_array_equal(np.asarray(found), of)
    np.testing.assert_array_equal(np.asarray(idx)[of], oi[of])


def test_hash_probe_duplicate_keys_keep_first():
    """Duplicate build keys: probes must land on the FIRST occurrence —
    over sorted keys that is exactly searchsorted's leftmost index, the
    byte-compat contract with the legacy DimTable probe."""
    base = np.sort(RNG.choice(500, size=200, replace=False))
    keys = np.sort(np.concatenate([base, base[:50], base[:25]]))
    built = hash_build((keys.astype(np.int64),))
    probes = np.arange(-5, 520).astype(np.int64)
    ss = np.clip(np.searchsorted(keys, probes), 0, len(keys) - 1)
    hit = keys[ss] == probes
    idx, found = _probe(built, (probes,))
    np.testing.assert_array_equal(np.asarray(found), hit)
    np.testing.assert_array_equal(np.asarray(idx)[hit], ss[hit])


def _walk(built, key_cols, start: int, stop) -> int:
    """Probes of a host-side linear walk from slot ``start`` until
    ``stop(slot)``."""
    size = built["table_size"]
    for step in range(size + 1):
        if stop((start + step) % size):
            return step + 1
    raise AssertionError("walk never stopped")


@pytest.mark.parametrize("keys", [
    np.arange(1, 301, dtype=np.int64),                       # dense, SSB-like
    RNG.choice(100_000, size=997, replace=False).astype(np.int64),
    np.array([5, 5, 9, 9, 9, 12], dtype=np.int64),           # duplicates
    np.array([42], dtype=np.int64),
])
def test_hash_build_probe_statistics_match_a_brute_force_walk(keys):
    """``mean_probes`` is the mean probe length a lookup of each distinct
    key needs, walked from its home slot; ``max_probes`` the passes that
    settle any probe: on an fmix32 table the longest walk from any slot to
    an empty one, on a direct table one (each occupied slot holds its own
    key, so a probe hits there or misses)."""
    built = hash_build((keys,))
    slot_idx, (slot_keys,) = built["slot_idx"], built["slot_keys"]
    size = built["table_size"]
    h = _home(built, (keys,))
    lengths = {int(k): _walk(built, (keys,), int(s),
                             lambda t, k=k: slot_idx[t] >= 0
                             and slot_keys[t] == k)
               for k, s in zip(keys, h)}
    assert built["mean_probes"] == pytest.approx(
        sum(lengths.values()) / len(lengths))
    if built["base"] is None:
        longest = max(_walk(built, (keys,), t, lambda u: slot_idx[u] < 0)
                      for t in range(size))
    else:
        occupied = np.flatnonzero(slot_idx >= 0)
        np.testing.assert_array_equal(slot_keys[occupied],
                                      built["base"] + occupied)
        longest = max(lengths.values())
        assert (longest, built["mean_probes"]) == (1, 1.0)
    assert built["max_probes"] == longest


def test_hash_build_of_no_keys_has_no_probe_length():
    built = hash_build((np.zeros(0, np.int64),))
    assert built["mean_probes"] == 0.0 and built["max_probes"] == 1


@pytest.mark.parametrize("n_cols", [1, 2])
def test_probe_lengths_match_a_brute_force_walk(n_cols):
    """``probe_lengths_np`` gives each probe row the passes that settle it:
    the walk from its home slot (the table's own: one key column here is
    direct, two are fmix32) to its key (a hit) or to an empty slot (a
    miss), at most ``max_probes`` as the device loop runs; and the device
    loop run for that many passes finds every hit."""
    build = [RNG.choice(5_000, size=700, replace=False).astype(np.int64)
             for _ in range(n_cols)]
    probes = [np.concatenate([b[RNG.integers(0, 700, 300)],
                              RNG.integers(-100, 5_100, 200)])
              for b in build]
    built = hash_build(build)
    assert (built["base"] is not None) == (n_cols == 1)
    slot_idx, slot_keys = built["slot_idx"], built["slot_keys"]
    home = _home(built, probes)
    want = [min(_walk(built, build, int(s),
                      lambda t, r=r: slot_idx[t] < 0 or all(
                          sk[t] == p[r] for sk, p in zip(slot_keys, probes))),
                built["max_probes"])
            for r, s in enumerate(home)]
    got = probe_lengths_np(built, probes)
    np.testing.assert_array_equal(got, want)
    assert got.max() <= built["max_probes"]
    _, found_all = _probe(built, probes)
    _, found_short = _probe(dict(built, max_probes=int(got.max())), probes)
    np.testing.assert_array_equal(np.asarray(found_short),
                                  np.asarray(found_all))


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_hash_probe_multi_column(dtype):
    rows = np.unique(RNG.integers(0, 40, size=(600, 3)), axis=0)
    built = hash_build(tuple(rows[:, j].astype(dtype) for j in range(3)))
    probes = RNG.integers(0, 45, size=(2_000, 3)).astype(dtype)
    oi, of = _probe_oracle(rows, probes)
    idx, found = _probe(built, tuple(probes[:, j] for j in range(3)))
    idx, found = np.asarray(idx), np.asarray(found)
    np.testing.assert_array_equal(found, of)
    np.testing.assert_array_equal(idx[of], oi[of])


def test_hash_probe_all_miss_and_empty_probe():
    keys = np.arange(100, dtype=np.int64) * 7
    built = hash_build((keys,))
    probes = (np.arange(50, dtype=np.int64) * 7) + 3   # never in table
    idx, found = _probe(built, (probes,))
    assert not np.asarray(found).any()
    idx, found = _probe(built, (np.zeros(0, np.int64),))
    assert np.asarray(idx).shape == (0,) and np.asarray(found).shape == (0,)


def test_hash_probe_ref_traceable():
    """The slot functions trace under jit over a table closed over as
    constants, with max_probes and base static — as the fused segment
    kernel inlines them."""
    keys = np.sort(RNG.choice(1_000, 300, replace=False)).astype(np.int64)
    built = hash_build((keys,))
    sk = tuple(jnp.asarray(k) for k in built["slot_keys"])
    si = jnp.asarray(built["slot_idx"])
    probes = RNG.integers(0, 1_100, 800).astype(np.int64)

    @jax.jit
    def f(p):
        return _rows(sk, si, (p,), built["max_probes"], built["base"])

    idx, found = f(jnp.asarray(probes))
    oi, of = _probe_oracle(keys[:, None], probes[:, None])
    np.testing.assert_array_equal(np.asarray(found), of)
    np.testing.assert_array_equal(np.asarray(idx)[of], oi[of])


def _ssb_dates():
    """yyyymmdd of every day of 1992-1998: 2,557 keys over 61,131 values,
    a direct table of 65,536 slots, 8x the fmix32 table's 8,192."""
    days = np.arange("1992-01-01", "1999-01-01", dtype="datetime64[D]")
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    dom = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    return year * 10_000 + month * 100 + dom


_BASE_CASES = {
    "below_min": (np.arange(100, 400), np.arange(-50, 110)),
    "above_max": (np.arange(100, 400), np.arange(390, 2_000)),
    "gaps": (np.sort(RNG.choice(4_000, size=2_500, replace=False)),
             np.arange(-10, 4_100)),
    "duplicates": (np.sort(np.r_[np.arange(10, 90), np.arange(10, 90, 3),
                                 np.arange(40, 50)]), np.arange(0, 100)),
    "negative_base": (np.arange(-500, 200, 2), np.arange(-700, 400)),
    "one_key": (np.array([42]), np.arange(-40, 120)),
    "date_edge": (_ssb_dates(), np.r_[_ssb_dates(),
                                      RNG.integers(19_911_201, 19_990_201,
                                                   5_000)]),
}


@pytest.mark.parametrize("case", sorted(_BASE_CASES))
@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_hash_probe_base_addressed_matches_the_oracle(case, dtype):
    """A direct table (slot ``key - base``) answers as the first-occurrence
    oracle in one pass: keys below and above the range, gaps inside it,
    duplicates, a negative base, one key, and SSB's dates at the 8x edge."""
    keys, probes = (a.astype(dtype) for a in _BASE_CASES[case])
    built = hash_build((keys,))
    assert built["base"] == keys.min() and built["max_probes"] == 1
    oi, of = _probe_oracle(keys[:, None], probes[:, None])
    idx, found = _probe(built, (probes,))
    idx, found = np.asarray(idx), np.asarray(found)
    np.testing.assert_array_equal(found, of)
    np.testing.assert_array_equal(idx[of], oi[of])
    np.testing.assert_array_equal(idx[~of], 0)
    np.testing.assert_array_equal(probe_lengths_np(built, (probes,)), 1)


@pytest.mark.parametrize("key_cols,direct", [
    # 100 keys: the fmix32 table has 256 slots, so a span of 2,048 is 8x
    ((np.r_[0, RNG.choice(np.arange(1, 2_047), 98, replace=False), 2_047],),
     True),
    ((np.r_[0, RNG.choice(np.arange(1, 2_048), 98, replace=False), 2_048],),
     False),
    ((_ssb_dates(),), True),
    ((np.arange(300), np.arange(300)), False),        # two key columns
    ((np.arange(300).astype(np.float64),), False),    # not integers
    ((np.zeros(0, np.int64),), False),                # no keys
])
def test_hash_build_picks_the_base_by_the_8x_rule(key_cols, direct):
    """Direct addressing exactly where ``next_pow2(span)`` is at most 8x
    the fmix32 table's ``next_pow2(2d)``, for one integer key column."""
    built = hash_build(key_cols)
    assert (built["base"] is not None) == direct
    if direct:
        (k,) = key_cols
        assert built["base"] == k.min()
        assert built["table_size"] == 1 << (int(k.max() - k.min())
                                            ).bit_length()


def _fmix32_placement(key_cols):
    """The fmix32 table as a plain loop places it: rounds of one probe
    distance, rows in index order, the lowest row winning a free slot, a
    row dropped where its slot holds its own key (keep-first)."""
    rows = list(zip(*(np.asarray(k).tolist() for k in key_cols)))
    d = len(rows)
    size = 16
    while size < 2 * d:
        size *= 2
    home = hash_keys_np(key_cols).astype(np.int64)
    slot_idx = np.full(size, -1, np.int32)
    live, step, lengths = list(range(d)), 0, []
    while live:
        claimed = {}
        for i in live:
            t = int(home[i] + step) & (size - 1)
            if slot_idx[t] < 0 and t not in claimed:
                claimed[t] = i
        for t, i in claimed.items():
            slot_idx[t] = i
            lengths.append(step + 1)
        placed = set(claimed.values())
        live = [i for i in live if i not in placed
                and rows[slot_idx[int(home[i] + step) & (size - 1)]] != rows[i]]
        step += 1
    full = slot_idx >= 0
    slot_keys = []
    for k in key_cols:
        sk = np.zeros(size, np.asarray(k).dtype)
        sk[full] = np.asarray(k)[slot_idx[full]]
        slot_keys.append(sk)
    occ = np.r_[slot_idx >= 0, slot_idx >= 0]
    run = longest = 0
    for o in occ:
        run = run + 1 if o else 0
        longest = max(longest, run)
    return {"slot_keys": tuple(slot_keys), "slot_idx": slot_idx,
            "table_size": size,
            "max_probes": min(longest, size) + 1,
            "mean_probes": float(np.mean(lengths)) if lengths else 0.0,
            "base": None}


@pytest.mark.parametrize("key_cols", [
    (RNG.choice(100_000, size=997, replace=False).astype(np.int64),),
    (np.r_[RNG.choice(50_000, size=300), np.arange(0, 50_000, 500)
           ].astype(np.int32),),                      # sparse, duplicates
    (RNG.integers(0, 40, 600), RNG.integers(0, 40, 600)),   # two columns
    (np.zeros(0, np.int64),),
])
def test_hash_build_fmix32_tables_are_placed_as_before(key_cols):
    """Sparse and multi-column keys keep the fmix32 table, slot for slot,
    as the ``hash_keys_np`` placement gives it."""
    built = hash_build(key_cols)
    want = _fmix32_placement(key_cols)
    assert set(built) == set(want)
    for k in ("table_size", "max_probes", "base"):
        assert built[k] == want[k], k
    assert built["mean_probes"] == pytest.approx(want["mean_probes"])
    np.testing.assert_array_equal(built["slot_idx"], want["slot_idx"])
    for got, exp in zip(built["slot_keys"], want["slot_keys"]):
        assert got.dtype == exp.dtype
        np.testing.assert_array_equal(got, exp)


# -------------------------------------------------------------- radix groupby
@pytest.mark.parametrize("n,c,g,part,tile", [
    (100, 1, 8, 256, 128),
    (4_000, 3, 300, 64, 512),     # multiple partitions
    (2_048, 2, 1_000, 256, 256),  # sparse occupancy
    (513, 0, 16, 256, 512),       # counts only (C=0)
    (7, 2, 700, 128, 512),        # more groups than rows
])
def test_radix_groupby_sweep(n, c, g, part, tile):
    ids = RNG.integers(-1, g, n).astype(np.int32)     # -1 = padding rows
    vals = RNG.normal(size=(n, c)).astype(np.float32)
    s_ref, c_ref = radix_groupby_ref(jnp.asarray(ids), jnp.asarray(vals), g)
    s_got, c_got = radix_groupby(jnp.asarray(ids), jnp.asarray(vals), g,
                                 impl="interpret", part_groups=part,
                                 rows_tile=tile)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_got), np.asarray(c_ref))


def test_radix_groupby_matches_numpy():
    ids = RNG.integers(0, 97, 5_000).astype(np.int32)
    vals = RNG.normal(size=(5_000, 2)).astype(np.float32)
    sums, counts = radix_groupby(jnp.asarray(ids), jnp.asarray(vals), 97,
                                 impl="interpret")
    expect_c = np.bincount(ids, minlength=97)
    np.testing.assert_array_equal(np.asarray(counts), expect_c)
    for j in range(2):
        expect_s = np.zeros(97)
        np.add.at(expect_s, ids, vals[:, j])
        np.testing.assert_allclose(np.asarray(sums)[:, j], expect_s,
                                   rtol=1e-4, atol=1e-4)


def test_radix_groupby_all_padding():
    ids = np.full(300, -1, np.int32)
    vals = RNG.normal(size=(300, 2)).astype(np.float32)
    sums, counts = radix_groupby(jnp.asarray(ids), jnp.asarray(vals), 32,
                                 impl="interpret")
    np.testing.assert_array_equal(np.asarray(sums), np.zeros((32, 2)))
    np.testing.assert_array_equal(np.asarray(counts), np.zeros(32))


# ------------------------------------------------- exact integer group sums
def _exact_case(n, g, seed):
    """Group ids (-1 = padding), a signed int32 column and a wide column of
    products past int32, with their exact int64 values."""
    from repro.core import wideint
    rng = np.random.default_rng(seed)
    ids = rng.integers(-1, g, n).astype(np.int32)
    small = rng.integers(-2**31, 2**31 - 1, n)
    big = rng.integers(-2**31, 2**31 - 1, n) * rng.integers(-100, 100, n)
    wide = wideint.Wide(jnp.asarray((big & 0xFFFFFFFF).astype(np.uint32)),
                        jnp.asarray((big >> 32).astype(np.int32)))
    return ids, (jnp.asarray(small, jnp.int32), small), (wide, big)


def _exact_expect(ids, vals, g):
    out = np.zeros(g, dtype=np.int64)
    keep = ids >= 0
    np.add.at(out, ids[keep], vals[keep])
    return out


@pytest.mark.parametrize("route", ["radix", "segment_sum"])
@pytest.mark.parametrize("impl", ["interpret", "reference"])
@pytest.mark.parametrize("n,g", [(5_000, 7), (1_500, 300), (700, 1)])
def test_exact_integer_sums_equal_int64(route, impl, n, g):
    """Signed int32 and wide inputs, each less its minimum in 8-bit limbs,
    summed per group by the radix and segment-sum wrappers: the recombined
    sums equal numpy's int64 sums."""
    from repro.core import wideint
    ids, (small_d, small), (wide, big) = _exact_case(n, g, seed=n + g)
    ranges = [(int(small.min()), int(small.max())),
              (int(big.min()), int(big.max()))]
    limbs = tuple(wideint.limb_count(r) for r in ranges)
    ints = ((small_d, wideint.const(ranges[0][0])),
            (wide, wideint.const(ranges[1][0])))
    kernel = radix_groupby if route == "radix" else segment_sum
    _, counts, exact = kernel(jnp.asarray(ids), jnp.zeros((n, 0)), g,
                              impl=impl, ints=ints, limbs=limbs)
    got = wideint.recombine(exact, counts, [r[0] for r in ranges], limbs)
    np.testing.assert_array_equal(np.asarray(counts).sum(0),
                                  np.bincount(ids[ids >= 0], minlength=g))
    assert np.array_equal(got[0], _exact_expect(ids, small, g))
    assert np.array_equal(got[1], _exact_expect(ids, big, g))


def test_exact_sums_split_rows_into_int32_blocks(monkeypatch):
    """Past ``BLOCK_ROWS`` rows the limb sums come per block of rows, each
    within int32, and add up on the host."""
    from repro.kernels.radix_groupby import exact
    monkeypatch.setattr(exact, "BLOCK_ROWS", 1024)
    ids = np.arange(5_000, dtype=np.int32) % 3
    limbs = jnp.full((2, 5_000), 255, jnp.int32)
    for fn in (exact.exact_sums_ref,
               lambda i, l, g: exact.exact_sums_pallas(i, l, g,
                                                       interpret=True)):
        out = np.asarray(fn(jnp.asarray(ids), limbs, 3))
        assert out.shape[0] == 5                   # ceil(5000 / 1024)
        np.testing.assert_array_equal(
            out.sum(0), 255 * np.bincount(ids, minlength=3)[:, None]
            .repeat(2, 1))


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,Sq,Skv,Kh,G,hd,causal,window,softcap,bq,bk", [
    (1, 64, 64, 1, 1, 32, True, 0, 0.0, 32, 32),
    (2, 128, 128, 2, 2, 64, True, 0, 0.0, 32, 64),
    (2, 128, 128, 2, 2, 64, False, 0, 0.0, 64, 32),
    (1, 96, 96, 2, 4, 32, True, 24, 0.0, 32, 32),     # sliding window
    (1, 64, 64, 4, 1, 64, True, 0, 30.0, 32, 32),     # grok softcap
    (2, 80, 80, 1, 8, 16, True, 0, 0.0, 32, 32),      # ragged blocks (pad)
    (1, 33, 57, 1, 2, 8, False, 0, 0.0, 16, 16),      # cross-attn shapes
])
def test_flash_attention_sweep(B, Sq, Skv, Kh, G, hd, causal, window,
                               softcap, bq, bk):
    q = jnp.array(RNG.normal(size=(B, Sq, Kh, G, hd)), jnp.float32)
    k = jnp.array(RNG.normal(size=(B, Skv, Kh, hd)), jnp.float32)
    v = jnp.array(RNG.normal(size=(B, Skv, Kh, hd)), jnp.float32)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=softcap)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, impl="interpret",
                          block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.array(got), np.array(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    B, S, Kh, G, hd = 1, 64, 2, 2, 32
    q = jnp.array(RNG.normal(size=(B, S, Kh, G, hd)), jnp.bfloat16)
    k = jnp.array(RNG.normal(size=(B, S, Kh, hd)), jnp.bfloat16)
    v = jnp.array(RNG.normal(size=(B, S, Kh, hd)), jnp.bfloat16)
    ref = flash_attention_ref(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, impl="interpret",
                          block_q=32, block_k=32)
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


# --------------------------------------------------------------- mamba scan
@pytest.mark.parametrize("Bt,T,d,N,chunk,dblk", [
    (1, 16, 8, 4, 8, 8),
    (2, 48, 24, 8, 16, 16),
    (2, 100, 32, 16, 32, 16),     # ragged T (pad)
    (1, 64, 48, 16, 64, 512),     # d < d_block
])
def test_mamba_scan_sweep(Bt, T, d, N, chunk, dblk):
    delta = jnp.array(np.abs(RNG.normal(size=(Bt, T, d))).clip(0.01, 1.0),
                      jnp.float32)
    x = jnp.array(RNG.normal(size=(Bt, T, d)), jnp.float32)
    B = jnp.array(RNG.normal(size=(Bt, T, N)), jnp.float32)
    C = jnp.array(RNG.normal(size=(Bt, T, N)), jnp.float32)
    A = jnp.array(-np.abs(RNG.normal(size=(d, N))) - 0.05, jnp.float32)
    h0 = jnp.array(RNG.normal(size=(Bt, d, N)), jnp.float32)
    y_ref, hT_ref = mamba_scan_ref(delta, x, B, C, A, h0)
    y, hT = mamba_scan(delta, x, B, C, A, h0, impl="interpret",
                       chunk=chunk, d_block=dblk)
    np.testing.assert_allclose(np.array(y), np.array(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.array(hT), np.array(hT_ref),
                               rtol=1e-4, atol=1e-4)


def test_mamba_scan_continuation():
    """Scanning [0:T1] then [T1:T] from hT equals scanning [0:T] — the
    chunked-carry invariant the kernel's sequential grid relies on."""
    Bt, T, d, N = 1, 32, 8, 4
    delta = jnp.array(np.abs(RNG.normal(size=(Bt, T, d))).clip(0.01, 1.0),
                      jnp.float32)
    x = jnp.array(RNG.normal(size=(Bt, T, d)), jnp.float32)
    B = jnp.array(RNG.normal(size=(Bt, T, N)), jnp.float32)
    C = jnp.array(RNG.normal(size=(Bt, T, N)), jnp.float32)
    A = jnp.array(-np.abs(RNG.normal(size=(d, N))) - 0.05, jnp.float32)
    h0 = jnp.zeros((Bt, d, N), jnp.float32)
    y_full, hT_full = mamba_scan_ref(delta, x, B, C, A, h0)
    y1, h1 = mamba_scan(delta[:, :16], x[:, :16], B[:, :16], C[:, :16],
                        A, h0, impl="interpret", chunk=8, d_block=8)
    y2, h2 = mamba_scan(delta[:, 16:], x[:, 16:], B[:, 16:], C[:, 16:],
                        A, h1, impl="interpret", chunk=8, d_block=8)
    np.testing.assert_allclose(np.array(jnp.concatenate([y1, y2], 1)),
                               np.array(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.array(h2), np.array(hT_full),
                               rtol=1e-4, atol=1e-4)
