"""Direct unit coverage for matcher/workset.py — the columnar work-batch
interface both the native and fallback gates provide (NativeWork/ListWork/
LazyResults/LazyLine). The differential suite covers these end-to-end; here
the interface contracts are pinned in isolation."""

import gc

import numpy as np
import pytest

from banjax_tpu import native
from banjax_tpu.matcher.encode import ParsedLine
from banjax_tpu.matcher.workset import (
    LazyLine,
    LazyResults,
    ListWork,
    NativeWork,
)
from tests.gate_reference import blob_text, unique_spans


def _native_batch(lines, max_len=64):
    b2c = np.zeros(257, dtype=np.int32)
    return native.parse_encode_batch(lines, b2c, max_len, 2e9, 1e18)


@pytest.fixture()
def nb():
    if not native.available():
        pytest.skip("no C compiler")
    lines = [
        f"1700000000.{i:06d} 10.0.0.{i % 3} GET h{i % 2}.com GET /p{i} x"
        for i in range(8)
    ]
    return _native_batch(lines)


def _work_from(nb, rows=None):
    rows = np.arange(nb.n, dtype=np.int64) if rows is None else rows
    text = blob_text(nb.blob)
    ips_u, ip_inv, _ = unique_spans(
        nb.ip_off[rows], nb.ip_len[rows], lambda k: nb.ip(int(rows[k])),
        blob=nb.blob, text=text,
    )
    hosts_u, host_inv, _ = unique_spans(
        nb.host_off[rows], nb.host_len[rows], lambda k: nb.host(int(rows[k])),
        blob=nb.blob, text=text,
    )
    return NativeWork(nb, rows, ips_u, ip_inv, hosts_u, host_inv,
                      nb.ts_ns[rows].astype(np.int64), {})


def test_native_work_rows_and_lazy_rest(nb):
    w = _work_from(nb)
    assert len(w) == 8
    i, p = w[3]
    assert i == 3
    assert p.ip == "10.0.0.0" and p.host == "h1.com"
    assert isinstance(p, LazyLine) and p._rest is None  # not yet decoded
    assert p.rest.startswith("GET h1.com GET /p3")
    assert p.error is False and p.old_line is False


def test_native_work_slicing_compacts_uniques(nb):
    w = _work_from(nb)
    ips, inv = w.unique_ips()
    assert ips == ["10.0.0.0", "10.0.0.1", "10.0.0.2"]  # first appearance
    assert inv.tolist() == [0, 1, 2, 0, 1, 2, 0, 1]
    sl = w[0:2]  # rows 0-1: only two ips present
    ips2, inv2 = sl.unique_ips()
    assert ips2 == ["10.0.0.0", "10.0.0.1"]
    assert inv2.tolist() == [0, 1]
    # host_idx maps through a host-row table; unknown hosts -> 0
    hi = sl.host_idx({"h1.com": 5})
    assert hi.tolist() == [0, 5]


def test_native_work_defer_map_overrides(nb):
    p = ParsedLine(timestamp_ns=123, ip="9.9.9.9", host="d.com", rest="R")
    w = _work_from(nb)
    w.defer_map[2] = p
    i, got = w[2]
    assert i == 2 and got is p


@pytest.mark.parametrize("rows", [[], [7], [0, 3, 3, 5], [6, 1, 7, 0]])
def test_native_work_lines_at_is_indexing_row_by_row(nb, rows):
    """The replay's route to the few rows of a chunk that have an effect
    (PR 42): one pass over the columns, a line per asked row."""
    from banjax_tpu.matcher.workset import CompositeWork

    sentinel = ParsedLine(timestamp_ns=5, ip="9.9.9.9", host="d", rest="r")
    w = _work_from(nb)
    w.defer_map[3] = sentinel
    want = [w[k] for k in rows]
    got = w.lines_at(np.asarray(rows, dtype=np.int32))
    key = lambda pair: (  # noqa: E731
        pair[0], pair[1].ip, pair[1].host, pair[1].timestamp_ns, pair[1].rest)
    assert list(map(key, got)) == list(map(key, want))
    assert all(p is sentinel for i, p in got if i == 3)
    assert all(isinstance(p, LazyLine) for i, p in got if i != 3)
    # a subset keeps its own numbering, and two shards the batch's
    sub = w.take(np.asarray([1, 3, 6]))
    assert [i for i, _ in sub.lines_at([2, 0])] == [6, 1]
    both = CompositeWork([w, sub], [0, 8])
    assert list(map(key, both.lines_at([9, 2, 10]))) \
        == list(map(key, [both[9], both[2], both[10]]))


def test_list_work_interface():
    mk = lambda ip, host, ts: ParsedLine(
        timestamp_ns=ts, ip=ip, host=host, rest="r"
    )
    lw = ListWork([(0, mk("a", "h", 5)), (1, mk("b", "h", 6)),
                   (2, mk("a", "g", 10**25))])
    ips, inv = lw.unique_ips()
    assert ips == ["a", "b"] and inv.tolist() == [0, 1, 0]
    assert lw.host_idx({"g": 3}).tolist() == [0, 0, 3]
    ts = lw.ts_array()
    assert ts.dtype == np.int64
    assert ts[2] == 2**63 - 1  # out-of-int64 clamps instead of raising
    sl = lw[1:]
    assert isinstance(sl, ListWork) and len(sl) == 2


def test_lazy_results_materialize_on_access():
    r = LazyResults(4)
    assert len(r) == 4
    r[1].error = True
    assert r._items[0] is None          # untouched stays unmaterialized
    assert r[1].error and not r[2].error
    assert [x.error for x in r] == [False, True, False, False]
    assert [x.error for x in r[1:3]] == [True, False]


@pytest.fixture()
def collector_off():
    """The test's body with the automatic collector off and nothing left
    over from earlier tests: what `gc.collect()` then returns is what the
    body left that only a collection can free."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def _read_flags(results):
    """error | old_line << 1 | exempted << 2 of every row (a list of
    tuples would be 4,096 objects the collector tracks, of the test's)."""
    return [x.error + 2 * x.old_line + 4 * x.exempted for x in results]


def _owes_row_7(results):
    results.owed(7).append("rr7")


def _plain(n):
    r = LazyResults(n)
    r.defer(_owes_row_7)
    r[5].old_line = True
    return r, None


def _absorbed_from_two_shards(n):
    half = n // 2
    a, b = LazyResults(half), LazyResults(n - half)
    a[5].old_line = True
    b[1].error = True
    kept = b[1]  # a reader that kept a row of a shard across the merge
    r = LazyResults(n)
    r.absorb(a, 0)
    r.absorb(b, half)
    r.defer(_owes_row_7)
    assert r[half + 1].error and not r[half].error
    return r, kept


def _after_a_rule_results_read(n):
    r, _ = _plain(n)
    assert r[7].rule_results == ["rr7"]     # ran the fill
    assert r[8].rule_results == []          # and made a list for a clean row
    return r, None


@pytest.mark.parametrize(
    "build",
    [_plain, _absorbed_from_two_shards, _after_a_rule_results_read],
)
def test_reading_every_rows_flags_leaves_the_collector_nothing(
        collector_off, build):
    """What the pipeline's observer does with every batch (ISSUE 40): a
    4,096-row vector, a fill deferred, the three flags of every row read,
    the vector dropped.  No reference cycle, so nothing waits for a
    collection; and while the vector lives the rows read left no object
    behind, so none is a survivor that brings a full pass nearer."""
    before = len(gc.get_objects())
    r, kept = build(4096)
    flags = _read_flags(r)
    assert flags[5] == 2 and len(flags) == 4096
    assert sum(flags) == (2 if kept is None else 3)
    # alive: the vector, its list, the list of fills, the written rows
    # with their lists, `flags` — not one object a row
    assert len(gc.get_objects()) - before < 64
    del r, kept, flags
    assert gc.collect() == 0


def test_a_line_result_outlives_its_vector_and_still_answers(collector_off):
    r = LazyResults(16)
    r.defer(_owes_row_7)
    r[3].exempted = True
    kept = [r[3], r[7], r[9]]
    it = iter(r)
    first = next(it)
    del r, it
    assert kept[0].exempted and not kept[0].error
    assert kept[1].rule_results == ["rr7"]  # the fill still runs
    assert kept[2].rule_results == [] and not kept[2].old_line
    kept[2].error = True                    # and a write still lands
    assert kept[2].error and not first.error
    assert kept[0] != kept[2]
    del kept, first
    assert gc.collect() == 0


def test_lazy_results_rows_are_one_row_whoever_reads_them():
    """A row has one state however many views of it are out: a write
    through one shows in all, slices and negative indices included."""
    r = LazyResults(4)
    a, b = r[2], r[2]
    a.error = True
    b.rule_results.append("x")
    assert b.error and a.rule_results == ["x"] and a == b
    assert r[-2].error and r[1:3][1].rule_results == ["x"]
    r[2].rule_results = []
    assert a.rule_results == []
    with pytest.raises(IndexError):
        r[4]


def test_array_ptr_points_at_the_array_and_leaves_no_cycle(collector_off):
    """`native/cptr.array_ptr` against numpy's `data_as`, which leaves a
    `c_void_p` and a dict in a cycle at every call."""
    import ctypes

    from banjax_tpu.native.cptr import array_ptr

    a = np.arange(5, dtype=np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    for _ in range(10):
        p = array_ptr(a, i32p)
    assert [p[k] for k in range(5)] == [0, 1, 2, 3, 4]
    p[1] = 7
    assert a[1] == 7
    assert ctypes.addressof(p.contents) == a.ctypes.data
    del p
    assert gc.collect() == 0
    q = array_ptr(np.arange(3, dtype=np.int64), ctypes.POINTER(ctypes.c_int64))
    assert q[2] == 2  # the pointer keeps a temporary alive


def test_unique_spans_fallback_and_native_agree_on_nuls():
    blob = b"a\x00b a\x00b a\x00c"
    offs = np.asarray([0, 4, 8], dtype=np.int64)
    lens = np.asarray([3, 3, 3], dtype=np.int32)

    def dec(k):
        return blob[int(offs[k]) : int(offs[k]) + int(lens[k])].decode()

    s1, i1, _ = unique_spans(offs, lens, dec)  # scalar fallback
    assert s1 == ["a\x00b", "a\x00c"] and i1.tolist() == [0, 0, 1]
    if native.available():
        s2, i2, _ = unique_spans(offs, lens, dec, blob=blob)
        assert s2 == s1 and i2.tolist() == i1.tolist()
