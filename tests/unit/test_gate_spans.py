"""The pieces of the one-call gate (ISSUE 50), each alone: the distinct
addresses as a sequence that makes a string when asked (workset.
SpanStrings), the scratches' addresses taken once and following a buffer
that grew (native.ParseScratch / DedupScratch), native.gate's columns
against numpy, and the two counters that say the mechanism engages.  The
gate as a whole is held to the composition it replaced in
tests/differential/test_gate_differential.py."""

import time

import numpy as np
import pytest

from banjax_tpu import native
from banjax_tpu.matcher.longrows import LONG_WIDTH
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.matcher.workset import (
    CompositeWork,
    NativeWork,
    SpanStrings,
    StringCount,
    decode_spans,
)
from tests.differential.test_host_parallel_differential import _build

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no native parse here (no C compiler)"
)

NOW = time.time()
B2C = np.arange(257, dtype=np.int32) % 7

WORDS = ["10.0.0.1", "", "10.0.0.é", "καφές", "x" * 70, "10.0.0.1"]


def _spans(words=WORDS, count=None):
    raw = [w.encode("utf-8", "surrogatepass") for w in words]
    blob = b"|".join(raw)
    lens = np.asarray([len(r) for r in raw], dtype=np.int64)
    offs = np.cumsum(lens + 1) - lens - 1
    return SpanStrings(blob, offs, lens, count)


# ------------------------------------------------------------ SpanStrings


@pytest.mark.parametrize("j", [0, 1, 2, 3, 4, 5, -1, -3, -6,
                               np.int64(2), np.int32(-2)])
def test_span_strings_index_is_the_lists(j):
    assert _spans()[j] == WORDS[j]


@pytest.mark.parametrize("j", [6, -7, 10**6])
def test_span_strings_index_out_of_range_raises_as_a_list_does(j):
    with pytest.raises(IndexError):
        WORDS[j]
    with pytest.raises(IndexError):
        _spans()[j]


@pytest.mark.parametrize("sl", [
    slice(None), slice(1, 4), slice(-2, None), slice(None, None, 2),
    slice(None, None, -1), slice(4, 1), slice(2, 100),
])
def test_span_strings_slice_is_the_lists_slice(sl):
    got = _spans()[sl]
    assert isinstance(got, list) and got == WORDS[sl]


def test_span_strings_wears_the_lists_interface():
    s = _spans()
    assert len(s) == len(WORDS) and list(s) == WORDS
    assert [w for w in s] == WORDS and "".join(s) == "".join(WORDS)
    assert s == WORDS and WORDS == s and s == _spans()
    assert s != WORDS[:-1] and not (s == WORDS[::-1])
    assert (s == 5) is False and s != "10.0.0.1"
    with pytest.raises(TypeError):
        hash(s)
    with pytest.raises(TypeError):
        s["0"]
    assert repr(s) == f"SpanStrings({WORDS!r})"
    assert len(_spans([])) == 0 and list(_spans([])) == []


def test_span_strings_counts_each_string_it_makes():
    """One for an index, as many as it yields for a slice or a walk; and
    nothing for what makes no string."""
    count = StringCount()
    s = _spans(count=count)
    len(s)
    assert count.n == 0
    s[0], s[-1]
    assert count.n == 2
    s[1:4]
    assert count.n == 5
    list(s)
    assert count.n == 5 + len(WORDS)
    with pytest.raises(IndexError):
        s[99]
    assert count.n == 5 + len(WORDS)
    # a sequence given no counter keeps one of its own
    assert _spans()._count is not count


def test_span_strings_lone_surrogates_round_trip():
    """A line's str may hold what utf-8 proper refuses; the blob is made
    with surrogatepass and a span reads back the same str."""
    odd = ["10.0.0.\udc80", "\ud800x"]
    assert list(_spans(odd)) == odd and _spans(odd)[1] == odd[1]


def _work(lines, count=None):
    nb = native.parse_encode_batch(lines, B2C, 64, NOW, 10.0)
    g = native.gate(nb)
    ips_u = SpanStrings(nb.blob, g.ip_off, g.ip_len, count)
    hosts_u = decode_spans(nb.blob, g.host_off, g.host_len)
    return NativeWork(
        nb, g.rows, ips_u, g.ip_inv, hosts_u, g.host_inv, g.ts, {},
        (np.frombuffer(nb.blob, dtype=np.uint8), g.ip_off, g.ip_len),
    )


def _lines(ips, host="h.com"):
    return [f"{NOW:f} {ip} GET {host} GET /p{i} -" for i, ip in enumerate(ips)]


def test_span_strings_survive_take_slice_and_lines_at():
    count = StringCount()
    ips = ["1.1.1.1", "2.2.2.2", "1.1.1.1", "3.3.3.3", "2.2.2.2", "4.4.4.4"]
    w = _work(_lines(ips), count)
    whole, inv = w.unique_ips()
    assert whole is w.ips_u and count.n == 0  # unsliced: the table itself
    assert whole == ["1.1.1.1", "2.2.2.2", "3.3.3.3", "4.4.4.4"]
    assert inv.tolist() == [0, 1, 0, 2, 1, 3]
    count.n = 0
    sub = w.take(np.asarray([2, 3]))
    assert sub.ips_u is w.ips_u
    got, inv = sub.unique_ips()
    assert got == ["1.1.1.1", "3.3.3.3"] and inv.tolist() == [0, 1]
    assert count.n == 2  # the two the subset holds, not the table's four
    got, inv = w[4:].unique_ips()
    assert got == ["2.2.2.2", "4.4.4.4"] and inv.tolist() == [0, 1]
    count.n = 0
    assert [(i, p.ip) for i, p in w.lines_at([5, 0])] == [
        (5, "4.4.4.4"), (0, "1.1.1.1")]
    assert count.n == 2
    i, p = w[3]
    assert (i, p.ip, p.host) == (3, "3.3.3.3", "h.com") and count.n == 3


def test_span_strings_survive_composite_unique_ips():
    """The string merge over shards whose tables are spans: shard order,
    then each shard's first-appearance order — and by bytes the same."""
    a = _work(_lines(["1.1.1.1", "2.2.2.2", "1.1.1.1"]))
    b = _work(_lines(["3.3.3.3", "2.2.2.2", "καφές"]))
    c = _work(_lines(["καφές", "1.1.1.1"]))
    comp = CompositeWork([a, b, c], [0, 3, 6])
    ips, inv = comp.unique_ips()
    assert ips == ["1.1.1.1", "2.2.2.2", "3.3.3.3", "καφές"]
    assert inv.tolist() == [0, 1, 0, 2, 1, 3, 3, 0]
    spans, sinv = comp.unique_ip_spans()
    assert spans.strings() == ips and sinv.tolist() == inv.tolist()
    assert [(i, p.ip) for i, p in comp.lines_at([5, 6])] == [
        (5, "καφές"), (6, "καφές")]


# ------------------------------------------------ addresses taken once


def _addresses(arrays):
    return tuple(a.ctypes.data for a in arrays)


def _parse_cols(s):
    return (s.starts, s.ends, s.ts_ns, s.flags, s.ip_off, s.ip_len,
            s.host_off, s.host_len, s.rest_off, s.rest_len, s.cls_ids, s.lens)


def _parsed(nb):
    return [
        (int(nb.ts_ns[i]), int(nb.flags[i]), nb.ip(i), nb.host(i),
         nb.rest(i), nb.cls_ids[i].tolist(), int(nb.lens[i]))
        for i in range(nb.n)
    ]


def test_parse_scratch_keeps_its_addresses_until_a_buffer_grows():
    s = native.ParseScratch()
    small = _lines([f"1.2.3.{i}" for i in range(10)])
    nb = native.parse_encode_batch(small, B2C, 64, NOW, 10.0, s)
    first = s.addrs
    assert first == _addresses(_parse_cols(s)) and nb.addrs is first
    want_small = _parsed(native.parse_encode_batch(small, B2C, 64, NOW, 10.0))
    assert _parsed(nb) == want_small
    # the same size again: nothing is made anew
    native.parse_encode_batch(small[::-1], B2C, 64, NOW, 10.0, s)
    assert s.addrs is first
    held = [np.array(a) for a in (nb.ts_ns, nb.ip_off, nb.rest_off, nb.lens)]
    # past the capacity: new buffers, and the addresses are theirs
    big = _lines([f"9.{i // 250}.{i % 250}.1" for i in range(1500)])
    nb_big = native.parse_encode_batch(big, B2C, 64, NOW, 10.0, s)
    assert s.cap >= 1500 and s.addrs != first
    assert s.addrs == _addresses(_parse_cols(s)) and nb_big.addrs is s.addrs
    assert _parsed(nb_big) == _parsed(
        native.parse_encode_batch(big, B2C, 64, NOW, 10.0))
    # the first batch's views keep the buffers they were cut from, which
    # the grown scratch no longer writes to
    assert nb.addrs is first
    for was, a in zip(held, (nb.ts_ns, nb.ip_off, nb.rest_off, nb.lens)):
        np.testing.assert_array_equal(a, was)
    # another width: the class matrix is made anew, with everything else
    grown = s.addrs
    nb_wide = native.parse_encode_batch(small, B2C, 96, NOW, 10.0, s)
    assert s.addrs != grown and s.addrs == _addresses(_parse_cols(s))
    assert nb_wide.cls_ids.shape == (10, 96)
    assert [r[:5] for r in _parsed(nb_wide)] == [r[:5] for r in want_small]


def test_dedup_scratch_keeps_its_addresses_until_it_grows():
    s = native.DedupScratch()
    blob = b"aa bb aa cc bb"
    offs = np.asarray([0, 3, 6, 9, 12], dtype=np.int64)
    lens = np.full(5, 2, dtype=np.int32)
    ids, first = native.dedup_spans(blob, offs, lens, s)
    assert ids.tolist() == [0, 1, 0, 2, 1] and first.tolist() == [0, 1, 3]
    held = s.addrs
    assert held == _addresses((s.table, s.ids, s.first))
    native.dedup_spans(blob, offs[:3], lens[:3], s)
    assert s.addrs is held
    n = 3000
    many = b"".join(b"%04d" % (i % 1700) for i in range(n))
    offs = np.arange(n, dtype=np.int64) * 4
    ids, first = native.dedup_spans(many, offs, np.full(n, 4, np.int32), s)
    assert s.addrs != held
    assert s.addrs == _addresses((s.table, s.ids, s.first))
    assert ids.tolist() == [i % 1700 for i in range(n)]
    assert first.tolist() == list(range(1700))


def test_a_parse_split_across_threads_fills_the_same_columns():
    """Row ranges past the first start at the cached addresses plus whole
    rows (a row of the class matrix is its width)."""
    lines = _lines([f"7.{i // 200}.{i % 200}.9" for i in range(8192 + 37)])
    lines[4100] = "garbage"
    lines[8200] = f"{NOW - 99:f} 1.1.1.1 GET h.com GET /old -"
    one = native.parse_encode_batch(lines, B2C, 48, NOW, 10.0, max_threads=1)
    many = native.parse_encode_batch(lines, B2C, 48, NOW, 10.0, max_threads=4)
    for name in ("ts_ns", "flags", "ip_off", "ip_len", "host_off",
                 "host_len", "rest_off", "rest_len", "cls_ids", "lens"):
        np.testing.assert_array_equal(
            getattr(one, name), getattr(many, name), err_msg=name)


# ------------------------------------------------------------ native.gate


def _gate_lines():
    long_rest = "GET h2.org GET /" + "a" * 100 + " -"
    return [
        f"{NOW:f} 1.1.1.1 GET h1.org GET /a -",
        "garbage",
        f"{NOW:f} 2.2.2.2 GET h2.org GET /b -",
        f"{NOW - 50:f} 3.3.3.3 GET h1.org GET /old -",
        f"{NOW:f} 1.1.1.1 {long_rest}",
        "1_0.5 4.4.4.4 GET h1.org GET /deferred -",
        f"{NOW:f} 5.5.5.5 GET h3.org GET /" + "b" * (LONG_WIDTH + 1) + " -",
        f"{NOW:f} 2.2.2.2 GET h1.org GET /café -",
    ]


@pytest.mark.parametrize("scratch", [None, "own"])
def test_gate_columns_are_numpys(scratch):
    lines = _gate_lines()
    ps = native.ParseScratch() if scratch else None
    ds = native.DedupScratch() if scratch else None
    nb = native.parse_encode_batch(lines, B2C, 64, NOW, 10.0, ps)
    g = native.gate(nb, ds)
    assert (g.n_err, g.n_old, g.n_defer, g.n_host_eval) == (1, 1, 1, 3)
    assert g.rows.tolist() == [0, 2, 4, 6, 7]
    np.testing.assert_array_equal(g.ts, nb.ts_ns[g.rows])
    assert g.ip_inv.tolist() == [0, 1, 0, 2, 1]
    assert g.host_inv.tolist() == [0, 1, 1, 2, 0]
    blob = nb.blob

    def strings(offs, lens):
        return [blob[o : o + n].decode() for o, n in zip(offs, lens)]

    assert strings(g.ip_off, g.ip_len) == ["1.1.1.1", "2.2.2.2", "5.5.5.5"]
    assert strings(g.host_off, g.host_len) == ["h1.org", "h2.org", "h3.org"]
    assert g.host_eval.dtype == np.bool_ and g.long_len.dtype == np.int32
    assert g.host_eval.tolist() == [False, False, True, True, True]
    assert g.long_len.tolist() == [0, 0, int(nb.rest_len[4]), -1, 0]
    for name in ("ts", "ip_off", "ip_len", "host_off", "host_len",
                 "rows", "ip_inv", "host_inv"):
        assert getattr(g, name).dtype == np.int64, name
    # nothing the gate leaves is a view of a scratch
    for name in g.__slots__[:10]:
        for buf in (nb.ts_ns, nb.flags, nb.ip_off, nb.ip_len):
            assert not np.shares_memory(getattr(g, name), buf), name
        if ds is not None:
            assert not np.shares_memory(getattr(g, name), ds.table), name


def test_gate_of_an_empty_batch_is_none():
    nb = native.parse_encode_batch([], B2C, 64, NOW, 10.0)
    assert nb.n == 0 and native.gate(nb) is None


# ------------------------------------------------------------ the counters


@pytest.fixture()
def matcher():
    m, *_ = _build(TpuMatcher, device_windows=True)
    yield m
    m.close()


def _good(n, tag="a"):
    return [f"{NOW:f} 10.0.{i % 4}.{i % 9} GET example.com GET /{tag}{i} -"
            for i in range(n)]


def test_gate_shards_counts_a_batch_or_a_shard_by_its_path(matcher):
    m = matcher
    assert m.gate_shards == {"native": 0, "python": 0}
    m.pipeline_begin(_good(30), NOW)
    assert m.gate_shards == {"native": 1, "python": 0}
    # a line with a newline in it: the parse declines, the loop gates
    m.pipeline_begin(_good(5) + ["x\ny"], NOW)
    assert m.gate_shards == {"native": 1, "python": 1}
    # shards are counted where they are merged, one each, by their own path
    lines = _good(20) + ["split\nline"] + _good(9, "b")
    cuts = [0, 10, 20, 25, 30]
    shards = [(a, m.encode_shard(lines[a:b], NOW))
              for a, b in zip(cuts, cuts[1:])]
    assert [s[1][3] for s in shards] == ["native", "native", "python",
                                         "native"]
    assert m.gate_shards == {"native": 1, "python": 1}  # nothing merged yet
    state = m.pipeline_begin_from_shards(lines, NOW, shards)
    assert m.gate_shards == {"native": 4, "python": 2}
    assert len(state["work"]) == 29  # the split line is no line
    # the synchronous entry counts its batches too
    m.consume_lines(_good(12), now_unix=NOW)
    assert m.gate_shards == {"native": 5, "python": 2}


def test_gate_shards_counts_python_without_the_library(matcher, monkeypatch):
    monkeypatch.setattr(matcher, "_native", False)
    matcher.pipeline_begin(_good(10), NOW)
    work, pre, results, path = matcher.encode_shard(_good(10), NOW)
    assert path == "python" and len(work) == 10 and pre is None
    assert matcher.gate_shards == {"native": 0, "python": 1}


def test_address_strings_are_counted_once_a_materialisation():
    """The test configuration of the differential suites has an allow
    entry, so its gate makes the strings of every batch (and counts
    them); without one a batch makes none until a row is asked for."""
    import tests.differential.test_gate_differential as gd

    allow, none = gd._matcher("allow"), gd._matcher("none")
    try:
        lines = _good(40)
        distinct = len({ln.split(" ")[1] for ln in lines})
        allow.pipeline_begin(lines, NOW)
        assert allow.gate_address_strings == distinct
        work = none.pipeline_begin(lines, NOW)["work"]
        assert none.gate_address_strings == 0
        work.unique_ip_spans(), work.host_idx({}), work.ts_array()
        assert none.gate_address_strings == 0
        work.lines_at([3, 4, 5])
        assert none.gate_address_strings == 3
        list(work.unique_ips()[0])
        assert none.gate_address_strings == 3 + distinct
    finally:
        allow.close()
        none.close()


def test_the_library_is_cached_by_what_its_source_says(tmp_path, monkeypatch):
    """Two checkouts unpacked in the same second share a cache directory
    and a source mtime; a build named by the mtime let the one load the
    other's library, without the functions it asks for."""
    import os
    import shutil

    monkeypatch.setenv("BANJAX_NATIVE_CACHE", str(tmp_path))
    here = native._so_path()
    assert os.path.dirname(here) == str(tmp_path)
    other = tmp_path / "fastparse.c"
    shutil.copy(native._SRC, other)
    with open(other, "a") as f:
        f.write("\nint64_t fp_one_more(void) { return 1; }\n")
    stamp = os.stat(native._SRC)
    os.utime(other, ns=(stamp.st_atime_ns, stamp.st_mtime_ns))
    monkeypatch.setattr(native, "_SRC", str(other))
    assert native._so_path() != here
    monkeypatch.undo()
    monkeypatch.setenv("BANJAX_NATIVE_CACHE", str(tmp_path))
    assert native._so_path() == here
