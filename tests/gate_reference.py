"""The native gate as a composition of numpy calls — what
`TpuMatcher._native_gate` was before it became one call into C (ISSUE 50),
kept here, outside the product, as the reference the one-call gate is held
to output for output (tests/differential/test_gate_differential.py):
`reference_native_gate` over a matcher, and the `unique_spans` it is made
of (the C span dedup behind a scalar fallback), which tests/unit still
exercise on their own."""

from typing import Dict, List, Tuple

import numpy as np

from banjax_tpu import native
from banjax_tpu.matcher.cpu_ref import OLD_LINE_CUTOFF_SECONDS
from banjax_tpu.matcher.encode import ParsedLine, parse_line
from banjax_tpu.matcher.longrows import LONG_WIDTH
from banjax_tpu.matcher.runner import log
from banjax_tpu.matcher.workset import ListWork, NativeWork


def blob_text(blob: bytes):
    """The whole blob as ONE str when it is pure ASCII (byte offsets are
    str offsets, so span strings are plain slices), else None."""
    return blob.decode("ascii") if blob.isascii() else None


def unique_spans(
    offs: np.ndarray, lens: np.ndarray, decode,
    blob: "bytes | None" = None, text: "str | None" = None,
    dedup_scratch=None,
) -> Tuple[List[str], np.ndarray, np.ndarray]:
    """Distinct-string extraction over (offset, length) spans of a blob.

    Fast path (native lib + `blob`): C open-addressing dedup
    (fastparse.c fp_dedup_spans) emits first-appearance-ordered ids
    directly; unique strings slice out of the ASCII `text` in one comp.
    Fallback (native lib failed to load mid-flight — the gate itself only
    runs with it loaded, so this is belt-and-braces): exact per-row dict
    dedup over decoded strings, trivially correct and first-appearance
    ordered. Returns (unique strings, per-row inverse, the row each
    string was first met in — where its bytes lie)."""
    n = len(offs)
    if n == 0:
        return [], np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    if blob is not None:
        df = native.dedup_spans(blob, offs, lens, dedup_scratch)
        if df is not None:
            ids, first = df
            if text is not None:
                # tolist() first: per-item numpy-scalar -> int conversions
                # cost more than the slices themselves at 65k uniques
                ot = offs.tolist()
                lt = lens.tolist()
                strings = [
                    text[ot[r] : ot[r] + lt[r]] for r in first.tolist()
                ]
            else:
                strings = [decode(int(r)) for r in first]
            return strings, ids, first
    seen: Dict[str, int] = {}
    strings: List[str] = []
    first_rows: List[int] = []
    inv = np.empty(n, dtype=np.int64)
    for r in range(n):
        s = decode(r)
        j = seen.get(s)
        if j is None:
            j = len(strings)
            strings.append(s)
            first_rows.append(r)
            seen[s] = j
        inv[r] = j
    return strings, inv, np.asarray(first_rows, dtype=np.int64)


def reference_native_gate(self, nb, lines, now, results, use_scratch=True):
    """`TpuMatcher._native_gate` as PR 49 left it, over the matcher
    `self`: flag masks, unique ip/host tables (unique_spans), allowlist
    per DISTINCT (host, ip) with a snapshot-keyed cache, and a columnar
    NativeWork whose `ips_u` is a list of strings."""

    dedup_scratch = self._dedup_scratch if use_scratch else None

    n = nb.n
    flags = np.asarray(nb.flags[:n])
    err = (flags & native.FLAG_ERROR) != 0
    old = (flags & native.FLAG_OLD) != 0
    ts = nb.ts_ns[:n].astype(np.int64, copy=True)

    defer_map: Dict[int, ParsedLine] = {}
    for r in np.flatnonzero(flags & native.FLAG_DEFER):
        r = int(r)
        p = parse_line(lines[r], now, OLD_LINE_CUTOFF_SECONDS)
        defer_map[r] = p
        err[r] = p.error
        old[r] = p.old_line
        if not p.error:
            # Python float()*1e9 can exceed int64 (the columnar array
            # feeding the device windows); clamp HERE only — replay and
            # the host window path read the exact Python int from the
            # deferred ParsedLine itself
            ts[r] = min(max(p.timestamp_ns, -(2**63)), 2**63 - 1)

    for r in np.flatnonzero(err):
        log.warning("could not parse log line: %r", lines[int(r)])
        results[int(r)].error = True
    for r in np.flatnonzero(old & ~err):
        results[int(r)].old_line = True

    cand = np.flatnonzero(~err & ~old)
    if cand.size == 0:
        return ListWork(), None

    # distinct ip/host string tables over the candidate rows; deferred
    # rows have no blob spans — patch their strings in via the tables
    dset = set(defer_map)
    vrows = np.asarray(
        [r for r in cand if int(r) not in dset], dtype=np.int64
    ) if dset else cand
    text = blob_text(nb.blob)
    ip_off, ip_len = nb.ip_off[vrows], nb.ip_len[vrows]
    ips_u, ip_inv_v, ip_first = unique_spans(
        ip_off, ip_len, lambda k: nb.ip(int(vrows[k])),
        blob=nb.blob, text=text, dedup_scratch=dedup_scratch,
    )
    hosts_u, host_inv_v, _ = unique_spans(
        nb.host_off[vrows], nb.host_len[vrows],
        lambda k: nb.host(int(vrows[k])),
        blob=nb.blob, text=text, dedup_scratch=dedup_scratch,
    )
    # the distinct addresses' key bytes where the parse blob holds
    # them: what the submit stage's pass over them works on
    span_buf = nb.blob
    span_off = ip_off[ip_first].astype(np.int64)
    span_len = ip_len[ip_first].astype(np.int64)
    ip_inv = np.empty(cand.size, dtype=np.int64)
    host_inv = np.empty(cand.size, dtype=np.int64)
    if dset:
        # vectorized membership/positions (cand is sorted): a python
        # per-element loop here would cost O(lines) whenever ANY row
        # deferred
        # sorted so deferred rows append to the unique tables in LINE
        # order (first-appearance contract), not set hash order
        darr = np.sort(np.fromiter(dset, dtype=np.int64))
        vmask = ~np.isin(cand, darr)
        ip_inv[vmask] = ip_inv_v
        host_inv[vmask] = host_inv_v
        iidx = {s: j for j, s in enumerate(ips_u)}
        hidx = {s: j for j, s in enumerate(hosts_u)}
        patched: List[bytes] = []  # a Python-parsed address's bytes
        for r in darr.tolist():
            p = defer_map[r]
            # position of r in cand, or absent (errored/old defer rows)
            k = int(np.searchsorted(cand, r))
            if k >= cand.size or cand[k] != r:
                continue
            j = iidx.get(p.ip)
            if j is None:
                j = len(ips_u)
                ips_u.append(p.ip)
                iidx[p.ip] = j
                patched.append(p.ip.encode("utf-8", "surrogatepass"))
            ip_inv[k] = j
            j = hidx.get(p.host)
            if j is None:
                j = len(hosts_u)
                hosts_u.append(p.host)
                hidx[p.host] = j
            host_inv[k] = j
        if patched:
            # ... lie behind the blob in a copy of it
            lens_p = np.fromiter(map(len, patched), np.int64, len(patched))
            offs_p = len(span_buf) + np.cumsum(lens_p) - lens_p
            span_buf = b"".join([span_buf, *patched])
            span_off = np.concatenate([span_off, offs_p])
            span_len = np.concatenate([span_len, lens_p])
    else:
        ip_inv[:] = ip_inv_v
        host_inv[:] = host_inv_v

    # allowlist per distinct (host, ip) pair, cached across batches
    # until the static-lists generation bumps (hot reload) — the CIDR
    # filters parse the ip string per check, which at per-line rates
    # costs more than the device match. A decision-lists object
    # WITHOUT the public counter never caches (fail safe, not stale).
    gen = getattr(self.decision_lists, "generation", None)
    if gen is None:
        self._allow_cache = {}
        self._allow_cache_snap = None
    elif gen != self._allow_cache_snap or \
            len(self._allow_cache) > 500_000:
        self._allow_cache = {}
        self._allow_cache_snap = gen
    has_allow = getattr(
        self.decision_lists, "has_any_allow_entries", lambda: True
    )()
    if has_allow:
        n_ip = max(1, len(ips_u))
        pair = host_inv * n_ip + ip_inv
        upair, upair_inv = np.unique(pair, return_inverse=True)
        allowed_u = np.empty(upair.size, dtype=bool)
        cache = self._allow_cache
        check = self.decision_lists.check_is_allowed
        for j, pr in enumerate(upair.tolist()):
            h = hosts_u[pr // n_ip]
            ip = ips_u[pr % n_ip]
            v = cache.get((h, ip))
            if v is None:
                v = check(h, ip)
                cache[(h, ip)] = v
            allowed_u[j] = v
        allowed = allowed_u[upair_inv]
        for k in np.flatnonzero(allowed):
            results[int(cand[k])].exempted = True
        keep = ~allowed
        rows = cand[keep]
    else:
        # no allow entries anywhere: nothing can be exempted
        keep = slice(None)
        rows = cand
    if rows.size == 0:
        return ListWork(), None
    work = NativeWork(
        nb, rows, ips_u, ip_inv[keep], hosts_u, host_inv[keep],
        ts[rows], defer_map,
        (np.frombuffer(span_buf, dtype=np.uint8), span_off, span_len),
    )

    deferred = (flags[rows] & native.FLAG_DEFER) != 0
    if rows.size == n:
        # nothing filtered (the common clean-traffic batch): views,
        # not 33 MB gather copies of the class matrix
        cls_ids = nb.cls_ids[:n]
        lens = nb.lens[:n]
    else:
        cls_ids = nb.cls_ids[rows]
        lens = nb.lens[rows]
    host_eval = (flags[rows] & native.FLAG_HOST_EVAL) != 0
    long_len = None
    if host_eval.any():
        # beside host_eval, as longrows.long_lens has it: a LONG row's
        # length, -1 for a row past LONG_WIDTH, 0 for every other row
        rest_len = nb.rest_len[:n][rows]
        long_len = np.where(
            (flags[rows] & native.FLAG_LONG) != 0, rest_len,
            np.where(host_eval & (rest_len > LONG_WIDTH), -1, 0),
        ).astype(np.int32)
    if deferred.any():
        # deferred rows were Python-parsed: encode them the Python way
        # into the same arrays
        d_idx = np.flatnonzero(deferred)
        d_cls, d_lens, d_he, d_long = self._encode_work(
            [work[int(k)] for k in d_idx]
        )
        cls_ids[d_idx] = d_cls
        lens[d_idx] = d_lens
        host_eval[d_idx] = d_he
        if d_long is not None:
            if long_len is None:
                long_len = np.zeros(len(lens), dtype=np.int32)
            long_len[d_idx] = d_long
    return work, (cls_ids, lens, host_eval, long_len)
