"""Compiled rules kept beside JAX's compile cache.

A matcher compiles its ruleset three times at start — the single-slab
tensors whose byte classes the encode uses, the slabs the Pallas kernel
scans, and the two-stage prefilter plan — about a second a thousand rules
each, so ten thousand rules are half a minute of every start (and were
minutes until `rulec.choose_shards` stopped simulating every shard count).
The results are pure functions of the ruleset, so they are kept as `.npz`
files in `<compile cache>/banjax_rules/`, keyed by the ruleset's content
(every rule's site, name, regex, interval, limit, decision and skipped
hosts, in order), the packing's arguments and the compilers' own source: a
changed regex or limit, or a changed `rulec.py` / `prefilter.py` /
`selectivity.py`, is
another key.  Arrays and numbers only: nothing is unpickled.

No compile cache directory (a test, a library use), or a file that cannot
be read: the rules are compiled as before."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import time
from typing import Callable, Optional

import numpy as np

from banjax_tpu.matcher import prefilter, rulec, selectivity
from banjax_tpu.obs import trace

log = logging.getLogger(__name__)

_DIR_NAME = "banjax_rules"


def _code_digest() -> str:
    h = hashlib.sha256()
    for mod in (rulec, prefilter, selectivity):
        with open(mod.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def default_directory() -> str:
    """`banjax_rules/` inside the directory JAX keeps its compile cache in
    (cli.place_compile_cache); "" where it keeps none."""
    import jax

    root = jax.config.jax_compilation_cache_dir
    return os.path.join(root, _DIR_NAME) if root else ""


def ruleset_key(entries) -> str:
    """Content key of a matcher's rule table: `entries` is its
    [(site or None, RegexWithRate)] in column order."""
    rows = [
        [site, r.rule, r.regex_string, int(r.interval_ns),
         int(r.hits_per_interval), str(r.decision),
         sorted(r.hosts_to_skip.items())]
        for site, r in entries
    ]
    h = hashlib.sha256(_code_digest().encode())
    h.update(str(rulec.KERNEL_WORD_ALIGN).encode())
    h.update(json.dumps(rows, sort_keys=True, default=str).encode())
    return h.hexdigest()[:32]


def _flatten(obj, prefix: str, arrays: dict, meta: dict) -> None:
    """A CompiledRules or PrefilterPlan → arrays by dotted name + a JSON
    tree of everything else."""
    for f in dataclasses.fields(obj):
        v = getattr(obj, f.name)
        name = prefix + f.name
        if isinstance(v, np.ndarray):
            arrays[name] = v
        elif dataclasses.is_dataclass(v):
            meta[f.name] = sub = {}
            _flatten(v, name + ".", arrays, sub)
        elif isinstance(v, dict):
            meta[f.name] = {
                "__dict__": [[int(k), str(x)] for k, x in v.items()]
            }
        else:
            meta[f.name] = None if v is None else (
                bool(v) if isinstance(v, (bool, np.bool_)) else int(v)
            )


def _rebuild(cls, prefix: str, arrays, meta: dict):
    kw = {}
    for f in dataclasses.fields(cls):
        name = prefix + f.name
        if name in arrays:
            kw[f.name] = arrays[name]
        elif isinstance(meta.get(f.name), dict) and "__dict__" in meta[f.name]:
            kw[f.name] = {int(k): x for k, x in meta[f.name]["__dict__"]}
        elif isinstance(meta.get(f.name), dict):
            kw[f.name] = _rebuild(
                rulec.CompiledRules, name + ".", arrays, meta[f.name]
            )
        else:
            kw[f.name] = meta[f.name]
    return cls(**kw)


def save(path: str, obj) -> None:
    arrays, meta = {}, {}
    _flatten(obj, "", arrays, meta)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, __meta__=np.frombuffer(
            json.dumps({"cls": type(obj).__name__, "meta": meta}).encode(),
            dtype=np.uint8,
        ), **arrays)
    os.replace(tmp, path)


def load(path: str):
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
        head = json.loads(z["__meta__"].tobytes().decode())
    cls = {"CompiledRules": rulec.CompiledRules,
           "PrefilterPlan": prefilter.PrefilterPlan}[head["cls"]]
    return _rebuild(cls, "", arrays, head["meta"])


class RuleCache:
    """One matcher start's view of the cache: `get(name, build)` loads
    the artefact `name` of this ruleset or builds and keeps it, under a
    `rules-compile` span; `seconds` and `source` are what the start spent
    on its rules and whether every artefact was loaded."""

    def __init__(self, entries, directory: Optional[str] = None):
        self.directory = (
            default_directory() if directory is None else directory
        )
        self.key = ruleset_key(entries) if self.directory else ""
        self.seconds = 0.0
        self.loaded = 0
        self.compiled = 0

    @property
    def source(self) -> str:
        return "loaded" if self.loaded and not self.compiled else "compiled"

    def get(self, name: str, build: Callable[[], object]):
        t0 = time.perf_counter()
        path = (os.path.join(self.directory, f"{self.key}-{name}.npz")
                if self.directory else "")
        # a start is no batch: the span opens a trace of its own
        with trace.span("rules-compile", trace_id=trace.new_trace(), parent=0,
                        args={"artefact": name}) as sp:
            out, source = None, "loaded"
            if path and os.path.exists(path):
                try:
                    out = load(path)
                    self.loaded += 1
                except Exception:  # noqa: BLE001 — a bad file is a miss
                    log.exception("compiled-rules cache: unreadable %s", path)
            if out is None:
                out, source = build(), "compiled"
                self.compiled += 1
                if path and out is not None:
                    try:
                        os.makedirs(self.directory, exist_ok=True)
                        save(path, out)
                    except OSError:
                        log.exception("compiled-rules cache: cannot write %s",
                                      path)
            sp.note("source", source)
        self.seconds += time.perf_counter() - t0
        return out
