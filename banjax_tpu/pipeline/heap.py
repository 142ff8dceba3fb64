"""The cyclic garbage collector under the pipeline: what it costs, and
the start-up heap out of its reach.

A collection stops every thread of the process.  A young one walks a
few hundred objects; a full (generation-2) one walks the whole heap —
JAX, the compiled rules, the device programs — and CPython runs one
whenever the survivors of the young collections reach a quarter of that
heap.  `HeapKeeper` is the scheduler's hold on both ends:

  * one `gc.callbacks` entry times every collection and counts it by
    generation (`banjax_gc_*` on /metrics, tracing on or off), and puts
    each full pass over 50 ms on the trace ring as an instant event of
    the thread it ran on;
  * `gc.freeze()` moves what start-up allocated to the permanent
    generation, so a full pass walks what was allocated since.  Nothing
    is switched off: the automatic collector runs as before, over less.

When to freeze is read from what the scheduler sees at each batch: the
matcher it was handed (the first one: freeze; another one, a hot reload:
unfreeze, so that the old one's cycles reach the collector) and the
matcher's count of device programs built (`compile_events()`; programs
are built or loaded lazily, on a shape's first batch): once that count
has stood still for `_SETTLE_S` after it moved, or after a reload, the
heap is collected and frozen again.  Stopping unfreezes: a process that
starts many pipelines (the tests) gets its collector back.

Frozen objects still die by reference count; only a cycle among them
waits for the next unfreeze.  `gc.freeze()` is the whole process's, so
of two pipelines in one process the one that stops first thaws the
other's heap too — slower full passes for it, nothing else."""

from __future__ import annotations

import gc
import time

from banjax_tpu.obs import trace

_GENERATIONS = 3
# a full pass longer than this is an event of its own on the trace ring
_SLOW_PASS_S = 0.05
# the build count has to stand still this long before the heap is
# frozen again: shorter than any warm-up's own wait for "nothing built"
_SETTLE_S = 2.0


class HeapKeeper:
    def __init__(self):
        # by generation, over the time the callback was installed
        self.collections = [0] * _GENERATIONS
        self.pause_s = [0.0] * _GENERATIONS
        self.collected = [0] * _GENERATIONS
        self._t0 = 0.0
        self._installed = False
        self._frozen = False
        self._matcher = None
        self._builds = 0
        self._moved_at = None  # monotonic; None = frozen since it moved

    # ---- lifecycle (the scheduler's start and stop) ----

    def start(self) -> None:
        if not self._installed:
            gc.callbacks.append(self._on_gc)
            self._installed = True

    def stop(self) -> None:
        if self._installed:
            gc.callbacks.remove(self._on_gc)
            self._installed = False
        self._thaw()
        self._matcher = None
        self._moved_at = None

    # ---- the collector's callback: any thread, inside a collection ----

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections never nest and run under the interpreter lock, so
        # one stamp and unlocked sums are enough
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        g = info["generation"]
        self.collections[g] += 1
        self.pause_s[g] += dt
        self.collected[g] += info["collected"]
        if g == _GENERATIONS - 1 and dt > _SLOW_PASS_S:
            # the ring stamps the event with the thread it is put from,
            # which is the one the pass ran on
            trace.instant("gc-pass", {
                "ms": round(dt * 1e3, 3), "collected": info["collected"],
            })

    # ---- the freeze (the encode thread, once a batch) ----

    def observe(self, matcher, builds: int) -> None:
        """The matcher a batch was handed and its count of programs
        built so far."""
        now = time.monotonic()
        if matcher is not self._matcher:
            reloaded = self._matcher is not None
            self._matcher, self._builds = matcher, builds
            self._moved_at = now
            if reloaded:
                # batches in flight still hold the old matcher: it is
                # collected, and the heap frozen, once things stand still
                self._thaw()
            else:
                self._freeze()
        elif builds != self._builds:
            self._builds, self._moved_at = builds, now
        elif (self._moved_at is not None
              and now - self._moved_at >= _SETTLE_S):
            self._moved_at = None
            self._freeze()

    def _freeze(self) -> None:
        gc.collect()
        gc.freeze()
        self._frozen = True

    def _thaw(self) -> None:
        if self._frozen:
            gc.unfreeze()
            self._frozen = False

    # ---- /metrics ----

    def snapshot(self) -> dict:
        return {
            "collections": list(self.collections),
            "pause_s": list(self.pause_s),
            "collected": list(self.collected),
            "frozen": gc.get_freeze_count(),
        }
