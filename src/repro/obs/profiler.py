"""Cycle-phase profiler (ISSUE 10 tentpole, pillar 2).

``perf_counter`` spans around the hot-path phases of one simulated run —
timeline drain, arrival ingest, wave selection (scoring + select kernel),
bind commit, reschedule (including the shadow-capacity plan), autoscaler
step, scale-in, completion scheduling/commit, metrics sampling — each
aggregated into a per-phase histogram (count / total / min / max + log2
duration buckets) plus a bounded span ring for timeline inspection.

``span(name)`` times a nested phase instead: a context manager that
records the enclosing ``span`` as its parent (the ring's parent column)
and enters ``jax.profiler.TraceAnnotation(name)``, so a profiler trace
shows the span on its host plane, on the clock of the device operations.
``self_times`` reads one tree of spans back as self time per name.

``chrome_trace`` renders the span ring as Chrome-trace/Perfetto JSON
(``chrome://tracing`` / https://ui.perfetto.dev): one complete-event
(``"ph": "X"``) per span, timestamps in microseconds relative to the first
recorded span, with the simulated time attached as an arg so wall-clock
hotspots can be correlated with simulation phases.

The profiler never touches simulation state — it reads the monotonic
clock and writes its own arrays — so profiling cannot perturb results.
"""
from __future__ import annotations

from contextlib import contextmanager
from time import perf_counter
from typing import Dict, Iterator, List, Optional

import numpy as np

#: log2 duration buckets: bucket ``b`` holds spans with duration in
#: ``[2**(b-1), 2**b)`` microseconds (bucket 0: < 1 µs; bucket 31: the
#: catch-all for anything ≥ ~17.9 min).
N_BUCKETS = 32


class PhaseProfiler:
    """Named-phase span aggregation + a bounded raw-span ring.

    Usage at an instrumented site::

        t0 = prof.start()
        ... the phase body ...
        prof.stop("wave_select", t0, sim_now)

    or, for a phase that nests others::

        with prof.span("lanes.call"):
            with prof.span("lanes.stack"):
                ...

    ``stop`` is O(1): a dict lookup, four scalar updates, one histogram
    increment, and a ring write.  Phases are interned on first use.

    Every span has an id, its position in the sequence of spans (the
    ring's slot is the id modulo ``max_spans``).  A ``span`` takes its id
    when it opens, so a parent's id is known to the spans inside it; the
    parent column holds the id of the innermost ``span`` open when a span
    opened (``stop`` spans: when they stopped), -1 for none.  The open
    spans are one stack per profiler: one thread at a time.
    """

    __slots__ = ("max_spans", "n_spans_seen", "_agg", "_names", "_open",
                 "sp_name", "sp_t0", "sp_dur", "sp_sim", "sp_parent")

    def __init__(self, max_spans: int = 1 << 16):
        self.max_spans = max_spans
        self.n_spans_seen = 0
        # name -> [count, total_s, min_s, max_s, hist(np.int64[32]), idx]
        self._agg: Dict[str, list] = {}
        self._names: List[str] = []
        self._open: List[int] = []           # ids of the open spans
        self.sp_name = np.zeros(max_spans, np.int16)
        self.sp_t0 = np.zeros(max_spans, np.float64)
        self.sp_dur = np.zeros(max_spans, np.float64)
        self.sp_sim = np.zeros(max_spans, np.float64)
        self.sp_parent = np.full(max_spans, -1, np.int64)

    @staticmethod
    def start() -> float:
        return perf_counter()

    def stop(self, name: str, t0: float, sim_now: float = 0.0,
             sid: int = -1) -> None:
        """Record the span ``name`` begun at ``t0``.  ``sid`` is an id
        reserved when the span opened (``span`` does); -1 takes the next."""
        dur = perf_counter() - t0
        if sid < 0:
            sid = self.n_spans_seen
            self.n_spans_seen += 1
        agg = self._agg.get(name)
        if agg is None:
            agg = self._agg[name] = [0, 0.0, np.inf, 0.0,
                                     np.zeros(N_BUCKETS, np.int64),
                                     len(self._names)]
            self._names.append(name)
        agg[0] += 1
        agg[1] += dur
        if dur < agg[2]:
            agg[2] = dur
        if dur > agg[3]:
            agg[3] = dur
        b = int(dur * 1e6).bit_length()
        agg[4][b if b < N_BUCKETS else N_BUCKETS - 1] += 1
        if self.n_spans_seen - sid > self.max_spans:
            return                  # its slot went to a newer span
        i = sid % self.max_spans
        self.sp_name[i] = agg[5]
        self.sp_t0[i] = t0
        self.sp_dur[i] = dur
        self.sp_sim[i] = sim_now
        self.sp_parent[i] = self._open[-1] if self._open else -1

    @contextmanager
    def span(self, name: str) -> Iterator[int]:
        """Time the ``with`` body as span ``name``; yields the span's id.
        The body also runs inside ``jax.profiler.TraceAnnotation(name)``."""
        from jax.profiler import TraceAnnotation
        sid = self.n_spans_seen
        self.n_spans_seen += 1
        self._open.append(sid)
        t0 = perf_counter()
        try:
            with TraceAnnotation(name):
                yield sid
        finally:
            # Once this span is off the stack, its top is the span's parent.
            self._open.pop()
            self.stop(name, t0, 0.0, sid)

    # -- reading -------------------------------------------------------------
    def phases(self) -> Dict[str, dict]:
        """Aggregates per phase, in first-use order."""
        return {name: {"count": agg[0], "total_s": agg[1],
                       "min_s": (0.0 if agg[0] == 0 else agg[2]),
                       "max_s": agg[3], "hist": agg[4].copy()}
                for name, agg in self._agg.items()}

    def _spans_unrolled(self):
        cols = (self.sp_name, self.sp_t0, self.sp_dur, self.sp_sim,
                self.sp_parent)
        n = min(self.n_spans_seen, self.max_spans)
        if self.n_spans_seen <= self.max_spans:
            return tuple(c[:n].copy() for c in cols)
        head = self.n_spans_seen % self.max_spans
        order = np.r_[head:self.max_spans, 0:head]
        return tuple(c[order] for c in cols)

    def self_times(self, sid: int) -> Dict[str, float]:
        """Self time per span name in the tree of spans under span ``sid``,
        the root included: each span's duration less its children's,
        summed over the spans of one name.  The values sum to the root's
        duration.  Read it once the root has closed, while the ring still
        holds the tree (empty if it no longer holds the root)."""
        first = max(self.n_spans_seen - self.max_spans, 0)
        if not first <= sid < self.n_spans_seen:
            return {}
        ids = np.arange(sid, self.n_spans_seen)
        slots = ids % self.max_spans
        parent = self.sp_parent[slots]
        dur = self.sp_dur[slots]
        # Children open after their parent, so one pass in id order finds
        # the whole tree.
        inside = np.zeros(ids.size, bool)
        inside[0] = True
        own = dur.copy()
        for j in range(1, ids.size):
            p = parent[j] - sid
            if 0 <= p < j and inside[p]:
                inside[j] = True
                own[p] -= dur[j]
        out: Dict[str, float] = {}
        for j in np.nonzero(inside)[0]:
            name = self._names[int(self.sp_name[slots[j]])]
            out[name] = out.get(name, 0.0) + float(own[j])
        return out

    def to_payload(self) -> Dict:
        names = list(self._names)
        count = np.asarray([self._agg[n][0] for n in names], np.int64)
        total = np.asarray([self._agg[n][1] for n in names], np.float64)
        mn = np.asarray([0.0 if self._agg[n][0] == 0 else self._agg[n][2]
                         for n in names], np.float64)
        mx = np.asarray([self._agg[n][3] for n in names], np.float64)
        hist = (np.stack([self._agg[n][4] for n in names])
                if names else np.zeros((0, N_BUCKETS), np.int64))
        sp_name, sp_t0, sp_dur, sp_sim, sp_parent = self._spans_unrolled()
        return {"names": names, "n_spans_seen": self.n_spans_seen,
                "count": count, "total_s": total, "min_s": mn, "max_s": mx,
                "hist": hist,
                "spans": {"name": sp_name, "t0": sp_t0, "dur_s": sp_dur,
                          "sim_s": sp_sim, "parent": sp_parent}}


def chrome_trace(profile: Dict, pid: int = 0, tid: int = 0) -> List[dict]:
    """Chrome-trace/Perfetto JSON event list from a profiler payload
    (live ``PhaseProfiler.to_payload()`` or the ``"profile"`` entry of a
    loaded obs bundle)."""
    names = profile["names"]
    spans = profile["spans"]
    sp_name = np.asarray(spans["name"])
    sp_t0 = np.asarray(spans["t0"], np.float64)
    sp_dur = np.asarray(spans["dur_s"], np.float64)
    sp_sim = np.asarray(spans["sim_s"], np.float64)
    if sp_t0.size == 0:
        return []
    epoch = float(sp_t0.min())
    return [{"name": names[int(sp_name[i])], "ph": "X", "pid": pid,
             "tid": tid, "ts": (float(sp_t0[i]) - epoch) * 1e6,
             "dur": float(sp_dur[i]) * 1e6,
             "args": {"sim_s": float(sp_sim[i])}}
            for i in range(sp_t0.size)]
