"""Device traces: capture one, normalise it, reduce it to numbers.

A traced run records the profiler's trace around its traced window
(:func:`capture`).  The trace is read with ``jax.profiler.ProfileData``
into plain :class:`Event` tuples (:func:`load_xplane`), and
:class:`Reduction` turns those into the numbers the per-layer metrics and
the ``breakdown`` use:

- device busy time: the union of the op intervals of each chip, clipped
  to the traced window, averaged over the chips;
- kernel time: the summed device durations of the events of one kernel,
  found by its stable name (every Pallas call of the program names
  itself, and the op carries that name);
- exposed collective time: per chip, the time in which a collective op
  runs and no other op does;
- idle gaps, each named by the innermost host span of the benchmark
  (``bench.*``, recorded with ``jax.profiler.TraceAnnotation``) that was
  open at the gap's middle.

The traced window is the host span ``bench.window``.
"""
from __future__ import annotations

import gzip
import json
import pathlib
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|collective-broadcast|"
                        r"\bsend\b|\brecv\b)")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def capture(fn) -> List[Event]:
    """Run ``fn()`` under the profiler; return the trace's events.  The
    trace is written to a temporary directory (under ``$TMPDIR``) and
    deleted once read."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        paths = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {tmp}")
        return load_xplane(paths[-1])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def short_name(name: str) -> str:
    """A TPU op event is named by its HLO text, ``%edm_update.1 = (...)
    custom-call(...)``: keep the instruction's name."""
    m = re.match(r"%(\S+) = ", name)
    return m.group(1) if m else name


def load_xplane(path) -> List[Event]:
    """Device op events of every TPU plane, and the benchmark's host
    spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: List[Event] = []
    for plane in pd.planes:
        device = DEVICE_PLANE.match(plane.name)
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if host and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                name = short_name(ev.name) if device else ev.name
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def save_events(events: Iterable[Event], path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def read_events(path) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(p, l, short_name(n), s, d)
                for p, l, n, s, d, *_ in json.load(f)]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def matches(ev: Event, kernel: str) -> bool:
    """Whether ``ev`` is a call of the kernel named ``kernel``: the op is
    named after the kernel, as ``edm_update`` or an instance of it,
    ``edm_update.1`` (not ``edm_update_ef_int8``)."""
    return ev.name == kernel or (ev.name.startswith(kernel + ".")
                                 and ev.name[len(kernel) + 1:].isdigit())


def is_collective(ev: Event) -> bool:
    return bool(COLLECTIVE.search(ev.name))


class Reduction:
    """Numbers from one traced window."""

    def __init__(self, events: List[Event]):
        self.events = events
        spans = [e for e in events if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        w = spans[0]
        self.lo, self.hi = w.start_ns, w.end_ns
        self.device_ops: Dict[str, List[Event]] = {}
        for e in events:
            if DEVICE_PLANE.match(e.plane) and e.end_ns > self.lo \
                    and e.start_ns < self.hi:
                self.device_ops.setdefault(e.plane, []).append(e)
        self.host_spans = [e for e in events
                           if e.plane.startswith("/host:")
                           and e.name != WINDOW_SPAN]

    @property
    def n_devices(self) -> int:
        return len(self.device_ops)

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _busy(self, ops) -> List[Tuple[float, float]]:
        return clip(union((e.start_ns, e.end_ns) for e in ops),
                    self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        if not self.device_ops:
            return 0.0
        return sum(length(self._busy(ops)) for ops in
                   self.device_ops.values()) * 1e-9 / self.n_devices

    def kernel(self, name: str) -> Tuple[int, float]:
        """(calls, summed device seconds) of one kernel over all chips."""
        n, t = 0, 0.0
        for ops in self.device_ops.values():
            for e in ops:
                if matches(e, name):
                    n += 1
                    t += e.dur_ns
        return n, t * 1e-9

    def exposed_collective_s(self) -> Optional[float]:
        """Seconds per chip in which a collective ran and nothing else
        did, averaged over the chips; None without collectives."""
        total, seen = 0.0, False
        for ops in self.device_ops.values():
            coll = [e for e in ops if is_collective(e)]
            seen = seen or bool(coll)
            busy_c = self._busy(coll)
            busy_o = self._busy([e for e in ops if not is_collective(e)])
            total += length(subtract(busy_c, busy_o))
        if not seen:
            return None
        return total * 1e-9 / self.n_devices

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Idle intervals of every chip inside the window, longest first,
        each named by the innermost benchmark span open at its middle."""
        gaps = []
        for ops in self.device_ops.values():
            busy = self._busy(ops)
            for s, e in subtract([(self.lo, self.hi)], busy):
                mid = (s + e) / 2
                open_spans = [h for h in self.host_spans
                              if h.start_ns <= mid < h.end_ns]
                label = (min(open_spans, key=lambda h: h.dur_ns).name
                         if open_spans else "no benchmark span")
                gaps.append((label, (e - s) * 1e-9))
        return sorted(gaps, key=lambda g: -g[1])

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device ops by summed seconds per chip, largest first; ops are
        grouped by name with trailing ``.<number>`` instances merged."""
        acc: Dict[str, float] = {}
        for ops in self.device_ops.values():
            for e in ops:
                key = re.sub(r"\.\d+$", "", e.name)
                acc[key] = acc.get(key, 0.0) + e.dur_ns
        per_chip = max(self.n_devices, 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9 / per_chip) for k, v in top]

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops(10)],
                "idle_gaps": [list(x) for x in self.idle_gaps()[:10]]}
