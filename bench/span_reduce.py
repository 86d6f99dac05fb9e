"""From a profiler trace to what the program's own spans say: per span
name, time, self time, count, summed counts and the longest instance, and
where the device's idle time went.

The program records its spans (``src/repro/trace.py``: ``engine.*`` in the
serving engine, ``vta.*`` in the network loop, the pallas backend and the
kernel call) into the profiler's trace, on the device's clock.  Everything
here is clipped to the run's marked window (``bench.window``):

* time: each span's time inside the window, summed per name;
* self time: that less the time of the program spans directly inside it
  on the same thread;
* counts: each integer stat of a span (``real``, ``useful_macs``...)
  summed, weighted by the share of the span inside the window;
* idle: each stretch in which no operation ran on the device is charged to
  the deepest program span open on any host thread then (of equals, the
  one that started last), or to "no program span".

    python3 bench/span_reduce.py <file.xplane.pb[.gz]>

prints both tables.  A trace with no program span in the window (a program
that records none) gives no summary, and every reader of it no reading.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):          # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import stats  # noqa: E402
from bench.trace_reduce import (OPS_LINE, WINDOW_SPAN, _union,  # noqa: E402
                                device_planes, find_trace, host_spans, load)

PROGRAM = ("engine.", "vta.")          # the program's span names start so
NO_SPAN = "no program span"


@dataclasses.dataclass
class SpanStats:
    total_s: float = 0.0               # inside the window
    self_s: float = 0.0                # less the program spans inside it
    count: int = 0                     # instances that overlap the window
    counts: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    longest_s: float = 0.0             # the longest overlapping instance
    durations: List[float] = dataclasses.field(default_factory=list)
    # (whole durations of the instances that start in the window)


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    spans: Dict[str, SpanStats]
    idle: Optional[Dict[str, float]]   # None where there is no device

    def time_s(self, *names: str) -> float:
        return sum(self.spans[n].total_s for n in names if n in self.spans)

    def count(self, name: str, key: str) -> float:
        span = self.spans.get(name)
        return span.counts.get(key, 0.0) if span else 0.0

    def images(self) -> float:
        """Requests served in the window: ``real`` of ``engine.execute``."""
        return self.count("engine.execute", "real")

    def ms_per_image(self, *names: str) -> Optional[float]:
        images = self.images()
        return 1e3 * self.time_s(*names) / images if images > 0 else None


def _clipped(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def _thread_spans(profile) -> List[List[tuple]]:
    """Per host thread, its program spans: ``(start, end, name, stats)``
    in ns, sorted so that a span comes before the spans inside it."""
    threads = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                      ev.stats) for ev in line.events
                     if ev.name.startswith(PROGRAM)]
            if spans:
                spans.sort(key=lambda x: (x[0], -x[1]))
                threads.append(spans)
    return threads


def _nest(spans: List[tuple]):
    """Walk one thread's spans.  Returns ``closed``: ``(span, children)``
    for every span, its children the spans directly inside it; and
    ``pieces``: ``(t0, t1, depth, start, name)``, the stretches in which
    the innermost open span is the same one."""
    stack: List[list] = []             # [span, depth, children]
    pieces: List[tuple] = []
    closed: List[tuple] = []
    t = None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0][1] <= limit:
            span, depth, children = stack.pop()
            if span[1] > t:
                pieces.append((t, span[1], depth, span[0], span[2]))
            t = span[1]
            closed.append((span, children))
            if stack:
                stack[-1][2].append(span)

    for span in spans:
        close_until(span[0])
        if stack and span[0] > t:
            top = stack[-1]
            pieces.append((t, span[0], top[1], top[0][0], top[0][2]))
        stack.append([span, len(stack) + 1, []])
        t = span[0]
    close_until(float("inf"))
    return closed, pieces


def _owners(threads_pieces: List[List[tuple]]
            ) -> List[Tuple[float, float, str]]:
    """Disjoint ``(t0, t1, name)``: the deepest program span open on any
    thread, of equals the one that started last."""
    marks = []
    for i, piece in enumerate(p for pieces in threads_pieces
                              for p in pieces):
        marks.append((piece[0], 1, i, piece))
        marks.append((piece[1], 0, i, piece))
    marks.sort(key=lambda m: (m[0], m[1]))       # ends before starts
    active: Dict[int, tuple] = {}
    out, t = [], None
    for time, starts, i, piece in marks:
        if active and time > t:
            name = max(active.values(), key=lambda p: (p[2], p[3]))[4]
            if out and out[-1][1] == t and out[-1][2] == name:
                out[-1] = (out[-1][0], time, name)
            else:
                out.append((t, time, name))
        t = time
        if starts:
            active[i] = piece
        else:
            active.pop(i, None)
    return out


def _idle_by_owner(idle, owners) -> Dict[str, float]:
    """Seconds of each idle stretch under each owner; both lists sorted
    and disjoint."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in idle:
        while j < len(owners) and owners[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(owners) and owners[k][0] < e:
            inside = _clipped(owners[k][0], owners[k][1], s, e)
            out[owners[k][2]] += inside / 1e9
            covered += inside
            k += 1
        out[NO_SPAN] += (e - s - covered) / 1e9
    return out


def _device_idle(profile, lo, hi) -> Optional[List[List[Tuple[float, float]]]]:
    """Per device, the stretches of the window with no operation on it."""
    planes = device_planes(profile)
    if not planes:
        return None
    out = []
    for plane in planes:
        ops = [(max(ev.start_ns, lo),
                min(ev.start_ns + ev.duration_ns, hi))
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        idle, cursor = [], lo
        for s, e in _union([(s, e) for s, e in ops if e > s]) + [(hi, hi)]:
            if s > cursor:
                idle.append((cursor, s))
            cursor = max(cursor, e)
        out.append(idle)
    return out


def summarize(profile) -> Optional[SpanSummary]:
    """None where the trace has no marked window or no program span in it."""
    windows = host_spans(profile, WINDOW_SPAN)
    if not windows:
        return None
    lo, hi = windows[0]
    spans: Dict[str, SpanStats] = defaultdict(SpanStats)
    all_pieces = []
    for thread in _thread_spans(profile):
        closed, pieces = _nest(thread)
        all_pieces.append(pieces)
        for (s, e, name, stats_), children in closed:
            inside = _clipped(s, e, lo, hi)
            if inside <= 0 and not (s == e and lo <= s <= hi):
                continue
            share = inside / (e - s) if e > s else 1.0
            st = spans[name]
            st.count += 1
            st.total_s += inside / 1e9
            st.self_s += (inside - sum(_clipped(c[0], c[1], lo, hi)
                                       for c in children)) / 1e9
            st.longest_s = max(st.longest_s, (e - s) / 1e9)
            if lo <= s <= hi:
                st.durations.append((e - s) / 1e9)
            for key, value in stats_ or ():
                if isinstance(value, (int, float)):
                    st.counts[key] += share * value
    if not spans:
        return None
    idle = None
    per_device = _device_idle(profile, lo, hi)
    if per_device is not None:
        owners = _owners(all_pieces)
        idle = defaultdict(float)
        for stretches in per_device:
            for owner, sec in _idle_by_owner(stretches, owners).items():
                idle[owner] += sec / len(per_device)
        idle = dict(idle)
    return SpanSummary(window_s=(hi - lo) / 1e9, spans=dict(spans),
                       idle=idle)


@functools.lru_cache(maxsize=2)
def _summary_of(path: str, mtime_ns: int) -> Optional[SpanSummary]:
    return summarize(load(path))


def of_run() -> Optional[SpanSummary]:
    """The summary of the trace the run wrote with ``--trace 1``, read once
    per process; None where there is none."""
    from bench import harness
    found = find_trace(harness.TRACE_DIR)
    if found is None:
        return None
    return _summary_of(str(found), found.stat().st_mtime_ns)


def pct(part: float, whole: float) -> Optional[float]:
    """``part`` as a percentage of ``whole``; None where ``whole`` is 0."""
    return 100.0 * part / whole if whole > 0 else None


def p50_ms(durations: List[float]) -> Optional[float]:
    return 1e3 * stats.nearest_rank(sorted(durations), 50) \
        if durations else None


def print_tables(s: SpanSummary) -> None:
    images = s.images()
    print(f"window {s.window_s:.3f} s; {images:.1f} images "
          f"(engine.execute real)")
    print(f"{'span':<22}{'count':>8}{'total ms':>12}{'self ms':>12}"
          f"{'self ms/img':>12}{'longest ms':>12}  counts")
    for name, st in sorted(s.spans.items(), key=lambda kv: -kv[1].self_s):
        per = 1e3 * st.self_s / images if images > 0 else float("nan")
        counts = ", ".join(f"{k}={v:.6g}" for k, v in st.counts.items())
        print(f"{name:<22}{st.count:>8}{1e3 * st.total_s:>12.3f}"
              f"{1e3 * st.self_s:>12.3f}{per:>12.4f}"
              f"{1e3 * st.longest_s:>12.3f}  {counts}")
    if s.idle is None:
        print("no device in the trace: no idle time to place")
        return
    total = sum(s.idle.values())
    print(f"device idle {total:.6f} s of {s.window_s:.3f}, by the deepest "
          f"program span open:")
    for name, sec in sorted(s.idle.items(), key=lambda kv: -kv[1]):
        print(f"  {name:<22}{sec:>12.6f} s {pct(sec, total):>9.4f}%")


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit("usage: python3 bench/span_reduce.py <file.xplane.pb[.gz]>")
    summary = summarize(load(sys.argv[1]))
    if summary is None:
        sys.exit("no program span inside a marked window in this trace")
    print_tables(summary)
