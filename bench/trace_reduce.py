"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time
and where the device sat idle.

The run marks its measured window with a host span (``bench.window``)
and each network serve with another (``bench.serve``); the device's
operations are the events of each TPU plane's ``XLA Ops`` line, and a
kernel is matched by its jitted function's module on the ``XLA Modules``
line (``jit_vta_gemm``...).  ``python3 bench/trace_reduce.py --dump
<file>`` prints a trace's planes and lines, for a look by hand.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SERVE_SPAN = "bench.serve"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class TraceSummary:
    window_s: float                     # the marked window, trace clock
    busy_s: float                       # union of device ops, mean over chips
    kernel_s: Dict[str, float]          # kernel -> summed module time
    device_ops: List[Tuple[str, float]]  # op name -> summed time, largest first
    idle_gaps: List[Tuple[str, float]]  # host activity -> idle time in it


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _op_family(name: str) -> str:
    """An op's name without its instance number: the TPU names an op by
    its HLO text (``%vta_gemm.1 = s8[...] custom-call(...)``), which
    gives ``vta_gemm``; ``fusion.12`` and ``fusion.3`` are ``fusion``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def device_planes(profile):
    return [p for p in profile.planes if p.name.startswith("/device:TPU:")
            and not re.search(r"SparseCore|Core \d", p.name)]


def host_spans(profile, name: str) -> List[Tuple[float, float]]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(s, e) for n, s, e in _events(line) if n == name]
    return out


def reduce_profile(profile, kernels=("vta_gemm",)) -> Optional[TraceSummary]:
    """None where the trace holds no marked window or no device plane."""
    windows = host_spans(profile, WINDOW_SPAN)
    planes = device_planes(profile)
    if not windows or not planes:
        return None
    lo, hi = windows[0]
    serves = _union(host_spans(profile, SERVE_SPAN))
    busy_total = 0.0
    kernel_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for plane in planes:
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for n, s, e in _events(line):
                    s, e = _clip(s, e, lo, hi)
                    if e > s:
                        ops.append((s, e))
                        op_s[_op_family(n)] += (e - s) / 1e9
            elif line.name == MODULES_LINE:
                for n, s, e in _events(line):
                    s, e = _clip(s, e, lo, hi)
                    for k in kernels:
                        if e > s and k in n:
                            kernel_s[k] += (e - s) / 1e9
        busy = _union(ops)
        busy_total += sum(e - s for s, e in busy) / 1e9
        cursor = lo
        for s, e in busy + [(hi, hi)]:
            if s > cursor:
                idle[_host_activity((cursor + s) / 2, serves)] += \
                    (s - cursor) / 1e9 / len(planes)
            cursor = max(cursor, e)
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / len(planes),
        kernel_s=dict(kernel_s),
        device_ops=sorted(op_s.items(), key=lambda kv: -kv[1]),
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1]))


def _host_activity(t: float, serves: List[Tuple[float, float]]) -> str:
    """What the host was doing at ``t``; ``serves`` is sorted and
    disjoint (a union)."""
    i = bisect.bisect_right(serves, (t, float("inf"))) - 1
    if i >= 0 and t <= serves[i][1]:
        return "host inside a network serve (staging, epilogue, copies)"
    return "host outside any serve (queue, batch forming, load generator)"


def load(path) -> object:
    """A trace file, ``.xplane.pb`` or gzipped ``.xplane.pb.gz``."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        return ProfileData.from_serialized_xspace(
            gzip.decompress(Path(path).read_bytes()))
    return ProfileData.from_file(str(path))


def find_trace(log_dir: Path) -> Optional[Path]:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def reduce_trace(path, kernels=("vta_gemm",)) -> Optional[TraceSummary]:
    return reduce_profile(load(path), kernels)


def dump(path, per_line: int = 5) -> None:
    """Print each plane, its lines, event counts and a few events."""
    profile = load(path)
    for plane in profile.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: v for k, v in ev.stats} if ev.stats else {}
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} {stats}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        sys.exit("usage: python3 bench/trace_reduce.py --dump <file.xplane.pb>")
