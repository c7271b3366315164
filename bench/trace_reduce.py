"""From a profiler trace to the numbers the device metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``.  Everything else works on that form,
which is also what the test fixture holds.

* device busy time: the union of the intervals of the device's operations
  (the ``XLA Ops`` line of each chip's ``/device:TPU:<n>`` plane), averaged
  over the chips.  A TPU trace also holds device planes that are not chips
  (``/device:CUSTOM:Megascale Trace``, with no operations); counted as
  chips, they would halve the busy time;
* a program's device time and call count: the events of the ``XLA
  Modules`` line whose name is the program's jit name (``jit_<fn>``,
  followed by an id in parentheses);
* the breakdown: the device operations that took most time, and the
  longest idle gaps on the device, each named by the host event that
  covers most of it.
"""
from __future__ import annotations

import pathlib
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path) -> dict:
    """Read an ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(str(path))
    return {"planes": [
        {"name": plane.name,
         "lines": [{"name": line.name,
                    "events": [[ev.name, int(ev.start_ns),
                                int(ev.duration_ns)]
                               for ev in line.events]}
                   for line in plane.lines]}
        for plane in pd.planes]}


CHIP_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


def device_planes(trace: dict) -> List[dict]:
    """The chips' planes: ``/device:TPU:0``, not ``/device:CUSTOM:...``."""
    return [p for p in trace["planes"] if CHIP_PLANE.match(p["name"])]


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _busy(plane: dict) -> List[Tuple[int, int]]:
    return union([(s, s + d) for _, s, d in _line(plane, OPS_LINE)])


def busy_seconds(trace: dict) -> Optional[float]:
    """Seconds in which an operation ran, averaged over device planes;
    None when the trace holds no device plane."""
    planes = device_planes(trace)
    if not planes:
        return None
    total = sum(sum(e - s for s, e in _busy(p)) for p in planes)
    return total / len(planes) * 1e-9


def module_name(event_name: str) -> str:
    """``jit_bucket_eval(123)`` -> ``jit_bucket_eval``."""
    return re.sub(r"\(.*$", "", event_name).strip()


def program_times(trace: dict) -> Dict[str, Tuple[float, int]]:
    """``{module: (device seconds, executions)}`` over device planes."""
    out: Dict[str, Tuple[float, int]] = {}
    for p in device_planes(trace):
        for name, _, dur in _line(p, MODULES_LINE):
            key = module_name(name)
            t, n = out.get(key, (0.0, 0))
            out[key] = (t + dur * 1e-9, n + 1)
    return out


def top_ops(trace: dict, n: int = 10) -> List[list]:
    """The ``n`` device operations with the most time, by HLO text cut to
    its first 120 characters (the op, its shape and operands)."""
    tot: Dict[str, float] = {}
    for p in device_planes(trace):
        for name, _, dur in _line(p, OPS_LINE):
            name = name[:120]
            tot[name] = tot.get(name, 0.0) + dur * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> List[list]:
    """The ``n`` longest gaps between device operations, each named by the
    host event that overlaps it most (``host:unattributed`` if none)."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = _busy(planes[0])
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1],
                    busy[i + 1][0]) for i in range(len(busy) - 1)),
                  reverse=True)[:n]
    host = [ev for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for ev in line["events"]]
    out = []
    for dur, s, e in gaps:
        best, cover = "host:unattributed", 0
        for name, hs, hd in host:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > cover:
                best, cover = name, ov
        out.append([best, dur * 1e-9])
    return out
