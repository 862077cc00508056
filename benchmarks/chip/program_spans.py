"""The program's round phases in one profiler trace of the window.

The engine opens a ``fed.<phase>`` annotation around each phase of a round
(``repro.obs.phases``). ``reduce_phases`` reads the ``.xplane.pb`` once
(cached by path) and returns, over the window of ``bench.round`` spans:

  phases_host_s  per phase, the union of its host intervals;
  device_s       per phase, the device time of the module runs whose host
                 dispatch lies innermost under that phase, averaged over the
                 cell's chips ("" for runs dispatched under no phase);
  idle_s         per phase, the idle gaps of device 0 whose midpoint lies
                 innermost under that phase, on the host's clock;
  offset_ms      the device clock's offset against the host's, bounded from
                 both sides by the linked runs (below), over the whole
                 window and its first and last tenth;
  linked_share   the share of the window's module runs linked to their
                 dispatch; runs not linked take the dispatch of the run
                 before them on the device's queue (enqueue order).

A module run is linked to the host call that launched it by the profiler's
flow ids, following

  PJRT_LoadedExecutable_Execute linkage  (the Python thread, ``_p``)
  -> PJRT_LoadedExecutable_Execute       (``_c``), which holds
     tpu::System::Execute                (``_p``)
  -> tpu::System::Execute=>IssueSequencedEvent (``_c``), which holds
     DoEnqueueProgram                    (``_p``)
  -> the module run on the device        (``_c``).

Each linked run starts after its ``DoEnqueueProgram`` and ends before its
``CompleteCallbacks`` (``_c`` equal to the run's), so the offset to add to
device times lies between the largest (enqueue - device start) and the
smallest (callback - device end); device times are corrected by the lower
bound before they are clipped to the window or matched with host spans.

    python benchmarks/chip/program_spans.py <trace.xplane.pb> --rounds <n> [--chips <c>]

prints the reduction as JSON.
"""

from __future__ import annotations

import argparse
import bisect
import functools
import json
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from trace_reduce import (DEVICE_PLANE, MODULE_ID, MODULES_LINE, OPS_LINE, clip, find_xplane,
                          gaps, union)

ROUND_SPAN = "bench.round"
PHASE_PREFIX = "fed."
STEP_SPAN = "fed.round"  # the step annotation around a whole round, not a phase

LINKAGE = "PJRT_LoadedExecutable_Execute linkage"
EXECUTE = "PJRT_LoadedExecutable_Execute"
SYSTEM_EXECUTE = "tpu::System::Execute"
SEQUENCED = "tpu::System::Execute=>IssueSequencedEvent"
ENQUEUE = "DoEnqueueProgram"
CALLBACK = "CompleteCallbacks"
LINKED = {LINKAGE, EXECUTE, SYSTEM_EXECUTE, SEQUENCED, ENQUEUE, CALLBACK}

Span = Tuple[int, int, str]


def _flow(event, key: str) -> Optional[int]:
    for k, v in event.stats:
        if k == key:
            return int(v)
    return None


class _Line:
    """One host line's events of a kind, sorted by start, for containment."""

    def __init__(self) -> None:
        self.events: List[Tuple[int, int, Optional[int]]] = []  # start, end, _c
        self.starts: List[int] = []

    def seal(self) -> None:
        self.events.sort()
        self.starts = [s for s, _, _ in self.events]

    def holding(self, t: int) -> Optional[Tuple[int, int, Optional[int]]]:
        j = bisect.bisect_right(self.starts, t) - 1
        if j >= 0 and self.events[j][1] >= t:
            return self.events[j]
        return None


@functools.lru_cache(maxsize=1)
def load(path: str) -> Dict[str, Any]:
    """The trace's phase spans, device module runs with their links, and
    device ops, read once per path."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    phase_lines: Dict[Tuple[str, str], List[Span]] = defaultdict(list)
    rounds: List[Tuple[int, int]] = []
    linkage: Dict[int, int] = {}            # _p -> start (Python thread)
    executes: Dict[Tuple[str, str], _Line] = defaultdict(_Line)
    system: Dict[int, Tuple[Tuple[str, str], int]] = {}  # _p -> (line, start)
    sequenced: Dict[Tuple[str, str], _Line] = defaultdict(_Line)
    enqueues: Dict[int, Tuple[Tuple[str, str], int]] = {}  # _p -> (line, start)
    callbacks: Dict[int, int] = {}          # _c -> start
    devices: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            runs, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    runs = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                             MODULE_ID.sub("", e.name), _flow(e, "_c"))
                            for e in line.events]
                elif line.name == OPS_LINE:
                    ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                           for e in line.events]
            devices[int(m.group(1))] = {"runs": sorted(runs), "ops": ops}
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            key = (plane.name, line.name)
            for e in line.events:
                name = e.name
                if name.startswith(PHASE_PREFIX):
                    if name != STEP_SPAN:
                        s = int(e.start_ns)
                        phase_lines[key].append((s, s + int(e.duration_ns),
                                                 name[len(PHASE_PREFIX):]))
                    continue
                if name == ROUND_SPAN:
                    s = int(e.start_ns)
                    rounds.append((s, s + int(e.duration_ns)))
                    continue
                if name not in LINKED:
                    continue
                s, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if name == LINKAGE:
                    p = _flow(e, "_p")
                    if p is not None:
                        linkage[p] = s
                elif name == EXECUTE:
                    executes[key].events.append((s, end, _flow(e, "_c")))
                elif name == SYSTEM_EXECUTE:
                    p = _flow(e, "_p")
                    if p is not None:
                        system[p] = (key, s)
                elif name == SEQUENCED:
                    sequenced[key].events.append((s, end, _flow(e, "_c")))
                elif name == ENQUEUE:  # the first enqueue of a run bounds its start
                    p = _flow(e, "_p")
                    if p is not None and (p not in enqueues or s < enqueues[p][1]):
                        enqueues[p] = (key, s)
                elif name == CALLBACK:  # the first callback bounds its end
                    c = _flow(e, "_c")
                    if c is not None and s < callbacks.get(c, s + 1):
                        callbacks[c] = s
    for lines in (executes, sequenced):
        for ln in lines.values():
            ln.seal()
    # The program's phases all run on one thread: the line holding most of them.
    spans = max(phase_lines.values(), key=len) if phase_lines else []

    def dispatch(flow: Optional[int]) -> Optional[int]:
        """Host time of the Python call that launched the run with this flow."""
        enq = enqueues.get(flow) if flow is not None else None
        if enq is None:
            return None
        seq = sequenced[enq[0]].holding(enq[1])
        sysex = system.get(seq[2]) if seq and seq[2] is not None else None
        if sysex is None:
            return None
        ex = executes[sysex[0]].holding(sysex[1])
        return linkage.get(ex[2]) if ex and ex[2] is not None else None

    for dev in devices.values():
        dev["runs"] = [(s, e, name, dispatch(flow),
                        enqueues[flow][1] if flow in enqueues else None,
                        callbacks.get(flow))
                       for s, e, name, flow in dev["runs"]]
    return {"spans": sorted(spans), "rounds": sorted(rounds), "devices": devices}


def innermost(spans: List[Span]) -> Tuple[List[int], List[Tuple[int, int, str]]]:
    """Properly nested spans flattened into disjoint segments, each named by
    the innermost span over it; returns (segment starts, segments)."""
    segs: List[Tuple[int, int, str]] = []
    stack: List[Span] = []
    cursor = 0

    def close_until(t: int) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            s, e, name = stack.pop()
            if e > cursor:
                segs.append((cursor, e, name))
            cursor = max(cursor, e)

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack and s > cursor:
            segs.append((cursor, s, stack[-1][2]))
        stack.append((s, e, name))
        cursor = s
    close_until(float("inf"))
    return [s for s, _, _ in segs], segs


def phase_at(index, t: int) -> str:
    starts, segs = index
    j = bisect.bisect_right(starts, t) - 1
    if j >= 0 and segs[j][1] > t:
        return segs[j][2]
    return ""


def offset_bounds(runs, lo: int, hi: int) -> Dict[str, List[Optional[float]]]:
    """[lower, upper] bounds of the device-to-host offset in ms, from the
    linked runs whose enqueue lies in each part of the window."""
    tenth = (hi - lo) // 10
    parts = {"window": (lo, hi), "first_tenth": (lo, lo + tenth),
             "last_tenth": (hi - tenth, hi)}
    out = {}
    for part, (a, b) in parts.items():
        lows = [enq - s for s, e, _, _, enq, cb in runs if enq is not None and a <= enq <= b]
        ups = [cb - e for s, e, _, _, enq, cb in runs
               if enq is not None and cb is not None and a <= enq <= b]
        out[part] = [max(lows) / 1e6 if lows else None, min(ups) / 1e6 if ups else None]
    return out


@functools.lru_cache(maxsize=1)
def reduce_phases(path: str, n_rounds: int, chips: int) -> Dict[str, Any]:
    """Per-phase host, device and idle time over the window, and the links
    and clock offset they rest on. A trace of a program without the
    ``fed.*`` annotations has no phases: everything is charged to ""."""
    raw = load(path)
    rounds = raw["rounds"]
    if len(rounds) < n_rounds or n_rounds < 1:
        raise ValueError(f"trace holds {len(rounds)} {ROUND_SPAN!r} spans, "
                         f"the window {n_rounds}")
    lo, hi = rounds[0][0], rounds[n_rounds - 1][1]
    devs = sorted(raw["devices"])[:chips]
    if len(devs) < chips:
        raise ValueError(f"trace holds {len(devs)} TPU planes, the cell {chips}")
    index = innermost(raw["spans"])

    host: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    for s, e, name in raw["spans"]:
        if (c := clip((s, e), lo, hi)):
            host[name].append(c)
    phases_host_s = {n: sum(e - s for s, e in union(v)) / 1e9 for n, v in host.items()}

    device_s: Dict[str, float] = defaultdict(float)
    modules: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    idle_s: Dict[str, float] = defaultdict(float)
    n_runs = n_linked = 0
    offsets = None
    for i, d in enumerate(devs):
        runs = raw["devices"][d]["runs"]
        bounds = offset_bounds(runs, lo, hi)
        if i == 0:
            offsets = bounds
        shift = round(bounds["window"][0] * 1e6) if bounds["window"][0] is not None else 0
        last = None
        for s, e, name, disp, _, _ in runs:
            c = clip((s + shift, e + shift), lo, hi)
            if disp is not None:
                last = disp
            if not c:
                continue
            n_runs += 1
            n_linked += disp is not None
            ref = disp if disp is not None else last
            owner = phase_at(index, ref) if ref is not None else ""
            sec = (c[1] - c[0]) / 1e9 / chips
            device_s[owner] += sec
            modules[owner][name] += sec
        if i == 0:
            busy = union([c for s, e in raw["devices"][d]["ops"]
                          if (c := clip((s + shift, e + shift), lo, hi))])
            for g in gaps(busy, lo, hi):
                idle_s[phase_at(index, (g[0] + g[1]) // 2)] += (g[1] - g[0]) / 1e9

    total = sum(device_s.values())
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:8]]
    return {
        "has_phases": bool(raw["spans"]),
        "n_rounds": n_rounds,
        "window_s": (hi - lo) / 1e9,
        "phases_host_s": phases_host_s,
        "device_s": dict(device_s),
        "idle_s": dict(idle_s),
        "charged_share": (total - device_s.get("", 0.0)) / total if total else None,
        "linked_share": n_linked / n_runs if n_runs else None,
        "module_runs": n_runs,
        "offset_ms": offsets,
        "modules": {p: top(m) for p, m in modules.items()},
    }


def window_xplane(ctx) -> Optional[str]:
    """The path of the window's trace, for a metric reader. A reduction that
    names its trace (``xplane``) is taken at its word. Otherwise the trace is
    the one in the ``trace_dir`` of the ``run.run_cell`` call that is reading
    the metrics: it is on the stack, and removes that directory only after its
    readers have run. None where neither is found."""
    if ctx.trace.get("xplane"):
        return ctx.trace["xplane"]
    frame = sys._getframe(1)
    while frame is not None:
        trace_dir = frame.f_locals.get("trace_dir")
        if frame.f_code.co_name == "run_cell" and isinstance(trace_dir, str):
            try:
                return find_xplane(trace_dir)
            except FileNotFoundError:
                return None
        frame = frame.f_back
    return None


def per_round_ms(ctx, key: str, phase: str) -> Optional[float]:
    """One phase's number from ``reduce_phases`` per window round, in ms, for
    a metric reader; None where the window's trace holds no phases."""
    path = window_xplane(ctx)
    if not path or not load(path)["spans"]:
        return None
    red = reduce_phases(path, ctx.trace["n_rounds"], ctx.cell["chips"])
    return red[key].get(phase, 0.0) / red["n_rounds"] * 1e3


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    print(json.dumps(reduce_phases(args.xplane, args.rounds, args.chips), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
