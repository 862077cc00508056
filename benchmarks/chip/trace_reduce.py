"""Reduce one profiler trace of the window to the numbers the metrics read.

``reduce_trace`` reads the ``.xplane.pb`` the JAX profiler wrote with
``jax.profiler.ProfileData`` and returns, over the traced window:

  busy_s      the union of the intervals in which an operation ran on a
              device, averaged over the cell's chips;
  window_s    the window: from the start of its first round to the end of
              its last whole round (the host's ``bench.round`` spans);
  modules     device time per XLA module (its ``(id)`` suffix dropped);
  ops         device time per op, keyed "<module> <op's HLO text>", from
              which a metric reader sums the ops of its kernel;
  idle        the idle gaps of device 0, each given to the innermost listed
              host span that holds its midpoint (``bench.gather``, the
              cohort's synthesis and copy; the runtime's dispatch of a
              jitted function, ``PjitFunction(<name>)``; a host-to-device
              put; garbage collection), else to "host: other", the
              program's Python between dispatches; and the breakdown the
              result line carries (top device ops and idle causes).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv: Interval, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def load(path: str) -> Dict[str, Any]:
    """Host spans and device op/module events of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: Dict[str, List[Interval]] = defaultdict(list)
    devices: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices[int(m.group(1))] = {"ops": lines.get(OPS_LINE, []),
                                        "modules": lines.get(MODULES_LINE, [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    host[name].append((s, e))
    return {"host": host, "devices": devices}


def reduce_trace(trace_dir: str, round_span: str, n_rounds: int, chips: int,
                 host_spans: List[str], path: Optional[str] = None) -> Dict[str, Any]:
    raw = load(path or find_xplane(trace_dir))
    rounds = sorted(raw["host"].get(round_span, []))
    if len(rounds) < n_rounds or n_rounds < 1:
        raise ValueError(f"trace holds {len(rounds)} {round_span!r} spans, "
                         f"the window {n_rounds}")
    lo, hi = rounds[0][0], rounds[n_rounds - 1][1]
    devs = sorted(raw["devices"])[:chips]
    if len(devs) < chips:
        raise ValueError(f"trace holds {len(devs)} TPU planes, the cell {chips}")

    busy_total = 0.0
    busy0: List[Interval] = []
    modules: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    top_ops: Dict[str, float] = defaultdict(float)
    for i, d in enumerate(devs):
        ev = raw["devices"][d]
        merged = union([c for _, s, e in ev["ops"] if (c := clip((s, e), lo, hi))])
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            busy0 = merged
        mods = sorted((s, e, MODULE_ID.sub("", n)) for n, s, e in ev["modules"])
        for s, e, name in mods:
            if (c := clip((s, e), lo, hi)):
                modules[name] += (c[1] - c[0]) / 1e9 / chips
        # Each op belongs to the module whose run holds its start.
        mstarts = [m[0] for m in mods]
        for name, s, e in ev["ops"]:
            c = clip((s, e), lo, hi)
            if not c:
                continue
            j = bisect.bisect_right(mstarts, s) - 1
            mod = mods[j][2] if j >= 0 and mods[j][1] >= s else "?"
            dur = (c[1] - c[0]) / 1e9 / chips
            ops[f"{mod} {name}"] += dur
            top_ops[f"{mod} {name.split(' = ')[0]}"] += dur

    # Each idle gap of device 0 goes to the innermost host span, among those
    # the name map lists, that holds the gap's midpoint.
    host_res = [re.compile(p) for p in host_spans]
    spans = {n: union([c for iv in v if (c := clip(iv, lo, hi))])
             for n, v in raw["host"].items() if any(r.search(n) for r in host_res)}
    starts = {n: [s for s, _ in ivs] for n, ivs in spans.items()}
    idle: Dict[str, float] = defaultdict(float)
    for g in gaps(busy0, lo, hi):
        mid = (g[0] + g[1]) // 2
        best, width = "host: other", None
        for name, ivs in spans.items():
            j = bisect.bisect_right(starts[name], mid) - 1
            if j >= 0 and ivs[j][1] >= mid:
                w = ivs[j][1] - ivs[j][0]
                if width is None or w < width:
                    best, width = name, w
        idle[best] += (g[1] - g[0]) / 1e9

    window_s = (hi - lo) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy_total / chips / 1e9,
        "window_s": window_s,
        "n_rounds": n_rounds,
        "modules": dict(modules),
        "ops": dict(ops),
        "idle": dict(idle),
        "breakdown": {"device_ops": top(top_ops), "idle_gaps": top(idle)},
    }
