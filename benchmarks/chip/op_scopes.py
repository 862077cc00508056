"""Device time of the ops that a program's name scope marks, from one trace.

A ``jax.named_scope`` inside a jitted program reaches the compiled HLO as
each op's ``op_name`` metadata (``jit(f)/.../<scope>/<sub-scope>/<op>``),
and the TPU profiler writes it into the trace as the ``tf_op`` stat of each
op's event metadata in the ``XLA Ops`` line. Copies that autodiff and remat
make of a scope keep its name inside the path
(``transpose(jvp(...))/checkpoint/<scope>/...``), so a scope is matched as a
substring of the path.

``jax.profiler.ProfileData`` gives the op events but not the stats of their
metadata, so ``op_scopes`` reads those from the ``.xplane.pb`` itself with a
minimal protobuf reader (``XSpace`` > ``XPlane`` > ``event_metadata`` and
``stat_metadata``; the lines, by far the largest part, are skipped whole).

``scoped_seconds`` then takes the union of the matching ops' intervals (a
while loop's op holds its body's ops, so intervals nest), clipped to the
window as ``trace_reduce.py`` clips, and keeps the ops of one program: those
whose start lies in a run of that module.
"""

from __future__ import annotations

import bisect
import functools
from typing import Dict, Iterator, List, Optional, Tuple

from program_spans import ROUND_SPAN, window_xplane
from trace_reduce import DEVICE_PLANE, MODULE_ID, clip, load, union

SCOPE_STAT = "tf_op"

Event = Tuple[str, int, int]


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_scopes(buf) -> Tuple[str, Dict[str, str]]:
    """An XPlane's name and, per op name, the string value of its metadata's
    ``tf_op`` stat (a str_value, or a ref_value naming a stat metadata)."""
    name, events, stat_names = "", [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 4:
            events.append(v)
        elif f == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next((bytes(s).decode() for g, s in _fields(meta) if g == 2), "")
    if not DEVICE_PLANE.match(name):
        return name, {}
    scope_ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
    scopes: Dict[str, str] = {}
    for entry in events:
        op, path = "", None
        for f, v in _fields(_map_entry(entry)[1]):
            if f == 2:
                op = bytes(v).decode()
            elif f == 5:
                stat = dict(_fields(v))
                if stat.get(1) in scope_ids:
                    if 5 in stat:
                        path = bytes(stat[5]).decode()
                    elif 7 in stat:
                        path = stat_names.get(stat[7])
        if op and path:
            scopes[op] = path
    return name, scopes


@functools.lru_cache(maxsize=1)
def op_scopes(path: str) -> Dict[int, Dict[str, str]]:
    """Per TPU device index, op name -> its scope path (``tf_op``)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[int, Dict[str, str]] = {}
    for field, plane in _fields(buf):
        if field == 1:
            name, scopes = _plane_scopes(plane)
            m = DEVICE_PLANE.match(name)
            if m:
                out[int(m.group(1))] = scopes
    return out


@functools.lru_cache(maxsize=1)
def _events(path: str):
    return load(path)


def scoped_seconds(ops: List[Event], modules: List[Event], scopes: Dict[str, str],
                   lo: int, hi: int, program: str, scope: str) -> float:
    """Seconds of [lo, hi] in which an op of ``program`` (a module name, its
    ``(id)`` suffix dropped) whose scope path holds ``scope`` ran on one device."""
    mods = sorted((s, e, MODULE_ID.sub("", n)) for n, s, e in modules)
    starts = [m[0] for m in mods]
    hits = []
    for name, s, e in ops:
        if scope not in scopes.get(name, ""):
            continue
        j = bisect.bisect_right(starts, s) - 1
        if j < 0 or mods[j][1] < s or mods[j][2] != program:
            continue
        if (c := clip((s, e), lo, hi)):
            hits.append(c)
    return sum(e - s for s, e in union(hits)) / 1e9


def scoped_ms(ctx, program: str, scope: str) -> Optional[float]:
    """Device time per window round, in ms, of the ops of ``program`` under
    the name scope ``scope``, averaged over the cell's chips; None where the
    window's trace is not found or no such op ran in the window."""
    path = window_xplane(ctx)
    if not path:
        return None
    raw, scopes = _events(path), op_scopes(path)
    n_rounds, chips = ctx.trace["n_rounds"], ctx.cell["chips"]
    rounds = sorted(raw["host"].get(ROUND_SPAN, []))
    if len(rounds) < n_rounds or n_rounds < 1:
        return None
    lo, hi = rounds[0][0], rounds[n_rounds - 1][1]
    total = sum(scoped_seconds(raw["devices"][d]["ops"], raw["devices"][d]["modules"],
                               scopes.get(d, {}), lo, hi, program, scope)
                for d in sorted(raw["devices"])[:chips])
    if not total:
        return None
    return total / chips / n_rounds * 1e3
