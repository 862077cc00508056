"""The program's phase spans read from a profiler trace (program_spans.py).

Two traces recorded on a TPU v5e: ``fixtures/v5e_small.xplane.pb`` (four
``bench.round`` spans, each a 10 ms host gather then one jitted matmul; no
program phases) and ``fixtures/v5e_phases.xplane.pb`` (two rounds of a tiny
federation with the engine's ``fed.*`` phases and an eager eval; see
``record_phases_fixture.py``)."""

import importlib
import sys
from collections import defaultdict
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import program_spans  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SMALL = str(FIXTURES / "v5e_small.xplane.pb")
PHASES = str(FIXTURES / "v5e_phases.xplane.pb")
EXISTING = ["compiles_in_window", "select_wall_ms", "score_select_roofline",
            "execute_device_ms", "execute_roofline", "device_idle_share", "round_mfu"]
NEW = ["eval_device_ms", "eval_idle_ms", "aggregate_device_ms",
       "state_update_device_ms", "gather_host_ms"]


def test_innermost_segments():
    spans = [(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (6, 8, "d"), (12, 13, "e")]
    index = program_spans.innermost(spans)
    assert index[1] == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"),
                        (6, 8, "d"), (8, 10, "a"), (12, 13, "e")]
    assert [program_spans.phase_at(index, t) for t in (0, 2, 3, 5, 7, 9, 11, 12)] == [
        "a", "b", "c", "a", "d", "a", "", "e"]


def test_small_fixture_links_and_offset():
    red = program_spans.reduce_phases(SMALL, 4, 1)
    assert red["module_runs"] == 4 and red["linked_share"] == 1.0
    lower, upper = red["offset_ms"]["window"]
    # Read by hand: enqueue - device start is largest for the first run,
    # callback - device end smallest for the last.
    assert lower == pytest.approx(1.48, abs=0.01)
    assert upper == pytest.approx(1.85, abs=0.01)
    assert not red["has_phases"] and red["charged_share"] == 0.0


def metric_context(trace, cell="resnet18.paper-k12"):
    c = run.load_cell(cell)
    c["name"] = cell
    return run.MetricContext(cell=c, kind="TPU v5 lite",
                             rounds=[{"end": 1.0, "wall_s": 0.02, "select_ms": 4.0}] * 4,
                             compiles=0, trace=trace)


def window_trace_dir(tmp_path, fixture):
    """A trace directory laid out as the profiler writes it."""
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(Path(fixture).read_bytes())
    return str(tmp_path)


def read_in_run_cell(name, trace, trace_dir):
    """A reader called as run.py calls it: from ``run_cell``, whose
    ``trace_dir`` holds the window's trace, with the reduction alone."""
    def run_cell(trace_dir):
        return importlib.import_module(f"metrics.{name}").read(metric_context(trace))
    return run_cell(trace_dir)


@pytest.mark.parametrize("fixture", [SMALL, PHASES])
def test_readers_find_the_window_trace(tmp_path, fixture):
    """The new readers find the window's trace through the ``run_cell`` call
    that reads them; the existing readers read what they read without it; a
    trace without the program's phases, or no trace found, leaves the new
    readers silent."""
    n = 4 if fixture == SMALL else 2
    red = trace_reduce.reduce_trace(None, "bench.round", n_rounds=n, chips=1,
                                    host_spans=["^bench\\.gather$"], path=fixture)
    trace_dir = window_trace_dir(tmp_path, fixture)
    for name in EXISTING:
        read = importlib.import_module(f"metrics.{name}").read
        assert read_in_run_cell(name, red, trace_dir) == read(metric_context(red)), name
    for name in NEW:
        read = importlib.import_module(f"metrics.{name}").read
        assert read(metric_context(red)) is None, name
        got = read_in_run_cell(name, red, trace_dir)
        if fixture == SMALL:
            assert got is None, name
        else:
            assert got is not None and got == read(metric_context(dict(red, xplane=fixture))), name
    assert read_in_run_cell(NEW[0], red, str(tmp_path / "none")) is None


def by_hand(path, n_rounds):
    """Per-phase device and idle seconds of device 0, recomputed the plain
    way: linear scans, and a phase's time charged to the shortest span that
    holds the moment."""
    from jax.profiler import ProfileData

    def flow(e, key):
        return next((int(v) for k, v in e.stats if k == key), None)

    spans, rounds, host = [], [], defaultdict(list)
    runs, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                s, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if plane.name == "/device:TPU:0":
                    if line.name == "XLA Modules":
                        runs.append((s, end, flow(e, "_c")))
                    elif line.name == "XLA Ops":
                        ops.append((s, end))
                elif e.name.startswith("fed.") and e.name != "fed.round":
                    spans.append((s, end, e.name[4:]))
                elif e.name == "bench.round":
                    rounds.append((s, end))
                elif e.name in program_spans.LINKED:
                    host[e.name].append((line.name, s, end, flow(e, "_p"), flow(e, "_c")))
    rounds.sort()
    lo, hi = rounds[0][0], rounds[n_rounds - 1][1]

    def holding(name, line, t):
        return next(ev for ev in host[name] if ev[0] == line and ev[1] <= t <= ev[2])

    def dispatch(c):
        enq = next(ev for ev in host["DoEnqueueProgram"] if ev[3] == c)
        seq = holding("tpu::System::Execute=>IssueSequencedEvent", enq[0], enq[1])
        sysex = next(ev for ev in host["tpu::System::Execute"] if ev[3] == seq[4])
        ex = holding("PJRT_LoadedExecutable_Execute", sysex[0], sysex[1])
        link = next(ev for ev in host["PJRT_LoadedExecutable_Execute linkage"] if ev[3] == ex[4])
        return link[1], enq[1]

    def phase(t):
        inside = [sp for sp in spans if sp[0] <= t < sp[1]]
        return min(inside, key=lambda sp: sp[1] - sp[0])[2] if inside else ""

    linked = [(s, e, *dispatch(c)) for s, e, c in runs]
    offset = max(enq - s for s, e, _, enq in linked if lo <= enq <= hi)
    device, idle = defaultdict(float), defaultdict(float)
    for s, e, disp, _ in linked:
        a, b = max(s + offset, lo), min(e + offset, hi)
        if b > a:
            device[phase(disp)] += (b - a) / 1e9
    busy = trace_reduce.union([(max(s + offset, lo), min(e + offset, hi)) for s, e in ops
                               if min(e + offset, hi) > max(s + offset, lo)])
    for a, b in trace_reduce.gaps(busy, lo, hi):
        idle[phase((a + b) // 2)] += (b - a) / 1e9
    return device, idle, offset


@pytest.fixture(scope="module")
def phases():
    return program_spans.reduce_phases(PHASES, 2, 1)


def test_phases_fixture_matches_hand_sums(phases):
    device, idle, offset = by_hand(PHASES, 2)
    assert phases["offset_ms"]["window"][0] == pytest.approx(offset / 1e6)
    assert set(phases["device_s"]) == set(device)
    for name, sec in device.items():
        assert phases["device_s"][name] == pytest.approx(sec, rel=1e-9, abs=1e-12), name
    assert set(phases["idle_s"]) == set(idle)
    for name, sec in idle.items():
        assert phases["idle_s"][name] == pytest.approx(sec, rel=1e-9, abs=1e-12), name


def test_phases_fixture_links_and_charges(phases):
    assert phases["has_phases"] and phases["linked_share"] == 1.0
    assert phases["charged_share"] >= 0.95
    for part in ("window", "first_tenth", "last_tenth"):
        lower, upper = phases["offset_ms"][part]
        assert lower is None or upper is None or lower <= upper, part
    for name in ("select", "execute", "gather", "aggregate", "state_update", "eval"):
        assert phases["phases_host_s"][name] > 0, name
    # every program the eval dispatches runs on the device inside the window
    assert phases["device_s"]["eval"] > 0 and phases["device_s"]["execute"] > 0


def test_new_readers_on_the_phases_fixture(phases):
    red = trace_reduce.reduce_trace(None, "bench.round", n_rounds=2, chips=1,
                                    host_spans=["^bench\\.gather$"], path=PHASES)
    ctx = metric_context(dict(red, xplane=PHASES))
    got = {name: importlib.import_module(f"metrics.{name}").read(ctx) for name in NEW}
    assert got["eval_device_ms"] == pytest.approx(phases["device_s"]["eval"] / 2 * 1e3)
    assert got["eval_idle_ms"] == pytest.approx(phases["idle_s"].get("eval", 0.0) / 2 * 1e3)
    assert got["aggregate_device_ms"] == pytest.approx(phases["device_s"]["aggregate"] / 2 * 1e3)
    assert got["gather_host_ms"] == pytest.approx(phases["phases_host_s"]["gather"] / 2 * 1e3)
    assert all(v is not None and v >= 0 for v in got.values()), got
