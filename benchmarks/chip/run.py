"""On-chip benchmark of the HeteRo-Select federation: one cell, one run.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is sits in files found by name: the cell's traffic,
federation and engine parameters in ``workloads/<cell>.json``, its traffic
kind's generator in ``traffic/<kind>.py``, its model configuration in
``configs/<config>.json``, the plain references (federation and model) in
``reference/<module>.py``, each per-layer metric's reader in
``metrics/<metric>.py`` and the operation counts in ``flops/<family>.py``.

A workload file's ``fed`` holds ``FedConfig`` fields (``num_selected`` and
``local_steps`` in the paper's terms) and its ``spec`` holds
``FederatedSpec`` options (executor, aggregator, compact state, ...), both
passed through as they are; ``spec.mesh`` maps mesh axis names to sizes and
is built over the cell's chips (a cell on more than one chip names its
mesh), and a nested config such as ``hier_cfg`` or ``async_cfg`` is built
from its dict. Options that are data, such as availability masks or
straggler profiles, come from the traffic's data object
(``spec_options()``), made from the seed like the rest of the traffic.

A run builds the cell's data from ``--seed`` and drives the program's own
entry, ``FederatedEngine.run()``, through a round hook. The first rounds
are set-up: they compile every program the window uses and are the rounds
the correctness check compares with the plain reference. The window then
holds the whole rounds that end within ``--seconds`` of its start. With
``--trace 1`` the window runs under the profiler (device and annotation
tracing; its Python tracer is off, since it slows the host) and the
per-layer metrics are read from the trace.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``; its last key, ``checks``, gives each compared number beside
its limit, which also close standard error. There is no CPU fallback: a run
that finds no TPU, or fewer chips than the cell asks for, fails.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import dataclasses
import gc
import importlib
import json
import math
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
WARMUP_ROUNDS = 3  # compiled, loaded and compared with the reference
ROUND_SPAN = "bench.round"


class BenchError(RuntimeError):
    """A run that cannot produce a result."""


class WindowClosed(Exception):
    """Raised from the round hook to end ``FederatedEngine.run()``."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    path = BENCH / "workloads" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no workload file {path}")
    cell = load_json(path)
    cell["config_file"] = load_json(BENCH / "configs" / f"{cell['config']}.json")
    cell.setdefault("spec", {})
    check_mesh(cell)
    return cell


def check_mesh(cell: Dict[str, Any]) -> None:
    """A cell runs on as many chips as its spec's mesh spans (one without)."""
    mesh = cell["spec"].get("mesh")
    chips = math.prod(mesh.values()) if mesh else 1
    if chips != cell["chips"]:
        raise BenchError(f"the cell asks for {cell['chips']} chips and its spec's "
                         f"mesh {mesh} spans {chips}")


def check_device(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    """The device as JAX reports it; a run without a TPU or with too few
    chips raises here, before any work."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform {d0.platform!r} "
                         f"({d0.device_kind}); this benchmark runs only on a TPU")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def enable_cache() -> str:
    """Persistent compilation cache at a fixed path (JAX_COMPILATION_CACHE_DIR
    when set), holding every program however quick it was to compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------


def program_model(cfg_file: Dict[str, Any]):
    from repro.configs.registry import get_config
    from repro.models import build_model

    prog = cfg_file["program"]
    return build_model(dataclasses.replace(get_config(prog["arch"]), **prog.get("overrides", {})))


def fed_config(fed: Dict[str, Any], k: int, alpha: float, seed: int):
    """``FedConfig`` from a workload's ``fed``: ``num_selected`` and
    ``local_steps`` translated, every other key passed as the field it names."""
    from repro.configs.base import FedConfig

    rest = {n: v for n, v in fed.items() if n not in ("num_selected", "local_steps")}
    try:
        cfg = FedConfig(num_clients=k, participation=fed["num_selected"] / k,
                        rounds=10 ** 9, local_epochs=fed["local_steps"],
                        dirichlet_alpha=alpha, seed=seed, **rest)
    except TypeError as e:
        raise BenchError(f"the workload's fed names no FedConfig field: {e}") from e
    if cfg.num_selected != fed["num_selected"]:
        raise BenchError(f"participation gives m={cfg.num_selected}, "
                         f"the cell asks for {fed['num_selected']}")
    return cfg


def host_params(params):
    """A host copy of a parameter tree (set-up only: it syncs)."""
    import jax
    import numpy as np

    return jax.tree_util.tree_map(np.asarray, jax.device_get(params))


class CompileCounter:
    """Counts the executables JAX compiles or loads from its cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.on and event == self.EVENT:
            self.count += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


# FederatedSpec options given as a dict in a workload file, and the config
# class each is built into.
SPEC_CONFIGS = {"hier_cfg": ("repro.fed.hierarchy", "HierarchyConfig"),
                "async_cfg": ("repro.fed.async_engine", "AsyncConfig")}


def spec_options(cell: Dict[str, Any], data) -> Dict[str, Any]:
    """The ``FederatedSpec`` keyword arguments of a cell's ``spec``."""
    options = dict(cell["spec"])
    if "mesh" in options:
        options["mesh"], options["mesh_axes"] = make_mesh(options["mesh"])
    for key, (module, cls) in SPEC_CONFIGS.items():
        if isinstance(options.get(key), dict):
            options[key] = getattr(importlib.import_module(module), cls)(**options[key])
    if hasattr(data, "spec_options"):
        options.update(data.spec_options())
    return options


def make_mesh(axes: Dict[str, int]):
    """A mesh of the given axis sizes over the first devices, and the
    program's ``MeshAxes`` naming those axes."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.sharding.rules import MeshAxes

    n = math.prod(axes.values())
    devs = np.asarray(jax.devices()[:n]).reshape(tuple(axes.values()))
    return Mesh(devs, tuple(axes)), MeshAxes(**{a: a for a in axes})


def make_hook(data, seconds: Optional[float], trace_dir: Optional[str],
              counter: CompileCounter):
    from repro.fed.engine import RoundHook
    import jax

    class BenchHook(RoundHook):
        """Marks round boundaries, keeps what the check compares from the
        set-up rounds, and closes the window after ``seconds`` (``None``:
        stop once the compared rounds are done)."""

        def __init__(self) -> None:
            self.snap: Dict[str, Any] = {}
            self.loss: List[float] = []
            self.metric: List[float] = []
            self.cohorts: List[Any] = []
            self.window: List[Dict[str, float]] = []
            self.w0 = self.setup_end = 0.0
            self._span = None
            self._t0 = 0.0

        def on_run_start(self, ctx) -> None:
            self.snap["p0"] = host_params(ctx.engine.params)
            data.recording = True

        def on_round_start(self, ctx) -> None:
            self._t0 = time.perf_counter()
            self._span = jax.profiler.TraceAnnotation(ROUND_SPAN)
            self._span.__enter__()

        def on_round_end(self, ctx) -> None:
            self._span.__exit__(None, None, None)
            t, now = ctx.round_idx, time.perf_counter()
            if t < WARMUP_ROUNDS:
                self.loss.append(float(ctx.train_loss))
                self.metric.append(float(ctx.metric))
                self.cohorts.append(ctx.selected.copy())
                if t == 0:
                    self.snap["p1"] = host_params(ctx.engine.params)
                if t == WARMUP_ROUNDS - 1:
                    self.snap["p_last"] = host_params(ctx.engine.params)
                    data.recording = False
                    if seconds is None:
                        raise WindowClosed()
                    if trace_dir:
                        opts = jax.profiler.ProfileOptions()
                        opts.python_tracer_level = 0
                        jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    counter.on = True
                    self.w0 = self.setup_end = time.perf_counter()
                return
            if now - self.w0 > seconds:
                counter.on = False
                raise WindowClosed()
            self.window.append({"end": now, "wall_s": now - self._t0,
                                "select_ms": float(ctx.select_ms)})

    return BenchHook()


def drive(cell: Dict[str, Any], seed: int, seconds: Optional[float],
          trace_dir: Optional[str], counter: CompileCounter):
    """Build the cell's data and engine from ``seed`` and run it through the
    set-up rounds and the window; returns (data, hook, the federation's
    seed)."""
    import jax

    from traffic import generate
    from repro.fed import FederatedSpec

    fed = cell["fed"]
    fed_seed = seed % (2 ** 31 - 1)
    data = generate.build(cell["traffic"], seed)
    model = program_model(cell["config_file"])
    fcfg = fed_config(fed, data.num_clients, cell["traffic"].get("dirichlet_alpha", 0.0),
                      fed_seed)
    hook = make_hook(data, seconds, trace_dir, counter)
    try:
        spec = FederatedSpec(model, fcfg, data, steps_per_round=fed["local_steps"],
                             hooks=[hook], **spec_options(cell, data))
    except TypeError as e:
        raise BenchError(f"the workload's spec names no FederatedSpec option: {e}") from e
    try:
        spec.build().run()
    except WindowClosed:
        pass
    finally:
        if trace_dir and hook.setup_end:
            jax.profiler.stop_trace()
    return data, hook, fed_seed


def run_cell(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_process: float = T_PROCESS) -> Dict[str, Any]:
    """One run of one cell; returns the result line as a dict."""
    import jax

    device = check_device(cell["chips"], require_tpu)
    if require_tpu:
        enable_cache()
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        try:
            data, hook, fed_seed = drive(cell, seed, seconds, trace_dir, counter)
        finally:
            counter.close()
        rounds = hook.window
        if not rounds:
            raise BenchError(f"no whole round ended within {seconds} s of the window's start")
        device["memory_peak_bytes"] = int(max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()[:cell["chips"]]))
        metrics: Dict[str, Any] = {}
        breakdown = None
        if trace:
            from trace_reduce import reduce_trace

            red = reduce_trace(trace_dir, ROUND_SPAN, n_rounds=len(rounds),
                               chips=cell["chips"],
                               host_spans=load_json(BENCH / "trace_names.json")["host_spans"])
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = red["breakdown"]
            ctx = MetricContext(cell=cell, kind=device["kind"], rounds=rounds,
                                compiles=counter.count, trace=red)
            for spec_m in per_layer_metrics(cell["name"]):
                value = importlib.import_module(f"metrics.{spec_m['name']}").read(ctx)
                if value is not None:
                    metrics[spec_m["name"]] = {"value": value, "unit": spec_m["unit"]}
        else:
            window_s = rounds[-1]["end"] - hook.w0
            metrics["round_ms"] = {"value": window_s / len(rounds) * 1e3, "unit": "ms"}
            metrics["setup_s"] = {"value": hook.setup_end - t_process, "unit": "s"}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # The check runs once the window has closed and the program's state is freed.
    snap, loss, metric, cohorts = hook.snap, hook.loss, hook.metric, hook.cohorts
    del hook
    gc.collect()
    from compare import compare_run

    checks = compare_run(cell, data, snap, loss, metric, cohorts, fed_seed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": bool(correct), "attempted": WARMUP_ROUNDS + len(rounds),
           "failed": 0 if correct else 1, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MetricContext:
    """What a metric reader sees: the cell, the device kind, the window's
    rounds (host clock and the engine's own select span), the compile count
    and the reduced device trace, whose programs and ops each reader picks
    by its own patterns, so a new metric is a new reader file alone."""

    cell: Dict[str, Any]
    kind: str
    rounds: List[Dict[str, float]]
    compiles: int
    trace: Dict[str, Any]

    def module_seconds(self, pattern: str) -> float:
        """Device time in the window of the XLA modules whose names match."""
        r = re.compile(pattern)
        return sum(sec for mod, sec in self.trace["modules"].items() if r.search(mod))

    def op_seconds(self, pattern: str) -> float:
        """Device time in the window of the ops whose "<module> <op>" label matches."""
        r = re.compile(pattern)
        return sum(sec for op, sec in self.trace["ops"].items() if r.search(op))

    def peak(self, key: str) -> float:
        peaks = load_json(BENCH / "peaks.json")["devices"]
        if self.kind not in peaks:
            raise BenchError(f"device kind {self.kind!r} is not in peaks.json")
        return float(peaks[self.kind][key])

    def flops(self):
        fam = self.cell["config_file"]["flops"]
        return importlib.import_module(f"flops.{fam}")


def per_layer_metrics(cell: str) -> List[Dict[str, Any]]:
    """BENCHMARK.json's per-layer metrics that this cell reports."""
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"{path} is missing")
    return [m for m in load_json(path)["per_layer"]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    try:
        cell = load_cell(args.workload)
        cell["name"] = args.workload
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
