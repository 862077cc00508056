"""Round phases as spans: one helper, two exporters.

Every phase of a federated round (``select``, ``execute`` and the
``gather`` and ``aggregate`` inside it, ``aggregate``, ``state_update``,
``eval``; ``outer_select``/``inner_select``/``edge_execute`` under the
hierarchical topology) is opened with ``phase``:

    with phase("select", ctx, engine.tracer, args={"round": t}):
        ...

which

  * opens ``jax.profiler.TraceAnnotation("fed.<name>")`` every time, so a
    profiler trace (``jax.profiler.trace(dir)``, then XProf or Perfetto)
    shows the phase on the host's timeline, and each device program the
    phase dispatches can be charged to it through the profiler's flow
    links from the host call to the device run;
  * times the phase on the host wall clock and, given a ``ctx``, sets
    ``ctx.<name>_ms`` (``RoundContext.select_ms`` ...);
  * given an enabled recorder (``obs.trace.TraceRecorder``), emits the
    matching host wall-time span into the Chrome trace: on the track
    ``"round"`` at top level, or on the track named after the enclosing
    phase when nested, so that every track stays monotone.

It adds no device sync: phase times are host wall time, which for a phase
that only dispatches work (``execute``, ``aggregate``) is the dispatch, not
the device's run. Device time per phase comes from the profiler trace.

``round_step(t)`` marks one whole round (hooks included) as the profiler's
step ``t`` (``fed.round``), for XProf's step view.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from repro.obs.trace import NULL_TRACER, NullRecorder

PREFIX = "fed."

_open = threading.local()  # per thread: the stack of open phases


def _stack() -> List["phase"]:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def current_recorder() -> NullRecorder:
    """The recorder of the innermost open phase (``NULL_TRACER`` if none).

    Executors open their nested phases (``gather``) with it, so those land
    in the Chrome trace of the engine that called them."""
    stack = _stack()
    return stack[-1].recorder if stack else NULL_TRACER


class phase:
    """Context manager of one round phase; see the module docstring.

    ``args`` are the Chrome span's arguments; the body may add to them
    (``with phase(...) as p: p.args["arrivals"] = n``). ``track`` overrides
    the Chrome track.
    """

    __slots__ = ("name", "ctx", "recorder", "args", "track", "t0", "_ann")

    def __init__(self, name: str, ctx: Any = None,
                 recorder: Optional[NullRecorder] = None, *,
                 args: Optional[Dict[str, Any]] = None,
                 track: Optional[str] = None):
        self.name = name
        self.ctx = ctx
        self.recorder = recorder if recorder is not None else NULL_TRACER
        self.args = {} if args is None else args
        self.track = track
        self.t0 = 0.0

    def __enter__(self) -> "phase":
        from jax.profiler import TraceAnnotation

        stack = _stack()
        if self.track is None:
            self.track = stack[-1].name if stack else "round"
        stack.append(self)
        self._ann = TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        _stack().pop()
        if exc_type is None:
            if self.ctx is not None:
                setattr(self.ctx, f"{self.name}_ms", (t1 - self.t0) * 1e3)
            if self.recorder.enabled:
                self.recorder.wall_span(self.name, self.t0, t1,
                                        track=self.track, args=self.args)
        return False


def round_step(t: int):
    """The profiler's step annotation ``fed.round`` for round ``t``."""
    from jax.profiler import StepTraceAnnotation

    return StepTraceAnnotation(PREFIX + "round", step_num=int(t))
