"""Record ``fixtures/v5e_phases.xplane.pb`` on a TPU: a tiny federation
(softmax regression on 8x8 images, K=4, M=2, 2 local steps, the eager eval
of 40 images), one warm-up round, then two rounds under the profiler as the
harness traces them (Python tracer off), each inside a ``bench.round``
span. The engine's own ``fed.*`` phase spans are in it. The trace is then
cut to the events the reduction reads (``trim``), which also drops the
machine's paths and names.

    python benchmarks/chip/tests/record_phases_fixture.py [<out.xplane.pb>]
"""

import glob
import os
import shutil
import sys
import tempfile
import types
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))

OUT = Path(__file__).resolve().parent / "fixtures" / "v5e_phases.xplane.pb"
WARMUP, TRACED = 1, 2


class LinearClassifier:
    """Softmax regression on flattened images, evaluated eagerly by the
    engine's ``default_eval`` (a few device programs a round)."""

    cfg = types.SimpleNamespace(family="resnet")

    def init_params(self, key):
        return {"w": 0.01 * jax.random.normal(key, (8 * 8 * 3, 10))}

    def forward(self, params, batch):
        x = batch["images"].reshape(batch["images"].shape[0], -1)
        return x @ params["w"]

    def loss(self, params, batch):
        logp = jax.nn.log_softmax(self.forward(params, batch))
        return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], 1))


def main(argv):
    from repro.configs.base import FedConfig
    from repro.data import make_vision_data
    from repro.fed import FederatedSpec
    from repro.fed.engine import RoundHook

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: JAX found {jax.devices()[0].platform!r}", file=sys.stderr)
        return 1
    out = Path(argv[0]) if argv else OUT
    trace_dir = tempfile.mkdtemp(prefix="phases_fixture_")

    class Trace(RoundHook):
        def on_round_start(self, ctx):
            if ctx.round_idx == WARMUP:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.round")
            self.span.__enter__()

        def on_round_end(self, ctx):
            self.span.__exit__(None, None, None)
            if ctx.round_idx == WARMUP + TRACED - 1:
                jax.profiler.stop_trace()

    model = LinearClassifier()
    fed = FedConfig(num_clients=4, participation=0.5, rounds=WARMUP + TRACED,
                    local_epochs=2, local_batch=4, lr=0.05, mu=0.1,
                    dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, image_size=8, train_per_class=8, test_per_class=4)
    FederatedSpec(model, fed, data, selector="heterosel", steps_per_round=2,
                  hooks=[Trace()]).build().run()
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    trim(out)
    print(f"{out}: {out.stat().st_size} bytes")
    return 0


KEEP_STATS = {"_p", "_c", "_pt", "_ct", "run_id"}


def trim(path):
    """Keep only what program_spans.py and trace_reduce.py read: the TPU
    planes' module and op lines (op names cut to their HLO name), and the
    host's phase, round and dispatch-chain events with their flow stats."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    sys.path.insert(0, str(BENCH))
    from program_spans import LINKED

    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    planes = []
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        meta = plane.event_metadata
        stat_ids = {k for k, m in plane.stat_metadata.items() if m.name in KEEP_STATS}
        lines = []
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            events = [e for e in line.events if device or meta[e.metadata_id].name in LINKED
                      or meta[e.metadata_id].name.startswith(("fed.", "bench.round"))]
            if not events:
                continue
            for e in events:
                kept = [st for st in e.stats if st.metadata_id in stat_ids]
                del e.stats[:]
                e.stats.extend(kept)
            del line.events[:]
            line.events.extend(events)
            lines.append(line)
        used = {e.metadata_id for line in lines for e in line.events}
        for k in list(meta):
            if k not in used:
                del meta[k]
            else:
                m = meta[k]
                m.name = m.name.split(" = ")[0]
                m.display_name = ""
                m.metadata = b""
                del m.stats[:]
                del m.child_id[:]
        for k in list(plane.stat_metadata):
            if k not in stat_ids:
                del plane.stat_metadata[k]
        del plane.stats[:]
        del plane.lines[:]
        plane.lines.extend(lines)
        planes.append(plane)
    del space.planes[:]
    space.planes.extend(planes)
    del space.hostnames[:]
    Path(path).write_bytes(space.SerializeToString())


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
