"""Tiny CPU-sized copies of the benchmark's cells, for the harness tests.

Widths, depths and counts shrink so a CPU runs a whole cell in seconds;
the traffic kind, the federation's options (but the tiny LM's lr), the
reference and the limits stay the cell's own.
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parents[1] / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402


def tiny_cell(name: str):
    cell = run.load_cell(name)
    cell["name"] = name
    cfg, traffic, fed = cell["config_file"], cell["traffic"], cell["fed"]
    if cfg["reference"] == "resnet":
        cfg["program"]["overrides"] = {"d_model": 8}
        cfg["d_model"] = 8
        lazy = traffic["kind"] == "vision_lazy"
        traffic.update(num_clients=40 if lazy else 4, image_size=8, train_per_class=16,
                       test_per_class=4)
        fed.update(num_selected=4 if lazy else 2, local_steps=2, local_batch=4,
                   client_chunk=2 if lazy else 0)
    else:
        cfg["program"]["overrides"] = dict(num_layers=2, d_model=64, vocab_size=256,
                                           ssm_state=16, ssm_headdim=16, ssm_chunk=16)
        cfg.update(n_layer=2, d_model=64, vocab_size=256, padded_vocab=256, d_state=16,
                   headdim=16, chunk_size=16)
        traffic.update(vocab=256, seq_len=32, eval_sequences=16)
        # At these widths the cell's lr moves bfloat16 weights by rounding
        # alone (updates under one ulp), which no comparison can follow.
        fed.update(local_batch=2, local_steps=2, client_chunk=0, lr=0.1)
    return cell


CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))
