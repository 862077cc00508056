"""A workload's ``fed`` and ``spec`` reach the program as they are written:
FedConfig fields and FederatedSpec options pass through, nested configs are
built from their dicts, data-made options come from the traffic, and a key
the program does not know, or a federation the reference does not
implement, is refused."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from reference import federation  # noqa: E402


class _Data:
    def spec_options(self):
        return {"availability": "masks"}


def test_nested_configs_and_data_options():
    from repro.fed.hierarchy import HierarchyConfig

    cell = {"spec": {"topology": "hierarchical", "hier_cfg": {}}}
    opts = run.spec_options(cell, _Data())
    assert isinstance(opts["hier_cfg"], HierarchyConfig)
    assert opts["topology"] == "hierarchical" and opts["availability"] == "masks"


def test_fed_passes_fields_through():
    fed = {"num_selected": 4, "local_steps": 3, "local_batch": 8, "lr": 0.05,
           "client_chunk": 2, "edge_count": 2}
    cfg = run.fed_config(fed, k=8, alpha=0.1, seed=3)
    assert (cfg.num_selected, cfg.local_epochs, cfg.local_batch) == (4, 3, 8)
    assert (cfg.client_chunk, cfg.edge_count, cfg.lr) == (2, 2, 0.05)


def test_unknown_fed_field_is_refused():
    with pytest.raises(run.BenchError, match="FedConfig"):
        run.fed_config({"num_selected": 2, "local_steps": 1, "no_such": 1}, 4, 0.1, 0)


@pytest.mark.parametrize("spec", [{"aggregator": "fedadam"}, {"topology": "hierarchical"},
                                  {"compression": "int8"}])
def test_reference_refuses_what_it_does_not_implement(spec):
    with pytest.raises(ValueError):
        federation.check_spec(spec)
