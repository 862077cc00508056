"""Kill-at-round-t golden resume matrix, continued: the ``compact_state``
half of the {sync, async} × {flat, hierarchical} matrix and the cells of
the selector-zoo policies. The contract, the harness and the f32-state half
are in ``test_resume_matrix.py``: killed after round t and resumed from its
checkpoint, a run must reproduce the uninterrupted run **bitwise**.
"""

import pytest

from conftest import (
    KILL_AT,
    MATRIX,
    assert_bitwise_resume,
    make_spec_factory,
)


@pytest.mark.parametrize("policy,topology", MATRIX)
@pytest.mark.parametrize("compact", [True], ids=["compact"])
def test_kill_at_round_t_resumes_bitwise(matrix_setup, preempt_harness,
                                         policy, topology, compact):
    make_spec = make_spec_factory(matrix_setup, policy, topology, compact)
    full, resumed, engine = preempt_harness(make_spec([]), KILL_AT)
    assert_bitwise_resume(full, resumed, engine, compact=compact)


# The zoo selectors added by ISSUE 10 must honor the same golden-resume
# contract. 'adaptive' × hierarchical is the hard cell: the snapshot must
# also carry the edge-budget controller's EMA utilities and the retuned
# per-edge budgets (HierarchicalEngine.extra_state).
NEW_SELECTOR_CELLS = [
    ("filtered", "sync", "flat"),
    ("adaptive", "async", "flat"),
    ("adaptive", "sync", "hierarchical"),
]


@pytest.mark.parametrize("selector,policy,topology", NEW_SELECTOR_CELLS)
def test_kill_at_round_t_resumes_bitwise_new_selectors(
        matrix_setup, preempt_harness, selector, policy, topology):
    make_spec = make_spec_factory(matrix_setup, policy, topology, False,
                                  selector=selector)
    full, resumed, engine = preempt_harness(make_spec([]), KILL_AT)
    assert_bitwise_resume(full, resumed, engine, compact=False)
