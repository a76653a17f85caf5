"""A serving run with the timed path broken underneath must come out not
correct: a token altered where it is produced, and a decode step that
returns its cache unchanged."""
import pytest

from benchmarks.chip import faults

import chipbench_tiny as tiny


@pytest.mark.parametrize("fault", ["token", "stale_state"])
def test_serving_fault_is_not_correct(fault):
    undo = faults.plant(fault)
    try:
        res = tiny.run_serve("serve-qwen2-1.5b-chat-steady")
    finally:
        undo()
    assert res["correct"] is False, res
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
