"""CPU rehearsals of the serving cells at tiny sizes: the whole run but
the look for a chip, with the program as it is. A rehearsal reports no
metrics."""
import pytest

import chipbench_tiny as tiny


@pytest.mark.parametrize("cell", ["serve-qwen2-1.5b-chat-steady",
                                  "serve-qwen2-1.5b-code-overload"])
def test_serving_cell_rehearsal_is_correct(cell):
    res = tiny.run_serve(cell)
    assert res["correct"], res
    assert res["attempted"] == round(8.0 * 1.5) and res["failed"] == 0
    assert res["metrics"] == {}
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
