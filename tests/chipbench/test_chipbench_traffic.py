"""The seeded open-loop generator."""
import numpy as np
import pytest

from benchmarks.chip.traffic import openloop

import chipbench_tiny as tiny


@pytest.mark.parametrize("name", ["chat-steady", "code-overload"])
def test_same_seed_same_arrivals(name):
    mix = tiny.load("traffic", name)
    a = openloop.generate(mix, 2 ** 33 + 5, 40, 151936)
    b = openloop.generate(mix, 2 ** 33 + 5, 40, 151936)
    assert [(r.due_s, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.max_new, r.prompt.tolist()) for r in b]


@pytest.mark.parametrize("name", ["chat-steady", "code-overload"])
def test_lengths_follow_the_mix(name):
    mix = tiny.load("traffic", name)
    reqs = openloop.generate(mix, 11, 40, 151936)
    assert len(reqs) == round(mix["rate_per_s"] * 40)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    for v, spec in ((p, mix["prompt_tokens"]), (o, mix["output_tokens"])):
        assert v.min() >= spec["min"] and v.max() <= spec["max"]
        assert abs(np.median(v) - spec["median"]) <= 0.02 * spec["median"] + 1
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] < 40
    # Mean gap is the rate's within a few percent (stratified draws).
    assert abs(np.diff(due).mean() * mix["rate_per_s"] - 1) < 0.05


def test_seeds_change_the_order_not_the_work():
    mix = tiny.load("traffic", "chat-steady")
    a = openloop.generate(mix, 1, 40, 151936)
    b = openloop.generate(mix, 2, 40, 151936)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt[:8].tolist() != b[0].prompt[:8].tolist()


def test_tokens_are_in_vocab():
    mix = tiny.load("traffic", "chat-steady")
    reqs = openloop.generate(mix, 3, 5, 1000)
    assert all(r.prompt.min() >= 0 and r.prompt.max() < 1000 for r in reqs)


def test_a_mix_for_another_generator_is_refused():
    with pytest.raises(ValueError):
        openloop.generate({"generator": "back_to_back"}, 1, 5, 10)
