"""The traffic generator: every seed offers the same load."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from perfbench import loadgen

TRAFFIC = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                      / "chat_open_0.8knee.json").read_text())


def _plan(seed, seconds=45.0):
    return loadgen.plan(TRAFFIC, seconds, np.random.default_rng(seed), 50304)


def _lengths(reqs, sampled):
    return Counter((len(r.prompt), r.max_new) for r in reqs
                   if r.sampled == sampled)


def test_two_seeds_offer_the_same_count_and_the_same_length_pairs():
    a, b = _plan(1), _plan(2 ** 31 + 7)
    n = round(TRAFFIC["rate_rps"] * 45.0)
    for reqs in (a, b):
        assert sum(r.sampled for r in reqs) == n
        assert sum(not r.sampled for r in reqs) == round(
            TRAFFIC["rate_rps"] * TRAFFIC["preroll_s"])
    for sampled in (True, False):
        assert _lengths(a, sampled) == _lengths(b, sampled)
    assert len(_lengths(a, True)) > 20          # lengths do vary


def test_seed_decides_order_instants_and_tokens():
    a, b = _plan(1), _plan(2)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert a[0].prompt != b[0].prompt
    again = _plan(1)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in again]


def test_lengths_follow_the_file():
    reqs = [r for r in _plan(3) if r.sampled]
    outs = sorted(r.max_new for r in reqs)
    spec = TRAFFIC["output_tokens"]
    assert spec["min"] <= outs[0] and outs[-1] <= spec["max"]
    assert abs(outs[len(outs) // 2] - spec["median"]) <= 2
    assert {len(r.prompt) for r in reqs} <= set(TRAFFIC["prompt_tokens"]["grid"])
    assert len(loadgen.prompt_lengths(7, TRAFFIC["prompt_tokens"])) == 7
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 45.0


def test_preroll_requests_are_due_before_the_window_and_never_sampled():
    reqs = _plan(4)
    pre = [r for r in reqs if r.due_s < 0]
    assert pre and all(not r.sampled for r in pre)
    assert all(r.sampled for r in reqs if r.due_s >= 0)
    outcomes = [loadgen.Outcome(r, 10.0 + r.due_s, 10.001 + r.due_s,
                                11.0 + r.due_s, 200, [1] * r.max_new, 5.0)
                for r in reqs]
    s = loadgen.summarize(outcomes)
    assert s["attempted"] == len(reqs) - len(pre)
    assert len(s["per_token_ms"]) == s["attempted"] and s["failed"] == 0


def test_lateness_is_reported_and_a_short_or_failed_answer_counts_failed():
    reqs = [r for r in _plan(5) if r.sampled][:3]
    outs = [loadgen.Outcome(reqs[0], 1.0, 1.002, 2.0, 200,
                            [1] * reqs[0].max_new, 4.0),
            loadgen.Outcome(reqs[1], 1.0, 1.010, 2.0, 200,
                            [1] * (reqs[1].max_new - 1), 4.0),
            loadgen.Outcome(reqs[2], 1.0, 1.001, 2.0, 0, [], None)]
    s = loadgen.summarize(outs)
    assert s["failed"] == 2 and s["attempted"] == 3
    assert s["late_ms"] == pytest.approx([2.0, 10.0, 1.0])
    assert s["per_token_ms"] == pytest.approx([1000.0 / reqs[0].max_new])


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert loadgen.percentile(xs, 50) == 50
    assert loadgen.percentile(xs, 90) == 90
    assert loadgen.percentile([3.0], 99) == 3.0
