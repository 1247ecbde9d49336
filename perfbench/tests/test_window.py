"""The training window on a stand-in step: every dispatched step is
counted, and dispatching stops when the work in flight reaches the
window's end."""

import time
from types import SimpleNamespace

import numpy as np

from perfbench.jobs import _train


def test_window_counts_every_step_and_ends_on_time():
    calls = []

    def step(state, *_batch):
        time.sleep(0.01)
        calls.append(time.perf_counter())
        return SimpleNamespace(step=state.step + 1), np.float32(0.0)

    ends = {}
    run = SimpleNamespace(
        seconds=1.0, end_to_end={}, attempted=0,
        cell=SimpleNamespace(traffic={"ahead_seconds": 0.2}),
        setup_done=time.perf_counter,
        window_done=lambda: ends.setdefault("t", time.perf_counter()))
    s = SimpleNamespace(compiled=step, batch=(), items_per_step=8,
                        rate_metric="items_per_s")
    t0 = time.perf_counter()
    state = _train.window(run, s, SimpleNamespace(step=0), step_s=0.01)
    assert state.step == len(calls) == run.attempted
    assert 0.9 <= ends["t"] - t0 <= 1.5
    rate = run.end_to_end["items_per_s"]
    assert 0.7 * 800 <= rate <= 800
