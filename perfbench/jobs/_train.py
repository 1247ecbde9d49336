"""What the training jobs share: the first three steps through the
window's own compiled call, the measured window, and the comparison with
the reference after the window.

A training job module gives ``build(run) -> TrainSetup``; everything a
cell's training run does beyond that is here.
"""

from __future__ import annotations

import collections
import math
import sys
import time
from typing import Any, Callable, Dict, NamedTuple, Tuple

import numpy as np

from perfbench import compare

N_FIRST_STEPS = 3
MIN_CHAIN_S = 0.3        # a host-clock reading spans at least this long


class TrainSetup(NamedTuple):
    compiled: Callable            # (state, *batch) -> (state, loss)
    state: Any
    batch: Tuple
    items_per_step: int           # images or tokens, all chips together
    rate_metric: str              # the end-to-end metric's name
    first_grad_norms: Callable    # state after step 1 -> {leaf: norm}
    delta_norms: Callable         # state after step 3 -> {leaf: norm}
    reference: Callable           # () -> reference dict (after the window)
    limits: Dict
    flops_per_item: float


def _fence(loss) -> float:
    """A host readback: it cannot complete before the chain has."""
    return float(np.asarray(loss).ravel()[0])


def first_steps(s: TrainSetup) -> Tuple[Any, Dict, float]:
    """The reference follows these; they are also the warm-up."""
    state = s.state
    losses, first, step_s = [], None, 0.0
    for i in range(N_FIRST_STEPS):
        t0 = time.perf_counter()
        state, loss = s.compiled(state, *s.batch)
        losses.append(_fence(loss))
        step_s = time.perf_counter() - t0
        if i == 0:
            first = s.first_grad_norms(state)
    prog = {"losses": losses, "first_grad_norm": first,
            "delta_norm": s.delta_norms(state)}
    return state, prog, step_s


def window(run, s: TrainSetup, state, step_s: float):
    """Chains of steps, each ended by a fence.  ``ahead_seconds`` of work
    (the traffic file's) stay dispatched beyond the chain being fenced, as
    a training loop that never reads its loss back would have them: the
    device does not wait for the host, nor for a host that stalls for a
    second or two (PERF.md, finding 8).  Dispatching stops when the work
    in flight reaches the window's end; the rate is every step between
    the first and the last fence over the time between them."""
    chain = max(1, math.ceil(MIN_CHAIN_S / max(step_s, 1e-4)))
    ahead = float(run.cell.traffic["ahead_seconds"])
    depth = 1 + max(1, math.ceil(ahead / (chain * max(step_s, 1e-4))))

    def dispatch(state):
        for _ in range(chain):
            state, loss = s.compiled(state, *s.batch)
        return state, loss

    _fence(state.step)
    t0 = t = run.setup_done()
    pending = collections.deque()
    done, chain_s, longest = 0, chain * step_s, 0.0
    while True:
        while (len(pending) < depth
               and t - t0 + len(pending) * chain_s < run.seconds):
            state, loss = dispatch(state)
            pending.append(loss)
        if not pending:
            break
        _fence(pending.popleft())
        done += chain
        now = time.perf_counter()
        longest, t = max(longest, now - t), now
        chain_s = (t - t0) / done * chain
    run.window_done()
    print(f"window: {done} steps in chains of {chain}, {depth} chains in "
          f"flight; a chain took {chain_s:.4f} s, the longest wait for a "
          f"fence {longest:.4f} s", file=sys.stderr, flush=True)
    run.attempted = done
    run.end_to_end[s.rate_metric] = done * s.items_per_step / (t - t0)
    return state


def traced_window(run, s: TrainSetup, state):
    """The traced run: every step fenced on its own and timed by the host,
    under the profiler, for the traffic file's ``trace_seconds``."""
    import jax

    span = min(run.seconds, float(run.cell.traffic["trace_seconds"]))
    _fence(state.step)
    run.start_trace()
    t0 = run.setup_done()
    step_ms = []
    with jax.profiler.TraceAnnotation("bench:trace_window"):
        t = t0
        while t - t0 < span:
            with jax.profiler.TraceAnnotation("bench:step"):
                state, loss = s.compiled(state, *s.batch)
            with jax.profiler.TraceAnnotation("bench:fence"):
                _fence(loss)
            now = time.perf_counter()
            step_ms.append((now - t) * 1e3)
            t = now
    run.window_done()
    run.stop_trace()
    run.load_trace()
    run.attempted = len(step_ms)
    rate = len(step_ms) * s.items_per_step / (t - t0)
    run.end_to_end[s.rate_metric] = rate
    run.facts.update(step_ms=step_ms, steps=len(step_ms), rate=rate,
                     flops_per_item=s.flops_per_item,
                     items_per_step=s.items_per_step)
    return state


def run_training(run, build: Callable) -> None:
    s = build(run)
    state, prog, step_s = first_steps(s)
    run.settle()
    if not all(math.isfinite(x) for x in prog["losses"]):
        run.failed = 1
    state = traced_window(run, s, state) if run.trace else \
        window(run, s, state, step_s)
    # The reference runs after the window, once the program's state is
    # freed: its time is not set-up and the peak stays the program's.
    del state
    ref = s.reference()
    run.checks = compare.training_checks(prog, ref, s.limits)
