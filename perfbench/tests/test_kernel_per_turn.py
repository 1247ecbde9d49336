"""readers/kernel_per_turn.py on hand-made events: a kernel that a decode
step and a prompt's program both call is timed a TURN from the calls of a
turn's rows inside the step's executions, and the two metric files that
read ``routed_ffn_rows`` with it name facts the job makes."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import trace as tr
from perfbench.readers import kernel_per_turn

CALL = ('%{name}.{n} = bf16[{rows},2048]{{1,0:T(8,128)(2,1)}} '
        'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
FACTS = {"routed_product_rows": 768, "routed_product_bytes": 819e6,
         "trace_window": (0.0, 100e6)}
ARGS = ("routed_ffn_rows", "routed_product_rows")


class _Traced:
    def __init__(self, events, modules, facts, platform="tpu"):
        self.facts = {"trace": tr.Trace(
            {0: [tr.Event(*e) for e in sorted(events, key=lambda e: e[1])]},
            {}, {0: [tr.Event(*m) for m in modules]}, {}), **facts}
        self.devices = [SimpleNamespace(platform=platform,
                                        device_kind="TPU v5 lite")]


def call(n, start, ms, rows=768, name="routed_ffn_rows"):
    return (CALL.format(name=name, n=n, rows=rows), start, start + 1e6 * ms)


# two steps of 10 ms with two calls of 1 ms each, a prefill between them
MODULES = [("jit_serve_step(1)", 0.0, 10e6),
           ("jit_serve_prefill(2)", 20e6, 30e6),
           ("jit_serve_step(1)", 40e6, 50e6)]
TURNS = [call(1, 1e6, 1), call(2, 3e6, 1), call(1, 41e6, 1),
         call(2, 43e6, 1)]


def test_a_turn_is_the_steps_calls_of_a_turns_rows():
    """Four calls in two steps: 2 ms a turn, and 819 MB over 819 GB/s x
    2 ms is half the roofline.  Not counted: a prompt's call of 768 rows
    (inside the prefill's execution), a call of another row count inside a
    step, another kernel of 768 rows, an XLA fusion of that name, and a
    step that ends after the window."""
    events = TURNS + [
        call(3, 21e6, 5),
        call(4, 5e6, 2, rows=512),
        call(5, 7e6, 2, name="ssd_step"),
        ("%routed_ffn_rows.9 = bf16[768,2048]{1,0} fusion(%a)", 8e6, 9e6),
        call(1, 96e6, 1)]
    run = _Traced(events, MODULES + [("jit_serve_step(1)", 95e6, 105e6)],
                  FACTS)
    assert kernel_per_turn.read(run, "ms_per_turn", *ARGS) \
        == pytest.approx(2.0)
    assert kernel_per_turn.read(run, "hbm_roofline_pct", *ARGS,
                                "routed_product_bytes") \
        == pytest.approx(50.0)


def test_a_call_that_holds_other_events_counts_its_own_time():
    """Self time: an event nested inside a call is not the call's."""
    events = TURNS + [("%inner = f32[8]{0} add(%a, %b)", 1.2e6, 1.7e6)]
    run = _Traced(events, MODULES, FACTS)
    assert kernel_per_turn.read(run, "ms_per_turn", *ARGS) \
        == pytest.approx(1.75)


@pytest.mark.parametrize("why", ["no such call", "no step", "no facts",
                                 "no bytes", "no chip", "no trace"])
def test_nothing_to_read_is_none(why):
    """The parent of the PR that adds the kernel has no such call: None,
    not an error; so has a program that does not count."""
    events = [call(1, 1e6, 1, name="ragged-dot-none")] \
        if why == "no such call" else TURNS
    modules = [MODULES[1]] if why == "no step" else MODULES
    facts = dict(FACTS)
    if why == "no facts":
        facts = {"trace_window": FACTS["trace_window"]}
    if why == "no bytes":
        del facts["routed_product_bytes"]
    run = _Traced(events, modules, facts,
                  "cpu" if why == "no chip" else "tpu")
    if why == "no trace":
        run.facts["trace"] = None
    assert kernel_per_turn.read(run, "hbm_roofline_pct", *ARGS,
                                "routed_product_bytes") is None


def test_an_unknown_reading_is_an_error():
    with pytest.raises(ValueError):
        kernel_per_turn.read(_Traced(TURNS, MODULES, FACTS), "gb", *ARGS)


@pytest.mark.parametrize("metric,what", [
    ("routed_ffn_ms_per_turn.serve", "ms_per_turn"),
    ("routed_ffn_hbm_roofline_pct.serve", "hbm_roofline_pct")])
def test_the_metric_files_read_the_kernel_with_the_jobs_facts(metric, what):
    root = Path(__file__).resolve().parents[1]
    spec = json.loads((root / "metrics" / f"{metric}.json").read_text())
    assert spec["reader"] == "kernel_per_turn"
    assert spec["args"]["what"] == what
    run = _Traced(TURNS, MODULES, FACTS)
    assert kernel_per_turn.read(run, **spec["args"]) == pytest.approx(
        2.0 if what == "ms_per_turn" else 50.0)
    entry = {m["name"]: m for m in json.loads(
        (root.parent / "BENCHMARK.json").read_text())["per_layer"]}[metric]
    assert entry["workloads"] == ["lfm2-8b-a1b_serve_assistants"]
    assert entry["moves"] == "latency_per_token_p50"
    assert entry["source"] == "device_trace"
