"""readers/step_phase.py on hand-made events and a hand-made map: a
training step's device time by phase is the self time of the first chip's
ops inside the step program's executions, joined by instruction name to
the phases the program remembers; and the twelve metric files that read it
name the reader, a phase and their cells."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import trace as tr
from perfbench.readers import step_phase

ROOT = Path(__file__).resolve().parents[2]
PHASES = ("forward", "recompute", "backward", "reduce", "optimizer", "other")
IMG = ("forward", "backward", "optimizer", "reduce", "mixed", "other")
TOK = ("forward", "recompute", "backward", "optimizer", "mixed", "other")


class Scope(SimpleNamespace):
    """What ``programs.scopes`` maps an instruction to."""


def S(phase, mixed=False):
    return Scope(op_name="x", phase=phase, mixed=mixed)


MAP = {"fusion.1": S("forward"), "fusion.2": S("backward"),
       "multiply_add_fusion.3": S("backward", mixed=True),
       "fusion.4": S("optimizer"), "all-reduce.3": S("reduce"),
       "while.5": S("backward"), "fusion.6": S("recompute"),
       "copy-done.7": S("other")}


def op(name, start_ms, ms, opcode="fusion"):
    return (f"%{name} = f32[8,128]{{1,0:T(8,128)}} {opcode}(%a, %b)",
            1e6 * start_ms, 1e6 * (start_ms + ms))


def step(at):
    """One step of 20 ms from ``at`` ms: 13.5 ms busy."""
    return [op("fusion.1", at + 0, 3),
            op("while.5", at + 3, 6, "while"),      # holds the next two
            op("fusion.6", at + 4, 2),
            op("fusion.2", at + 6, 1),
            op("multiply_add_fusion.3", at + 10, 2),
            op("all-reduce.3", at + 12, 1, "all-reduce"),
            op("fusion.4", at + 13, 1),
            op("copy-done.7", at + 14, 0.25, "copy-done"),
            op("not_in_the_map.8", at + 15, 0.25)]


MODULES = [("jit_train_step_lm(123)", 0.0, 20e6),
           ("jit_fence(9)", 20e6, 21e6),
           ("jit_train_step_lm(123)", 30e6, 50e6)]
EVENTS = step(0) + step(30) + [op("fusion.1", 20.2, 0.5)]   # the fence's


def traced(events=EVENTS, modules=MODULES, steps=2, window=(0.0, 60e6)):
    return SimpleNamespace(facts={
        "trace": tr.Trace(
            {0: [tr.Event(*e) for e in sorted(events, key=lambda e: e[1])],
             1: [tr.Event(*op("fusion.1", 0, 19))]}, {},
            {0: [tr.Event(*m) for m in modules]}, {}),
        "trace_window": window, "steps": steps})


def table(run, scopes_of=lambda module: MAP):
    return step_phase.by_phase(
        step_phase.step_ops(run.facts["trace"], run.facts["trace_window"]),
        scopes_of, run.facts["steps"])


def test_phases_of_a_step():
    got = table(traced())
    assert got["forward"] == pytest.approx(3.0)
    assert got["recompute"] == pytest.approx(2.0)
    # the while's self time (6 - 2 - 1), its body's backward op, the
    # weight-gradient fusion
    assert got["backward"] == pytest.approx(3.0 + 1.0 + 2.0)
    assert got["reduce"] == pytest.approx(1.0)
    assert got["optimizer"] == pytest.approx(1.0)
    # no metadata, and not in the map at all
    assert got["other"] == pytest.approx(0.5)
    # counted under its own phase too
    assert got["mixed"] == pytest.approx(2.0)
    assert got["nothing_of_that_name"] == 0.0


def test_the_six_phases_add_up_to_the_steps_busy_time():
    """Busy time: the union of the chip's op intervals inside the two
    executions (the fence's op lies between them), a step."""
    run = traced()
    got = table(run)
    inside = [e for e in run.facts["trace"].ops[0]
              if e.end <= 20e6 or e.start >= 30e6]
    busy = tr.total(tr.union((e.start, e.end) for e in inside))
    assert sum(got[p] for p in PHASES) == pytest.approx(1e-6 * busy / 2)
    assert sum(got[p] for p in PHASES) == pytest.approx(13.5)


def test_ops_outside_a_step_programs_execution_are_left_out():
    """The fence's ``fusion.1`` (another program's instruction of the same
    name) and a step that ends after the window are not counted; nor is
    the second chip."""
    late = [("jit_train_step_lm(123)", 55e6, 75e6)]
    got = table(traced(EVENTS + step(55), MODULES + late))
    assert got["forward"] == pytest.approx(3.0)
    assert sum(got[p] for p in PHASES) == pytest.approx(13.5)
    rows = step_phase.step_ops(traced().facts["trace"], (0.0, 60e6))
    assert {m for m, _, _ in rows} == {"jit_train_step_lm"}
    assert len(rows) == 2 * len(step(0))


def test_each_module_is_looked_up_under_its_own_name():
    modules = [("jit_train_step_resnet_hvd(7)", 0.0, 20e6), MODULES[2]]
    asked = []

    def scopes_of(module):
        asked.append(module)
        return MAP if module == "jit_train_step_lm" else {}

    got = table(traced(modules=modules), scopes_of)
    assert sorted(asked) == ["jit_train_step_lm", "jit_train_step_resnet_hvd"]
    # the unmapped program's ops are all ``other``
    assert got["other"] == pytest.approx((13.5 + 0.5) / 2)
    assert got["forward"] == pytest.approx(1.5)


@pytest.mark.parametrize("why", ["empty map", "no step program", "no steps",
                                 "no trace", "no device plane"])
def test_nothing_to_read_is_none(why, monkeypatch):
    """The parent remembers nothing and a CPU rehearsal has no device
    plane: None, not an error, and the line leaves the metric out."""
    from horovod_tpu.telemetry import programs

    monkeypatch.setattr(programs, "scopes",
                        lambda m: {} if why == "empty map" else MAP)
    run = traced(modules=MODULES[1:2] if why == "no step program"
                 else MODULES, steps=0 if why == "no steps" else 2)
    if why == "no trace":
        run.facts["trace"] = None
    if why == "no device plane":
        run.facts["trace"] = tr.Trace({}, {}, {}, {})
    assert step_phase.read(run, "forward") is None
    assert step_phase.read(run, "other") is None


def test_read_joins_the_programs_own_map(monkeypatch):
    from horovod_tpu.telemetry import programs

    asked = []
    monkeypatch.setattr(programs, "scopes",
                        lambda m: asked.append(m) or MAP)
    run = traced()
    assert step_phase.read(run, "backward") == pytest.approx(6.0)
    assert step_phase.read(run, "mixed") == pytest.approx(2.0)
    assert step_phase.read(run, "reduce") == pytest.approx(1.0)
    assert asked == ["jit_train_step_lm"]       # parsed once a run


def test_a_tree_without_the_module_reads_none(monkeypatch):
    """The benchmark's files are laid over the parent's checkout too."""
    import builtins

    real = builtins.__import__

    def no_programs(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "horovod_tpu.telemetry" and "programs" in (fromlist or ()):
            raise ImportError("cannot import name 'programs'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_programs)
    assert step_phase.read(traced(), "forward") is None


@pytest.mark.parametrize("suffix, phases, cells", [
    ("img", IMG, ["resnet50_b128_1chip", "resnet50_b128_dp4"]),
    ("tok", TOK, ["olmo-1b_train_s2048"])])
def test_every_new_metric_has_its_file_its_reader_and_its_cells(
        suffix, phases, cells):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    moves = {"img": "images_per_s", "tok": "tokens_per_s"}[suffix]
    for phase in phases:
        name = f"step_{phase}_ms.{suffix}"
        spec = json.loads(
            (ROOT / "perfbench" / "metrics" / f"{name}.json").read_text())
        assert spec == {"reader": "step_phase", "args": {"phase": phase}}
        m = entries[name]
        assert m["workloads"] == cells and m["moves"] == moves
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "ms", "lower", "device_trace", "step makers (parallel/train.py)")
    ours = [n for n in entries if n.startswith("step_")
            and n.endswith(f"_ms.{suffix}")]
    assert len(ours) == 6
