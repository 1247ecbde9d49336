"""The readers of the device's own line of programs (``readers/module_ms.py``,
``module_hbm_roofline.py``: the chip's ``XLA Modules`` events, one per
execution of a compiled program) on hand-made traces and on the recorded
one, and the five metrics that read the serving programs' names and the
loop's two child spans."""

import importlib
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import harness
from perfbench import trace as tr
from perfbench.readers import (hbm_roofline, module_hbm_roofline, module_ms,
                               span_stat)
from perfbench.trace import Event

DATA = Path(__file__).parent / "data"
TPU = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
BW = 819e9


def _run(modules, host=None, window=(0, 1000), devices=(TPU,), **facts):
    facts.update(trace=tr.Trace({}, {}, modules, host or {}),
                 trace_window=window)
    return SimpleNamespace(facts=facts, devices=list(devices))


def _step(start, end, fingerprint=7):
    return Event(f"jit_serve_step({fingerprint})", start, end)


# One chip's programs in a window 0-1000: three steps, two prefills of two
# lengths, an install, one of the dispatch's lazy ops; a second chip whose
# only step is long.
MODULES = {
    0: [_step(-50, 40),                                 # began before: whole
        Event("jit_serve_prefill_s1024(3)", 100, 400),
        Event("jit_serve_install(4)", 400, 410),
        _step(410, 510),
        Event("jit_argmax(5)", 510, 512),
        Event("jit_serve_prefill_s32(6)", 600, 620),
        _step(700, 830),
        _step(950, 1100)],                              # ends after: left out
    1: [_step(0, 900)]}


def test_module_ms_matches_by_name_on_the_first_chip_inside_the_window():
    run = _run(MODULES)
    assert module_ms.read(run, "serve_step") == \
        pytest.approx((90 + 100 + 130) / 3 * 1e-6)
    # every prompt length's program is a prefill
    assert module_ms.read(run, "serve_prefill") == \
        pytest.approx((300 + 20) / 2 * 1e-6)
    assert module_ms.read(run, "serve_prefill_s1024") == pytest.approx(300e-6)
    assert module_ms.read(run, "serve_install") == pytest.approx(10e-6)
    # without a window every event of the first chip counts
    assert module_ms.read(_run(MODULES, window=None), "serve_step") == \
        pytest.approx((90 + 100 + 130 + 150) / 4 * 1e-6)
    assert module_ms.runs(run.facts["trace"], "serve_step", (0, 1000)) == \
        [(-50, 40), (410, 510), (700, 830)]


def test_a_program_without_the_names_reads_nothing():
    # the parent's programs: jax names a jit of a functools.partial so
    parent = _run({0: [Event("jit__unknown(1)", 0, 100),
                       Event("jit__unknown(2)", 100, 400)]},
                  decode_turn_bytes=1e9)
    assert module_ms.read(parent, "serve_step") is None
    assert module_ms.read(parent, "serve_prefill") is None
    assert module_hbm_roofline.read(
        parent, "serve_step", "decode_turn_bytes") is None
    # a CPU trace has no device plane, an untraced run no trace
    assert module_ms.read(_run({}), "serve_step") is None
    assert module_ms.read(SimpleNamespace(facts={}), "serve_step") is None
    assert module_ms.read(_run({0: []}), "serve_step") is None


def test_roofline_is_the_facts_bytes_over_the_peak_and_the_modules_time():
    # 1 GB a step, steps of 2 ms: 500 GB/s of 819
    ms = 2_000_000
    run = _run({0: [_step(0, ms), _step(3 * ms, 4 * ms)]},
               window=(0, 10 * ms), decode_turn_bytes=1e9)
    share = module_hbm_roofline.read(run, "serve_step", "decode_turn_bytes")
    assert share == pytest.approx(100 * 1e9 / (BW * 2e-3))
    assert share == pytest.approx(61.05, abs=0.01)
    # no bytes counted, no chip, an unknown chip
    assert module_hbm_roofline.read(run, "serve_step", "nope") is None
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    for devices in ((), (cpu,)):
        assert module_hbm_roofline.read(
            _run(run.facts["trace"].modules, devices=devices,
                 decode_turn_bytes=1e9),
            "serve_step", "decode_turn_bytes") is None
    other = SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(ValueError):
        module_hbm_roofline.read(
            _run(run.facts["trace"].modules, window=(0, 10 * ms),
                 devices=(other,), decode_turn_bytes=1e9),
            "serve_step", "decode_turn_bytes")


def test_a_span_shorter_than_its_step_passes_100_and_the_module_does_not():
    """The case the readers exist for.  A loop that runs a step ahead
    comes back to a step that has been running all through its confirm,
    emit and frame: the ``serve.decode`` span holds what is LEFT of the
    step.  Over the span the needed bytes read more than the chip can
    move; over the device's own event they cannot."""
    ms = 1_000_000
    step, host = 8 * ms, 3 * ms          # the span opens 3 ms into a step
    modules, spans = [], []
    for k in range(5):
        modules.append(_step(k * step, (k + 1) * step))
        spans.append(Event("hvd:serve.decode", k * step + host,
                           (k + 1) * step))
    need = 0.9 * BW * step * 1e-9        # bytes of a step at 90 % of peak
    run = _run({0: modules}, {"loop": spans}, window=(0, 5 * step),
               decode_turn_bytes=need)
    by_span = hbm_roofline.read(run, "decode_turn_bytes", "hvd:serve.decode")
    by_module = module_hbm_roofline.read(
        run, "serve_step", "decode_turn_bytes")
    assert by_span == pytest.approx(90 * 8 / 5) and by_span > 100
    assert by_module == pytest.approx(90)
    assert module_ms.read(run, "serve_step") \
        - span_stat.read(run, "hvd:serve.decode", "mean_ms") == \
        pytest.approx(3.0)


def test_recorded_trace_gives_the_programs_device_time():
    path = DATA / "small_1chip.xplane.pb.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    t = tr.load(str(path))
    run = SimpleNamespace(facts={"trace": t, "trace_window": None,
                                 "bytes": 100e6}, devices=[TPU])
    # four executions of one program, named by jax after its function
    assert {e.name.split("(")[0] for e in t.modules[0]} == {"jit_prog"}
    ms = module_ms.read(run, "jit_prog")
    assert ms == pytest.approx(0.1472, rel=0.01)
    assert ms == pytest.approx(
        1e-6 * sum(e.end - e.start for e in t.modules[0]) / 4)
    assert module_ms.read(run, "serve_step") is None
    # the program's device time is its ops' busy time, to the gaps
    assert 4 * ms * 1e-3 == pytest.approx(tr.busy(t)["busy_s"][0], rel=0.02)
    assert module_hbm_roofline.read(run, "jit_prog", "bytes") == \
        pytest.approx(100 * 100e6 / (BW * ms * 1e-3))
    # inside a window that ends before the third program does: two
    first = t.modules[0][0].start
    run.facts["trace_window"] = (first, t.modules[0][2].end - 1)
    assert len(module_ms.runs(t, "jit_prog", run.facts["trace_window"])) == 2


NEW = {"decode_step_device_ms.serve": (module_ms, "device_trace", 4),
       "decode_step_hbm_roofline_pct.serve":
           (module_hbm_roofline, "device_trace", 3),
       "prefill_device_ms_mean.serve": (module_ms, "device_trace", 4),
       "serve_dispatch_ms_mean.serve": (span_stat, "program_span", 4),
       "serve_read_wait_ms_mean.serve": (span_stat, "program_span", 4)}


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_has_its_file_its_reader_and_its_cells(name):
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    reader, source, cells = NEW[name]
    spec = harness.load_json(harness.HERE / "metrics" / f"{name}.json")
    assert importlib.import_module(
        f"perfbench.readers.{spec['reader']}") is reader
    assert entry["source"] == source and len(entry["workloads"]) == cells
    assert entry["moves"] == "latency_per_token_p50"
    serving = {w["name"] for w in bench["workloads"] if "_serve_" in w["name"]}
    assert set(entry["workloads"]) <= serving
    # the layer is one BENCHMARK.json had, letter for letter
    assert sum(m["layer"] == entry["layer"] for m in bench["per_layer"]) > 5
    # the engine's names are what the readers match
    from horovod_tpu.serving import decode

    if "match" in spec["args"]:
        assert spec["args"]["match"] in (decode.STEP_PROGRAM,
                                         decode.PREFILL_PROGRAM)
