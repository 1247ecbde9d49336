"""The readers of the program's own spans and kernel names
(``readers/span_stat.py``, ``idle_under.py``, ``kernel_ms.py``) on
hand-made traces, and a CPU rehearsal of the serving cell that reports the
span means under their ``rehearsal_`` names."""

import json
from types import SimpleNamespace

import pytest

from perfbench import harness
from perfbench import trace as tr
from perfbench.readers import idle_under, kernel_ms, span_stat
from perfbench.trace import Event

LEAVES = ["hvd:serve.frame", "hvd:serve.prefill", "hvd:serve.decode",
          "hvd:serve.confirm", "hvd:serve.emit"]


def _run(ops, host, window=(0, 100), steps=None):
    facts = {"trace": tr.Trace(ops, {}, {}, host), "trace_window": window}
    if steps:
        facts["steps"] = steps
    return SimpleNamespace(facts=facts)


# One chip busy 0-30, 40-60, 90-120 of a window 0-100: idle 30-40 and 60-90,
# 40 % of the window.  The loop thread's spans and a second host line whose
# span overlaps the loop's decode.
OPS = {0: [Event("%f = f32[] fusion()", 0, 30),
           Event("%g = f32[] fusion()", 40, 60),
           Event("%h = f32[] fusion()", 90, 120)]}
HOST = {
    "loop": [Event("hvd:serve.frame", -10, 2),        # straddles the start
             Event("hvd:serve.decode", 28, 36),       # idle 30-36
             Event("hvd:serve.confirm", 36, 38),      # idle 36-38
             Event("hvd:serve.emit", 38, 39),         # idle 38-39
             Event("hvd:serve.frame", 39, 41),        # idle 39-40
             Event("hvd:serve.prefill", 55, 70),      # idle 60-70
             Event("hvd:serve.decode", 70, 85),       # idle 70-85
             Event("hvd:serve.decode", 95, 130)],     # straddles the end
    "door": [Event("hvd:serve.queued", 10, 50),
             Event("hvd:serve.decode", 80, 88),       # overlaps the loop's
             Event("bench:trace_window", 0, 100)],
}


def test_span_stat_takes_the_spans_that_end_in_the_window():
    run = _run(OPS, HOST)
    mean = span_stat.read(run, "hvd:serve.decode", "mean_ms")
    # 28-36, 70-85, 80-88 end inside; 95-130 ends after the window
    assert mean == pytest.approx((8 + 15 + 8) / 3 * 1e-6)
    # a span that began before the window and ends in it counts whole
    assert span_stat.read(run, "hvd:serve.frame", "mean_ms") == \
        pytest.approx((12 + 2) / 2 * 1e-6)
    assert span_stat.read(run, "hvd:serve.queued", "mean_ms") == \
        pytest.approx(40e-6)
    assert span_stat.read(run, "hvd:serve.nope", "mean_ms") is None
    assert span_stat.read(run, "hvd:serve", "mean_ms") is None   # exact name
    with pytest.raises(ValueError):
        span_stat.read(run, "hvd:serve.decode", "p99")


def test_idle_shares_add_up_to_the_idle_share():
    run = _run(OPS, HOST)
    decode = idle_under.read(run, ["hvd:serve.decode"])
    # 30-36 and 70-85 from the loop, 85-88 more from the other line's span:
    # a union, so the stretch both cover (80-85) counts once
    assert decode == pytest.approx(6 + 15 + 3)
    prefill = idle_under.read(run, ["hvd:serve.prefill"])
    confirm = idle_under.read(run, ["hvd:serve.confirm"])
    book = idle_under.read(run, ["hvd:serve.frame", "hvd:serve.emit"])
    rest = idle_under.read(run, LEAVES, invert=True)
    assert (prefill, confirm, book) == pytest.approx((10, 2, 2))
    assert rest == pytest.approx(2)                   # 88-90, nobody's
    idle = 100.0 * tr.busy(run.facts["trace"], (0, 100))["idle_share"][0]
    assert decode + prefill + confirm + book + rest == pytest.approx(idle)


def test_idle_under_clips_spans_at_the_windows_edges():
    ops = {0: [Event("%f = f32[] fusion()", 20, 80)]}   # idle 0-20, 80-100
    host = {"loop": [Event("hvd:serve.decode", -50, 10),
                     Event("hvd:serve.decode", 90, 500)]}
    run = _run(ops, host)
    assert idle_under.read(run, ["hvd:serve.decode"]) == pytest.approx(20)
    assert idle_under.read(run, ["hvd:serve.decode"], invert=True) == \
        pytest.approx(20)


def test_a_program_without_the_spans_or_a_chip_without_ops_reads_nothing():
    bare = {"loop": [Event("bench:request", 0, 50)]}
    assert idle_under.read(_run(OPS, bare), LEAVES) is None
    assert idle_under.read(_run(OPS, bare), LEAVES, invert=True) is None
    assert idle_under.read(_run({}, HOST), LEAVES) is None     # a CPU trace
    assert idle_under.read(_run(OPS, HOST, window=None), LEAVES) is None
    assert kernel_ms.read(_run({}, HOST, steps=2), "flash_fwd") is None
    # a chip that ran nothing in the window is idle throughout
    quiet = _run({0: []}, HOST)
    assert idle_under.read(quiet, LEAVES) \
        + idle_under.read(quiet, LEAVES, invert=True) == pytest.approx(100)
    no_trace = SimpleNamespace(facts={})
    assert span_stat.read(no_trace, "hvd:serve.decode", "mean_ms") is None
    assert idle_under.read(no_trace, LEAVES) is None
    assert kernel_ms.read(no_trace, "flash_fwd") is None


def _mosaic(name, operands, start, end):
    return Event(f"%{name} = bf16[8,256,64]{{2,1,0}} custom-call({operands}), "
                 'custom_call_target="tpu_custom_call"', start, end)


def test_kernel_ms_tells_the_three_kernels_apart_by_instruction_name():
    ops = {0: [Event("%while.1 = () while()", 0, 1000),
               _mosaic("flash_fwd.3", "%q, %k, %v", 0, 100),
               _mosaic("flash_fwd.4", "%q, %k, %v", 100, 250),
               # an operand named after another kernel is not a match
               _mosaic("flash_bwd_dq.1", "%flash_fwd.3, %do", 300, 500),
               _mosaic("flash_bwd_dkv.1", "%q, %do", 500, 900),
               Event("%f = f32[] fusion()", 900, 1000)],
           1: [_mosaic("flash_fwd.3", "%q", 0, 999)]}     # first chip only
    run = _run(ops, {}, window=(0, 1000), steps=2)
    fwd = kernel_ms.read(run, "flash_fwd")
    dq = kernel_ms.read(run, "flash_bwd_dq")
    dkv = kernel_ms.read(run, "flash_bwd_dkv")
    assert (fwd, dq, dkv) == pytest.approx((125e-6, 100e-6, 200e-6))
    total_s, calls = tr.op_seconds(run.facts["trace"], 0, tr.is_mosaic_call,
                                   (0, 1000))
    assert calls == 4
    assert (fwd + dq + dkv) * 2 == pytest.approx(total_s * 1e3)
    # the parent's anonymous kernels: nothing to read, not zero
    anon = _run({0: [_mosaic("branch_0_fun.37", "%q", 0, 100)]}, {},
                window=(0, 1000), steps=2)
    assert kernel_ms.read(anon, "flash_fwd") is None


def test_every_new_metric_has_its_file_and_its_reader():
    import importlib

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    new = [m for m in bench["per_layer"]
           if m["name"].startswith(("serve_", "idle_", "flash_fwd_",
                                    "flash_bwd_"))]
    assert len(new) == 12
    for m in new:
        spec = harness.load_json(
            harness.HERE / "metrics" / f"{m['name']}.json")
        reader = importlib.import_module(
            f"perfbench.readers.{spec['reader']}")
        assert reader in (span_stat, idle_under, kernel_ms)
        assert m["better"] == "lower" and m["workloads"]


@pytest.mark.timeout(300)
def test_a_traced_rehearsal_of_the_serving_cell_reports_the_span_means(
        capsys):
    harness.main(["--workload", "olmo-1b_serve_chat", "--seed", "2147483659",
                  "--seconds", "4", "--trace", "1", "--rehearsal"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0
    metrics = out["metrics"]
    for name in ("rehearsal_serve_decode_ms_mean.serve",
                 "rehearsal_serve_confirm_ms_mean.serve",
                 "rehearsal_serve_prefill_ms_mean.serve",
                 "rehearsal_serve_queue_wait_ms_mean.serve"):
        assert metrics[name]["value"] > 0 and metrics[name]["unit"] == "ms"
    # the CPU has no device line: no idle share under any name
    assert not [k for k in metrics if "idle" in k]
    # a decode step, its confirm and the prefills fit inside the turn
    assert metrics["rehearsal_serve_decode_ms_mean.serve"]["value"] \
        + metrics["rehearsal_serve_confirm_ms_mean.serve"]["value"] \
        < 2 * metrics["rehearsal_server_step_ms_mean.serve"]["value"]
