"""The ``ssm_lm`` family's benchmark files: the cell's rehearsal runs to a
``correct`` result line with the metrics it lists, the bytes a decode turn
must move against a hand count, and the new reader against a made-up
trace."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import bytes_count, harness
from perfbench import trace as tr
from perfbench.readers import hbm_roofline

CELL = "jamba2-3b_serve_reason"
CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "jamba2-3b.json").read_text())


def _run_cell(capsys, trace):
    harness.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                  "3", "--trace", trace, "--rehearsal"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal_is_correct_and_reports_its_end_to_end_metrics(capsys):
    out = _run_cell(capsys, "0")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_setup_s",
                                   "rehearsal_latency_per_token_p50"}


def test_traced_rehearsal_reports_every_metric_the_cell_lists(capsys):
    """Every per-layer metric that lists the cell, but the three that only
    a chip's trace or peak can give."""
    out = _run_cell(capsys, "1")
    assert out["correct"] is True
    listed = {m["name"] for m in harness.Cell(CELL).metrics("per_layer")}
    chip_only = {"device_idle_pct.serve", "peak_hbm_gb.serve",
                 "decode_hbm_roofline_pct.serve"}
    idle = {n for n in listed if n.startswith("idle_")}
    got = {k[len("rehearsal_"):] for k in out["metrics"]}
    assert got >= listed - chip_only - idle, listed - got
    m = out["metrics"]
    # one slot's state at the rehearsal's sizes: 6 Mamba layers x 128
    # channels x (4 states float32 + 3 inputs bfloat16), 2 x 2 lanes of
    # 64 x 16 bfloat16, for 4 slots
    assert m["rehearsal_recurrent_state_gb.serve"]["value"] \
        == pytest.approx(4 * 6 * 128 * (4 * 4 + 3 * 2) / 1e9)
    assert m["rehearsal_kv_cache_gb.serve"]["value"] \
        == pytest.approx(4 * 2 * 2 * 64 * 16 * 2 / 1e9)
    assert m["rehearsal_prefill_ms_per_ktoken.serve"]["value"] > 0


def test_the_configuration_holds_the_catalog_row_unchanged():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "AI21-Jamba2-3B")
    assert CONFIG["source"] == row["source_url"]
    assert {k: CONFIG[k] for k in row["config"]} == row["config"]
    assert CONFIG["reduced"] == {} and CONFIG["torch_dtype"] == "bfloat16"


def test_parameters_by_hand():
    p = bytes_count.ssm_lm_params(CONFIG)
    # in 2560 x 10240; conv 5120 x 4 + 5120; x 5120 x 192; three inner
    # norms 160 + 16 + 16; dt 160 x 5120 + 5120; A 5120 x 16; D 5120;
    # out 5120 x 2560
    assert p["mamba_mixer"] == (26214400 + 25600 + 983040 + 192 + 824320
                                + 81920 + 5120 + 13107200)
    # q 2560 x 2560, k and v 2560 x 128, o 2560 x 2560
    assert p["attn_mixer"] == 6553600 + 2 * 327680 + 6553600
    assert p["ffn"] == 3 * 2560 * 8192 + 2 * 2560
    assert (p["mamba_layers"], p["attn_layers"]) == (26, 2)
    total = (26 * (p["mamba_mixer"] + p["ffn"])
             + 2 * (p["attn_mixer"] + p["ffn"]) + p["embed"])
    assert total == pytest.approx(3.03e9, rel=2e-3)


def test_a_decode_turn_moves_the_weights_once_and_the_state_twice():
    got = bytes_count.ssm_lm_decode_turn_bytes(CONFIG, 64)
    # 3.029 B parameters in bfloat16; 64 slots x 26 layers x 5120 channels
    # x (16 float32 + 3 bfloat16) = 0.596 GB, once in and once out
    state = 64 * 26 * 5120 * (16 * 4 + 3 * 2)
    assert state == pytest.approx(0.596e9, rel=1e-3)
    assert got == pytest.approx(6.06e9 + 2 * 0.596e9, rel=1e-3)
    assert got - 2 * state == pytest.approx(2 * 3.0293e9, rel=1e-4)
    # a free slot's state is moved too; no slots, weights alone
    assert bytes_count.ssm_lm_decode_turn_bytes(CONFIG, 0) == got - 2 * state


def _traced_run(spans, platform="tpu", facts=None):
    host = {"loop": [tr.Event(*span) for span in spans]}
    t = tr.Trace(ops={}, async_ops={}, modules={}, host=host)
    run = SimpleNamespace(
        facts={"trace": t, "trace_window": (0, 10 ** 12), **(facts or {})},
        devices=[SimpleNamespace(platform=platform,
                                 device_kind="TPU v5 lite")])
    return run


def test_roofline_reader_is_bytes_over_peak_times_the_span():
    ms = 10 ** 6
    run = _traced_run([("hvd:serve.decode", 0, 10 * ms),
                       ("hvd:serve.decode", 20 * ms, 40 * ms),
                       ("hvd:serve.prefill", 50 * ms, 51 * ms)],
                      facts={"decode_turn_bytes": 8.19e9})
    # mean span 15 ms; 8.19 GB at 819 GB/s is 10 ms
    got = hbm_roofline.read(run, "decode_turn_bytes", "hvd:serve.decode")
    assert got == pytest.approx(100 * 10 / 15)


@pytest.mark.parametrize("why", ["no span", "no fact", "not a chip"])
def test_roofline_reader_reads_nothing_where_there_is_nothing(why):
    ms = 10 ** 6
    spans = [] if why == "no span" else [("hvd:serve.decode", 0, 10 * ms)]
    facts = {} if why == "no fact" else {"decode_turn_bytes": 1e9}
    run = _traced_run(spans, "cpu" if why == "not a chip" else "tpu", facts)
    assert hbm_roofline.read(run, "decode_turn_bytes",
                             "hvd:serve.decode") is None
