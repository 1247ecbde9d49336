"""The ``moe_lm`` family's benchmark files: the cell's rehearsal runs to a
``correct`` result line with the metrics it lists, the configuration holds
the catalog's row, and the counts of parameters, of the bytes a decode
turn must move and of the operations a prefill needs against hand
counts."""

import json
from pathlib import Path

import pytest

from perfbench import harness, moe_lm_count

CELL = "glm-4.7-flash_serve_context"
HERE = Path(__file__).resolve().parents[1]
CONFIG = json.loads((HERE / "configs" / "glm-4.7-flash.json").read_text())
TRAFFIC = json.loads((HERE / "traffic" / "context_open.json").read_text())


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
    """Every per-layer metric that lists the cell, but the four that only
    a chip's trace or peak can give."""
    out = _run_cell(capsys, "1")
    assert out["correct"] is True
    listed = {m["name"] for m in harness.Cell(CELL).metrics("per_layer")}
    assert {"moe_experts_touched_mean.serve", "prefill_mfu_pct.serve",
            "moe_expert_load_max_over_mean.serve", "kv_cache_gb.serve",
            "decode_hbm_roofline_pct.serve"} <= listed
    chip_only = {"device_idle_pct.serve", "peak_hbm_gb.serve",
                 "decode_hbm_roofline_pct.serve", "prefill_mfu_pct.serve"}
    idle = {n for n in listed if n.startswith("idle_")}
    got = {k[len("rehearsal_"):] for k in out["metrics"]}
    assert got >= listed - chip_only - idle, listed - got
    m = out["metrics"]
    # the rehearsal's lanes: 4 slots x 64 positions x 3 layers x (16 + 8)
    # bfloat16 values
    assert m["rehearsal_kv_cache_gb.serve"]["value"] \
        == pytest.approx(4 * 64 * 3 * (16 + 8) * 2 / 1e9)
    # 8 experts, 2 a row, at most 4 live rows
    assert 2 <= m["rehearsal_moe_experts_touched_mean.serve"]["value"] <= 8
    assert 1 <= m["rehearsal_moe_expert_load_max_over_mean.serve"]["value"] \
        <= 4
    assert m["rehearsal_prefill_ms_per_ktoken.serve"]["value"] > 0


def test_the_configuration_holds_the_catalog_row_unchanged():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "GLM-4.7-Flash")
    assert CONFIG["source"] == row["source_url"]
    cut = set(CONFIG["reduced"])
    assert cut == {"num_hidden_layers"}
    assert {k: CONFIG[k] for k in row["config"] if k not in cut} \
        == {k: v for k, v in row["config"].items() if k not in cut}
    # 7 of 47: the leading dense layer and six expert layers
    assert (CONFIG["num_hidden_layers"], row["config"]["num_hidden_layers"],
            CONFIG["first_k_dense_replace"]) == (7, 47, 1)
    entry = next(c for c in harness.load_json(harness.ROOT / "BENCHMARK.json")
                 ["configs"] if c["name"] == "glm-4.7-flash")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]


def test_the_traffic_is_the_issues():
    assert TRAFFIC["prompt_tokens"] == {"median": 1536, "sigma": 0.7,
                                        "grid": [512, 1024, 2048, 4096]}
    assert TRAFFIC["output_tokens"] == {"median": 192, "sigma": 0.6,
                                        "min": 64, "max": 512}
    assert TRAFFIC["rate_rps"] == pytest.approx(0.8 * TRAFFIC["knee_rps"])
    serve = CONFIG["serve"]
    assert (serve["max_batch"], serve["cache_len"]) == (64, 4096 + 512)


def test_parameters_by_hand():
    p = moe_lm_count.moe_lm_params(CONFIG)
    # q down 2048 x 768, q up 768 x 20 x 256, kv down 2048 x 576, kv up
    # 512 x 20 x 448, o 20 x 256 x 2048
    assert p["attention"] == (1572864 + 3932160 + 1179648 + 4587520
                              + 10485760) == 21757952
    assert p["expert"] == 3 * 2048 * 1536 == 9437184
    assert p["router"] == 131072
    assert p["dense_ffn"] == 62914560
    assert p["attention"] + p["dense_ffn"] == pytest.approx(84.7e6, rel=1e-3)
    assert 2 * p["embed"] == pytest.approx(634.4e6, rel=1e-4)
    assert (p["dense_layers"], p["moe_layers"]) == (1, 6)
    outside = p["attention"] + p["router"] + p["expert"]
    assert outside == pytest.approx(31.3e6, rel=1e-3)
    total = (p["attention"] + p["dense_ffn"]
             + 6 * (outside + 64 * p["expert"]) + 2 * p["embed"])
    assert total == pytest.approx(4531e6, rel=1e-3)


def test_a_decode_turn_moves_the_touched_experts_and_the_rest_once():
    got = moe_lm_count.moe_lm_decode_turn_bytes(CONFIG, 63.0)
    # outside the routed experts: the dense layer 84.67 M, 6 x 31.33 M,
    # the head 317.2 M (the embedding is a lookup): 589.8 M parameters
    outside = 2 * (84672512 + 6 * 31326208 + 317194240)
    assert outside == pytest.approx(1.18e9, rel=2e-3)
    # 6 layers x 63 experts x 18.87 MB
    assert got - outside == pytest.approx(6 * 63 * 18.874e6, rel=1e-4)
    assert got == pytest.approx(8.31e9, rel=2e-3)
    # no expert touched: the rest alone; all 64: 7.25 GB of experts
    assert moe_lm_count.moe_lm_decode_turn_bytes(CONFIG, 0) == outside
    assert moe_lm_count.moe_lm_decode_turn_bytes(CONFIG, 64) - outside \
        == pytest.approx(7.247e9, rel=1e-3)


def test_a_prefill_needs_four_experts_a_row_not_sixty_four():
    one = moe_lm_count.moe_lm_prefill_flops(CONFIG, 1)
    # active: 84.67 M + 6 x (21.76 + 0.13 + 5 x 9.437) M = 499.1 M; the
    # head once
    active = 84672512 + 6 * (21757952 + 131072 + 5 * 9437184)
    assert active == pytest.approx(499.1e6, rel=1e-3)
    assert one == pytest.approx(2 * active + 2 * 317194240
                                + 7 * 20 * 2 * 512 / 2, rel=1e-9)
    long = moe_lm_count.moe_lm_prefill_flops(CONFIG, 4096)
    # causal attention at 4096: 7 layers x 20 heads x 4096^2 x 512
    attention = 7 * 20 * 4096 ** 2 * 512
    assert attention == pytest.approx(1.203e12, rel=1e-3)
    assert long == pytest.approx(2 * active * 4096 + attention
                                 + 2 * 317194240, rel=1e-9)
    assert long == pytest.approx(5.29e12, rel=2e-3)
    # every expert for every row would be 64 + 1 experts a row
    every = long + 2 * 4096 * 6 * 60 * 9437184
    assert every / long == pytest.approx(6.26, rel=1e-2)
