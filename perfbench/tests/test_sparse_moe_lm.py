"""The ``sparse_moe_lm`` family's benchmark files: the cell's rehearsal
runs to a ``correct`` result line with the metrics it lists, a resident
that finishes inside the window makes it not ``correct``, the
configuration holds the catalog's row, the traffic is the issue's, and the
counts of parameters, of the bytes a decode turn must move and of the
operations a prefill needs against hand counts."""

import json
from pathlib import Path

import pytest

from perfbench import harness
from perfbench import sparse_moe_lm_count as count
from perfbench.jobs import sparse_moe_lm_serve as job

CELL = "deepseek-v3.2_serve_resident"
HERE = Path(__file__).resolve().parents[1]
CONFIG = json.loads((HERE / "configs" / "deepseek-v3.2.json").read_text())
TRAFFIC = json.loads((HERE / "traffic" / "resident_open.json").read_text())


class _Run:
    """What ``job.model_sizes`` asks of a run."""
    rehearsal = False
    cell = harness.Cell(CELL)


SIZES = job.model_sizes(_Run)


def _run_cell(capsys, trace, seconds="3"):
    harness.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                  seconds, "--trace", trace, "--rehearsal"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal_is_correct_and_reports_its_end_to_end_metrics(capsys):
    out = _run_cell(capsys, "0")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_setup_s",
                                   "rehearsal_latency_per_token_p50"}
    assert out["checks"]["residents_not_decoding_at_end"]["value"] == 0
    # two sessions of 500 tokens at positions past their 40-token prompts
    assert out["checks"]["resident_token_logit_gap_mean"]["value"] < 0.01


def test_a_resident_that_finishes_inside_the_window_is_not_correct(
        capsys, monkeypatch):
    """The rehearsal's residents return 500 tokens; given 20 they are done
    before the window opens: nothing failed, and the run is not
    ``correct``."""
    short = {**TRAFFIC["rehearsal"], "residents": {
        **TRAFFIC["rehearsal"]["residents"], "new_tokens": 20}}
    real = harness.load_json

    def load(path):
        found = real(path)
        return {**found, "rehearsal": short} \
            if Path(path).name == "resident_open.json" else found

    monkeypatch.setattr(harness, "load_json", load)
    out = _run_cell(capsys, "0")
    assert out["failed"] == 0 and out["correct"] is False
    assert out["checks"]["residents_not_decoding_at_end"]["value"] == 2


def test_the_limits_tool_holds_the_int8_pass_to_the_cells_checks(capsys):
    """With ``control`` the tokens an int8 pass of the reference puts
    first stand in the program's place: the checks' numbers are that
    pass's, not the program's (printed beside), against the cell's
    limits.  That they FAIL is the chip's to show, at the real widths."""
    from functools import partial

    harness.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                  "3", "--trace", "0", "--rehearsal"],
                 job=partial(job.run, control=True))
    lines = capsys.readouterr().out.strip().splitlines()
    sound = next(json.loads(ln) for ln in lines
                 if ln.startswith('{"sound_widest_gap"'))
    checks = json.loads(lines[-1])["checks"]
    limits = CONFIG["serve"]["limits"]
    assert checks["served_token_logit_gap_mean"]["limit"] \
        == limits["logit_gap_mean"]
    assert checks["served_token_logit_gap_mean"]["value"] \
        != sound["sound_mean_gap"]
    assert checks["resident_token_logit_gap_mean"]["value"] \
        != sound["sound_resident_mean_gap"]
    assert any("int8 pass" in ln for ln in lines if ln.startswith("check"))


def test_traced_rehearsal_reports_every_metric_the_cell_lists(capsys):
    """Every per-layer metric that lists the cell, but those that only a
    chip's trace, peak or kernels can give."""
    out = _run_cell(capsys, "1")
    assert out["correct"] is True
    listed = {m["name"] for m in harness.Cell(CELL).metrics("per_layer")}
    assert {"index_select_ms_per_turn.serve", "sparse_attn_ms_per_turn.serve",
            "sparse_attn_roofline_pct.serve", "attn_selected_share_pct.serve",
            "index_cache_gb.serve", "decode_step_hbm_roofline_pct.serve",
            "moe_experts_touched_mean.serve"} <= listed
    assert "decode_hbm_roofline_pct.serve" not in listed
    chip_only = {"device_idle_pct.serve", "peak_hbm_gb.serve",
                 "prefill_mfu_pct.serve", "decode_step_device_ms.serve",
                 "decode_step_hbm_roofline_pct.serve",
                 "prefill_device_ms_mean.serve",
                 "index_select_ms_per_turn.serve",
                 "sparse_attn_ms_per_turn.serve",
                 "sparse_attn_roofline_pct.serve"}
    idle = {n for n in listed if n.startswith("idle_")}
    got = {k[len("rehearsal_"):] for k in out["metrics"]}
    assert got >= listed - chip_only - idle, listed - got
    m = out["metrics"]
    # the rehearsal's lanes: 6 slots x 640 positions x 3 layers
    assert m["rehearsal_kv_cache_gb.serve"]["value"] \
        == pytest.approx(6 * 640 * 3 * (16 + 8) * 2 / 1e9)
    assert m["rehearsal_index_cache_gb.serve"]["value"] \
        == pytest.approx(6 * 640 * 3 * 16 * 2 / 1e9)
    # 16 of the residents' 40 to 540 positions seen; the sampled see all
    assert 2 < m["rehearsal_attn_selected_share_pct.serve"]["value"] < 60
    assert 0 < m["rehearsal_moe_experts_touched_mean.serve"]["value"] <= 4


def test_the_configuration_holds_the_catalog_row_unchanged():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "DeepSeek-V3.2")
    assert CONFIG["source"] == row["source_url"]
    cut = set(CONFIG["reduced"])
    assert cut == {"num_hidden_layers", "first_k_dense_replace",
                   "n_routed_experts", "vocab_size"}
    assert {k: CONFIG[k] for k in row["config"] if k not in cut} \
        == {k: v for k, v in row["config"].items() if k not in cut}
    assert CONFIG["published"] == {k: row["config"][k] for k in cut}
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"]) \
        == (5, 1, 16, 129280 // 8)
    assert CONFIG["share"]["router_outputs"] == 256
    # every width as published
    assert [CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "index_n_heads", "index_head_dim", "index_topk",
        "moe_intermediate_size", "intermediate_size", "n_group",
        "topk_group", "num_experts_per_tok", "routed_scaling_factor")] == [
        7168, 128, 128, 64, 128, 1536, 512, 64, 128, 2048, 2048, 18432, 8, 4,
        8, 2.5]
    assert CONFIG["rope_scaling"]["factor"] == 40
    entry = next(c for c in harness.load_json(harness.ROOT / "BENCHMARK.json")
                 ["configs"] if c["name"] == "deepseek-v3.2")
    assert set(entry["reduced"]) == cut
    assert entry["source"] == row["source_url"]
    # what the program is given: the router's width, and the share
    assert (SIZES["n_routed_experts"], SIZES["experts_held"],
            SIZES["expert_first"]) == (256, 16, 0)


def test_the_traffic_is_the_issues():
    assert TRAFFIC["residents"]["count"] == 16
    assert TRAFFIC["residents"]["prompt_tokens"] == 12288
    serve = CONFIG["serve"]
    assert (serve["max_batch"], serve["cache_len"]) == (24, 18432)
    assert TRAFFIC["residents"]["new_tokens"] <= 18432 - 12288
    # shorter than the issue's log-normal(512) on 256-2048: its remedy for
    # a cell that does not repeat (PERF.md section 6, PR 41)
    assert TRAFFIC["prompt_tokens"] == {"median": 256, "sigma": 0.7,
                                        "grid": [256, 512]}
    assert TRAFFIC["output_tokens"] == {"median": 128, "sigma": 0.5,
                                        "min": 48, "max": 384}
    assert TRAFFIC["rate_rps"] == pytest.approx(0.8 * TRAFFIC["knee_rps"])
    cell = harness.Cell(CELL)
    assert (cell.entry["traffic"], cell.chips) == ("resident_open", 1)


def test_parameters_by_hand():
    p = count.params(SIZES)
    # q down 7168 x 1536, q up 1536 x 128 x 192, kv down 7168 x 576, kv up
    # 512 x 128 x 256, o 128 x 128 x 7168
    assert p["attention"] == (11010048 + 37748736 + 4128768 + 16777216
                              + 117440512) == 187105280
    # index q 1536 x 64 x 128, index k 7168 x 128, head weights 7168 x 64
    assert p["indexer"] == 12582912 + 917504 + 458752 == 13959168
    assert p["expert"] == 3 * 7168 * 2048 == 44040192
    assert p["router"] == 7168 * 256 == 1835008
    o = count.outside_experts(SIZES)
    assert o["moe"] == pytest.approx(246.96e6, rel=1e-4)
    assert o["dense"] == pytest.approx(597.4e6, rel=1e-4)
    assert (p["dense_layers"], p["moe_layers"]) == (1, 4)
    held = count.held_params(SIZES)
    assert held == o["dense"] + 4 * (o["moe"] + 16 * p["expert"]) \
        + 2 * 16160 * 7168
    assert held == pytest.approx(4635.5e6, rel=1e-4)
    assert 2 * held == pytest.approx(9.27e9, rel=1e-3)
    assert count.cache_bytes_per_position(SIZES) == 7040
    assert 24 * 18432 * 7040 == pytest.approx(3.11e9, rel=2e-3)


def test_a_decode_turn_moves_what_was_touched_scored_and_selected():
    o, p = count.outside_experts(SIZES), count.params(SIZES)
    outside = 2 * (o["dense"] + 4 * o["moe"] + p["embed"])
    assert outside == pytest.approx(3.40e9, rel=2e-3)
    assert count.decode_turn_bytes(SIZES, 0, 0, 0) == outside
    # 8 experts touched a layer: 4 x 8 x 88.1 MB
    assert count.decode_turn_bytes(SIZES, 8, 0, 0) - outside \
        == pytest.approx(4 * 8 * 88.08e6, rel=1e-4)
    # 16 slots at 14k positions scored, 22 x 2048 selected, a layer
    got = count.decode_turn_bytes(SIZES, 8, 16 * 14000, 22 * 2048)
    assert got - count.decode_turn_bytes(SIZES, 8, 0, 0) == pytest.approx(
        5 * (16 * 14000 * 256 + 22 * 2048 * 1152), rel=1e-9)
    need = count.selected_read(SIZES, 2048)
    assert need["bytes"] == pytest.approx(2.36e6, rel=1e-3)
    assert need["ops"] == pytest.approx(0.57e9, rel=1e-2)
    # a slot's 2048 selected latents sit at the chip's ridge
    assert need["ops"] / need["bytes"] == pytest.approx(242, rel=1e-2)


def test_a_prefill_attends_the_selected_keys_not_the_dense_triangle():
    o, p = count.outside_experts(SIZES), count.params(SIZES)
    active = o["dense"] + 4 * (o["moe"] + 8 * 16 / 256 * p["expert"])
    one = count.prefill_flops(SIZES, 1)
    assert one == pytest.approx(
        2 * active + 5 * 128 * 2 * 320 + 5 * 64 * 2 * 128
        + 2 * 16160 * 7168, rel=1e-9)
    short = count.prefill_flops(SIZES, 2048)
    attention = 5 * 128 * 2 * 320 * 2048 * 2049 / 2
    index = 5 * 64 * 2 * 128 * 2048 * 2049 / 2
    assert short == pytest.approx(2 * active * 2048 + attention + index
                                  + 2 * 16160 * 7168, rel=1e-9)
    # 12288 rows: the first 2048 a triangle, the rest 2048 keys each; the
    # dense triangle would be 3.3 times the attention
    long = count.prefill_flops(SIZES, 12288)
    selected = 2048 * 2049 / 2 + (12288 - 2048) * 2048
    dense = 12288 * 12289 / 2
    assert dense / selected == pytest.approx(3.27, rel=1e-2)
    assert long == pytest.approx(
        2 * active * 12288 + 5 * 128 * 2 * 320 * selected
        + 5 * 64 * 2 * 128 * dense + 2 * 16160 * 7168, rel=1e-9)
