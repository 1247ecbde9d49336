"""The ``ssd_moe_lm`` family's benchmark files: the cell's rehearsal runs
to a ``correct`` result line with the metrics it lists, the limits tool
holds the int8 pass to the cell's checks, the configuration holds the
catalog's row, the traffic is the issue's, and the counts of parameters,
of the bytes a decode turn must move and of the operations a prefill needs
against hand counts."""

import json
from functools import partial
from pathlib import Path

import pytest

from perfbench import harness
from perfbench import ssd_moe_lm_count as count
from perfbench.jobs import ssd_moe_lm_serve as job

CELL = "nemotron-3-nano-30b-a3b_serve_agents"
HERE = Path(__file__).resolve().parents[1]
CONFIG = json.loads((HERE / "configs" / "nemotron-3-nano-30b-a3b.json"
                     ).read_text())
TRAFFIC = json.loads((HERE / "traffic" / "agents_open.json").read_text())
NEW = {"ssd_state_live_share_pct.serve", "moe_rows_absent_pct.serve",
       "ssd_prefill_chunks_per_prompt.serve"}


class _Run:
    """What ``job.model_sizes`` asks of a run."""
    rehearsal = False
    cell = harness.Cell(CELL)


SIZES = job.model_sizes(_Run)


def _run_cell(capsys, trace, job_fn=None):
    harness.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                  "3", "--trace", trace, "--rehearsal"], job=job_fn)
    return capsys.readouterr().out.strip().splitlines()


def test_rehearsal_is_correct_and_reports_its_end_to_end_metrics(capsys):
    out = json.loads(_run_cell(capsys, "0")[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_setup_s",
                                   "rehearsal_latency_per_token_p50"}
    # bfloat16 program against the float32 reference at the rehearsal's
    # widths: roundings, far under the cell's limits
    assert out["checks"]["served_token_logit_gap_mean"]["value"] < 0.01


def test_traced_rehearsal_reports_every_metric_the_cell_lists(capsys):
    """Every per-layer metric that lists the cell, but those that only a
    chip's trace or peak can give."""
    out = json.loads(_run_cell(capsys, "1")[-1])
    assert out["correct"] is True
    listed = {m["name"] for m in harness.Cell(CELL).metrics("per_layer")}
    assert NEW | {"recurrent_state_gb.serve", "kv_cache_gb.serve",
                  "decode_step_hbm_roofline_pct.serve",
                  "moe_experts_touched_mean.serve", "prefill_mfu_pct.serve",
                  "prefill_ms_per_ktoken.serve"} <= listed
    assert "decode_hbm_roofline_pct.serve" not in listed
    chip_only = {"device_idle_pct.serve", "peak_hbm_gb.serve",
                 "prefill_mfu_pct.serve", "decode_step_device_ms.serve",
                 "decode_step_hbm_roofline_pct.serve",
                 "prefill_device_ms_mean.serve"}
    idle = {n for n in listed if n.startswith("idle_")}
    got = {k[len("rehearsal_"):] for k in out["metrics"]}
    assert got >= listed - chip_only - idle, listed - got
    m = {k[len("rehearsal_"):]: v["value"] for k, v in out["metrics"].items()}
    # the rehearsal's state: 6 slots x 2 Mamba-2 layers x (4 heads x 16 x
    # 16 float32 + 3 rows of 64 + 2 x 2 x 16 channels bfloat16); one
    # attention layer's lanes of 64 positions x 2 heads x 16
    assert m["recurrent_state_gb.serve"] == pytest.approx(
        6 * 2 * (4 * 16 * 16 * 4 + 3 * 128 * 2) / 1e9)
    assert m["kv_cache_gb.serve"] == pytest.approx(
        6 * 2 * 64 * 2 * 16 * 2 / 1e9)
    # 4 of the router's 8 outputs are held: about half of the pairs
    assert 25 < m["moe_rows_absent_pct.serve"] < 75
    assert 0 < m["moe_experts_touched_mean.serve"] <= 4
    assert 0 < m["ssd_state_live_share_pct.serve"] <= 100
    # prompts of 4 to 32 tokens in chunks of 8
    assert 1 <= m["ssd_prefill_chunks_per_prompt.serve"] <= 4


def test_the_limits_tool_holds_the_int8_pass_to_the_cells_checks(capsys):
    """With ``control`` the tokens an int8 pass of the reference puts
    first stand in the program's place: the checks' numbers are that
    pass's (the program's own are printed beside), against the cell's
    limits.  That they FAIL is the chip's to show, at the real widths: at
    the rehearsal's the int8 pass puts the reference's own best first at
    all but a near tie or none."""
    lines = _run_cell(capsys, "0", partial(job.run, control=True))
    sound = next(json.loads(ln) for ln in lines
                 if ln.startswith('{"sound_widest_gap"'))
    checks = json.loads(lines[-1])["checks"]
    limits = CONFIG["serve"]["limits"]
    assert checks["served_token_logit_gap_mean"]["limit"] \
        == limits["logit_gap_mean"]
    assert checks["served_token_logit_gap"]["limit"] == limits["logit_gap"]
    assert set(sound) == {"sound_widest_gap", "sound_mean_gap"}
    assert checks["served_token_logit_gap_mean"]["value"] < 0.01
    assert any("int8 pass" in ln for ln in lines if ln.startswith("check"))


def test_the_configuration_holds_the_catalog_row_unchanged():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert CONFIG["source"] == row["source_url"]
    cut = set(CONFIG["reduced"])
    assert cut == {"num_hidden_layers", "hybrid_override_pattern",
                   "n_routed_experts", "vocab_size"}
    assert {k: CONFIG[k] for k in row["config"] if k not in cut} \
        == {k: v for k, v in row["config"].items() if k not in cut}
    assert CONFIG["published"] == {k: row["config"][k] for k in cut}
    # published layers 34-42, letter for letter: one whole period
    assert CONFIG["hybrid_override_pattern"] == "EMEMEMEM*" \
        == row["config"]["hybrid_override_pattern"][34:43]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (9, 64, 131072 // 2)
    assert CONFIG["share"] == {"router_outputs": 128, "expert_first": 0,
                               "chips_sharing_a_layer": 2,
                               "vocabulary_over": 2}
    # every width as published
    assert [CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "conv_kernel", "chunk_size",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "num_experts_per_tok", "routed_scaling_factor")] == [
        2688, 32, 2, 128, 64, 64, 8, 128, 4, 128, 1856, 3712, 6, 2.5]
    entry = next(c for c in harness.load_json(harness.ROOT / "BENCHMARK.json")
                 ["configs"] if c["name"] == "nemotron-3-nano-30b-a3b")
    assert set(entry["reduced"]) == cut
    assert entry["source"] == row["source_url"]
    # what the program is given: the router's width, and the share
    assert (SIZES["n_routed_experts"], SIZES["experts_held"],
            SIZES["expert_first"]) == (128, 64, 0)


def test_the_traffic_is_the_issues():
    serve = CONFIG["serve"]
    assert (serve["max_batch"], serve["cache_len"], serve["max_queue"]) \
        == (96, 4096, 4096)
    assert TRAFFIC["prompt_tokens"] == {"median": 512, "sigma": 0.7,
                                        "grid": [256, 512, 1024, 2048]}
    assert TRAFFIC["output_tokens"] == {"median": 512, "sigma": 0.6,
                                        "min": 128, "max": 1536}
    assert max(TRAFFIC["prompt_tokens"]["grid"]) \
        + TRAFFIC["output_tokens"]["max"] <= serve["cache_len"]
    assert (TRAFFIC["pairing_seed"], TRAFFIC["preroll_s"]) == (1, 15)
    assert TRAFFIC["rate_rps"] == pytest.approx(0.8 * TRAFFIC["knee_rps"])
    cell = harness.Cell(CELL)
    assert (cell.entry["traffic"], cell.chips) == ("agents_open", 1)
    assert len(cell.entry["why"]) <= 200
    new = [m for m in cell.bench["per_layer"] if m["name"] in NEW]
    assert len(new) == 3 and all(
        m["workloads"] == [CELL] and m["moves"] == "latency_per_token_p50"
        and m["source"] == "program_counter" for m in new)


def test_parameters_by_hand():
    p = count.params(SIZES)
    # in 2688 x (4096 + 6144 + 64), out 4096 x 2688, the convolution 5 x
    # 6144, dt_bias + A_log + D 3 x 64, the gated norm's gain 4096
    assert p["mamba"] == 27697152 + 11010048 + 30720 + 192 + 4096 \
        == 38742208
    # q 2688 x 4096, k and v 2688 x 256, o 4096 x 2688
    assert p["attention"] == 11010048 + 2 * 688128 + 11010048 == 23396352
    assert p["expert"] == 2 * 2688 * 1856 == 9977856
    assert p["shared"] == 2 * 2688 * 3712 == 19955712
    assert p["router"] == 2688 * 128 == 344064
    assert (p["mamba_layers"], p["moe_layers"], p["attn_layers"]) == (4, 4, 1)
    assert count.outside_experts(SIZES) == 4 * 38742208 + 23396352 \
        + 4 * (344064 + 19955712)
    held = count.held_params(SIZES)
    assert held == count.outside_experts(SIZES) + 4 * 64 * 9977856 \
        + 2 * 65536 * 2688
    assert 2 * held == pytest.approx(6.33e9, rel=1e-3)
    # the whole published model: 31.6 B
    whole = {**SIZES, "hybrid_override_pattern":
             CONFIG["published"]["hybrid_override_pattern"],
             "experts_held": 128, "vocab_size": 131072}
    assert count.held_params(whole) == pytest.approx(31.58e9, rel=1e-3)
    # the routed stacks are held 3072 x 2048 for the published 2688 x 1856
    assert count.held_weight_bytes(SIZES) == 2 * (
        held + 4 * 64 * 2 * (3072 * 2048 - 2688 * 1856))
    assert count.held_weight_bytes(SIZES) == pytest.approx(7.66e9, rel=1e-3)
    assert count.state_bytes_per_slot(SIZES) == 4 * (2097152 + 36864)
    assert count.kv_bytes_per_position(SIZES) == 1024
    assert 96 * count.state_bytes_per_slot(SIZES) == pytest.approx(
        0.82e9, rel=2e-3)
    assert 96 * 4096 * 1024 == pytest.approx(0.40e9, rel=1e-2)


def test_a_decode_turn_moves_the_touched_experts_and_the_state_twice():
    p = count.params(SIZES)
    outside = 2 * (count.outside_experts(SIZES) + p["embed"])
    assert outside == pytest.approx(0.871e9, rel=1e-3)
    assert count.decode_turn_bytes(SIZES, 0, 0) == outside
    # 62 experts touched a layer: 4 x 62 x 19.96 MB
    assert count.decode_turn_bytes(SIZES, 0, 62) - outside \
        == pytest.approx(4 * 62 * 19.9557e6, rel=1e-5)
    # 96 slots' state, once in and once out: a free slot's is moved too
    assert count.decode_turn_bytes(SIZES, 96, 62) \
        - count.decode_turn_bytes(SIZES, 0, 62) == 2 * 96 * 8536064
    assert count.decode_turn_bytes(SIZES, 96, 62) == pytest.approx(
        7.46e9, rel=1e-3)


def test_a_prefill_needs_the_recurrence_not_the_chunked_forms_products():
    p = count.params(SIZES)
    active = count.outside_experts(SIZES) + 4 * 6 * 64 / 128 * p["expert"]
    one = count.prefill_flops(SIZES, 1)
    assert one == pytest.approx(
        2 * active + 4 * 4 * 64 * 64 * 128 + 32 * 2 * 256 * 0.5
        + 2 * 65536 * 2688, rel=1e-9)
    # 0.76 GFLOP a prompt token in the products, the recurrence 1 % more
    assert 2 * active == pytest.approx(0.7586e9, rel=1e-3)
    long = count.prefill_flops(SIZES, 2048)
    assert long == pytest.approx(
        2048 * (2 * active + 4 * 4 * 64 * 64 * 128)
        + 32 * 2 * 256 * 2048 * 2048 / 2 + 2 * 65536 * 2688, rel=1e-9)
    assert count.mean_prefill_flops_per_token(SIZES, [256, 2048]) \
        == pytest.approx((count.prefill_flops(SIZES, 256) + long) / 2304)
