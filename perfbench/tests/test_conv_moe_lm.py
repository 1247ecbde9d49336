"""The ``conv_moe_lm`` family's benchmark files: the cell's rehearsal runs
to a ``correct`` result line with the metrics it lists, a token altered
where the loop produces it is not ``correct``, the limits tool holds the
int8 pass to the cell's checks, the configuration holds the catalog's row,
the traffic is the issue's, and the counts of parameters, of the bytes a
decode turn must move and of the operations a prefill needs against hand
counts and against the parameters ``init`` makes."""

import json
from functools import partial
from pathlib import Path

import pytest

from perfbench import conv_moe_lm_count as count
from perfbench import harness
from perfbench.jobs import conv_moe_lm_serve as job
from perfbench.readers import grouped_product

CELL = "lfm2-8b-a1b_serve_assistants"
HERE = Path(__file__).resolve().parents[1]
CONFIG = json.loads((HERE / "configs" / "lfm2-8b-a1b.json").read_text())
TRAFFIC = json.loads((HERE / "traffic" / "assistants_open.json").read_text())
NEW = {"moe_rows_per_expert_mean.serve", "routed_product_ms_per_turn.serve",
       "routed_product_hbm_roofline_pct.serve"}


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
                  "moe_experts_touched_mean.serve",
                  "moe_expert_load_max_over_mean.serve",
                  "prefill_mfu_pct.serve",
                  "prefill_ms_per_ktoken.serve"} <= listed
    assert not {"decode_hbm_roofline_pct.serve",
                "moe_rows_absent_pct.serve"} & listed
    chip_only = {"device_idle_pct.serve", "peak_hbm_gb.serve",
                 "prefill_mfu_pct.serve", "decode_step_device_ms.serve",
                 "decode_step_hbm_roofline_pct.serve",
                 "prefill_device_ms_mean.serve",
                 "routed_product_ms_per_turn.serve",
                 "routed_product_hbm_roofline_pct.serve"}
    idle = {n for n in listed if n.startswith("idle_")}
    got = {k[len("rehearsal_"):] for k in out["metrics"]}
    assert got >= listed - chip_only - idle, listed - got
    m = {k[len("rehearsal_"):]: v["value"] for k, v in out["metrics"].items()}
    # the rehearsal's state: 6 slots x 4 short-convolution layers x 2 rows
    # of 64 bfloat16; two attention layers' lanes of 64 positions x 2 heads
    # x 16, keys and values
    assert m["recurrent_state_gb.serve"] == pytest.approx(
        6 * 4 * 2 * 64 * 2 / 1e9)
    assert m["kv_cache_gb.serve"] == pytest.approx(
        6 * 2 * 2 * 64 * 2 * 16 * 2 / 1e9)
    # every expert is held: a live row's three pairs are all routed
    assert 0 < m["moe_experts_touched_mean.serve"] <= 8
    assert m["moe_rows_per_expert_mean.serve"] >= 1
    assert m["moe_expert_load_max_over_mean.serve"] >= 1


def test_a_token_altered_where_the_loop_produces_it_is_not_correct(
        capsys, monkeypatch):
    """The comparison is of what the served path itself produced: with the
    engine's read handing the loop another token than the step put first
    (every slot's, every turn), the result line reads ``correct`` false by
    the logit gap, whatever the program computed."""
    from horovod_tpu.serving.decode import DecodeEngine

    read = DecodeEngine.read
    monkeypatch.setattr(DecodeEngine, "read",
                        lambda self: (read(self) + 1) % 256)
    out = json.loads(_run_cell(capsys, "0")[-1])
    assert out["failed"] == 0 and out["correct"] is False
    assert out["checks"]["served_token_logit_gap"]["value"] > 1.0
    assert out["checks"]["served_token_logit_gap_mean"]["value"] \
        > out["checks"]["served_token_logit_gap_mean"]["limit"]


def test_the_limits_tool_holds_the_int8_pass_to_the_cells_checks(capsys):
    """With ``control`` the tokens an int8 pass of the reference puts
    first stand in the program's place: the checks' numbers are that
    pass's (the program's own are printed beside), against the cell's
    limits.  That they FAIL is the chip's to show, at the real widths."""
    lines = _run_cell(capsys, "0", partial(job.run, control=True))
    sound = next(json.loads(ln) for ln in lines
                 if ln.startswith('{"sound_widest_gap"'))
    checks = json.loads(lines[-1])["checks"]
    limits = CONFIG["serve"]["limits"]
    assert checks["served_token_logit_gap_mean"]["limit"] \
        == limits["logit_gap_mean"]
    assert checks["served_token_logit_gap"]["limit"] == limits["logit_gap"]
    assert set(sound) == {"sound_widest_gap", "sound_mean_gap"}
    assert any("int8 pass" in ln for ln in lines if ln.startswith("check"))


def test_the_configuration_holds_the_catalog_row_unchanged():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "LFM2-8B-A1B")
    assert CONFIG["source"] == row["source_url"]
    cut = set(CONFIG["reduced"])
    assert cut == {"num_hidden_layers", "layer_types"}
    assert {k: CONFIG[k] for k in row["config"] if k not in cut} \
        == {k: v for k, v in row["config"].items() if k not in cut}
    assert CONFIG["published"] == {k: row["config"][k] for k in cut}
    # published layers 0-13, entry for entry: both dense layers and three
    # whole periods of four
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:14]
    assert CONFIG["layer_types"] == ["conv", "conv"] \
        + ["full_attention", "conv", "conv", "conv"] * 3
    assert CONFIG["num_hidden_layers"] == 14
    assert CONFIG["chips_sharing_a_layer"] == 1
    # every width, every expert and the whole vocabulary as published
    assert [CONFIG[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "conv_L_cache",
        "num_dense_layers", "num_experts", "num_experts_per_tok",
        "routed_scaling_factor", "vocab_size", "rope_theta")] == [
        2048, 7168, 1792, 32, 8, 3, 2, 32, 4, 1, 65536, 1000000]
    entry = next(c for c in harness.load_json(harness.ROOT / "BENCHMARK.json")
                 ["configs"] if c["name"] == "lfm2-8b-a1b")
    assert set(entry["reduced"]) == cut
    assert entry["source"] == row["source_url"]


def test_the_traffic_is_the_issues():
    serve = CONFIG["serve"]
    assert (serve["max_batch"], serve["cache_len"], serve["max_queue"]) \
        == (192, 2048, 4096)
    assert TRAFFIC["prompt_tokens"]["median"] == 320
    assert TRAFFIC["prompt_tokens"]["sigma"] in (0.8, 0.6)
    assert TRAFFIC["prompt_tokens"]["grid"] == [128, 256, 512, 1024]
    assert TRAFFIC["output_tokens"] == {"median": 256, "sigma": 0.7,
                                        "min": 32, "max": 1024}
    assert max(TRAFFIC["prompt_tokens"]["grid"]) \
        + TRAFFIC["output_tokens"]["max"] <= serve["cache_len"]
    assert (TRAFFIC["pairing_seed"], TRAFFIC["preroll_s"]) == (1, 20)
    assert TRAFFIC["rate_rps"] == pytest.approx(0.8 * TRAFFIC["knee_rps"])
    cell = harness.Cell(CELL)
    assert (cell.entry["traffic"], cell.chips) == ("assistants_open", 1)
    assert len(cell.entry["why"]) <= 200
    new = [m for m in cell.bench["per_layer"] if m["name"] in NEW]
    assert len(new) == 3 and all(
        m["workloads"] == [CELL] and m["moves"] == "latency_per_token_p50"
        for m in new)
    assert {m["name"]: m["source"] for m in new} == {
        "moe_rows_per_expert_mean.serve": "program_counter",
        "routed_product_ms_per_turn.serve": "device_trace",
        "routed_product_hbm_roofline_pct.serve": "device_trace"}


def test_parameters_by_hand():
    p = count.params(SIZES)
    # in 2048 x 6144, out 2048 x 2048, the convolution 3 x 2048
    assert p["conv"] == 12582912 + 4194304 + 6144 == 16783360
    # q and o 2048 x 2048, k and v 2048 x 512, two gains of 64
    assert p["attention"] == 2 * 4194304 + 2 * 1048576 + 128 == 10485888
    assert p["dense"] == 3 * 2048 * 7168 == 44040192
    assert p["expert"] == 3 * 2048 * 1792 == 11010048
    assert p["router"] == 2048 * 32
    assert (p["conv_layers"], p["attn_layers"], p["dense_layers"],
            p["moe_layers"]) == (11, 3, 2, 12)
    held = count.held_params(SIZES)
    assert held == 11 * 16783360 + 3 * 10485888 + 2 * 44040192 \
        + 12 * (32 * 11010048 + 65536) + 65536 * 2048 == 4667017600
    assert 2 * held == pytest.approx(9.33e9, rel=1e-3)
    # the whole published model: 8.3 B, the tied count
    whole = {**SIZES, "layer_types": CONFIG["published"]["layer_types"]}
    assert count.held_params(whole) == pytest.approx(8.34e9, rel=1e-3)
    # stacks held 2048 wide for the published 1792
    assert count.held_weight_bytes(SIZES) \
        == 2 * (held + 12 * 32 * 3 * 2048 * 256)
    assert count.kv_bytes_per_position(SIZES) == 6144
    assert count.window_bytes_per_slot(SIZES) == 11 * 8192
    assert 192 * 2048 * 6144 == pytest.approx(2.42e9, rel=2e-3)


def test_the_counts_are_of_the_parameters_init_makes():
    """``held_params`` at a small size against the leaves of
    ``models/conv_moe.py:init`` (less the norm gains it leaves out), and
    ``held_weight_bytes`` at the published sizes against the bytes of the
    leaves ``init`` would make there, the stacks padded (shapes alone)."""
    import jax

    from horovod_tpu.models import conv_moe

    def leaves(sizes):
        cfg = conv_moe.ConvMoEConfig(
            **{**sizes, "layer_types": tuple(sizes["layer_types"])})
        shapes = jax.eval_shape(lambda k: conv_moe.init(k, cfg),
                                jax.random.PRNGKey(0))
        gains = sum(a.size for kind in ("conv", "attn", "dense", "moe")
                    for a in [shapes[kind]["ln"]]) + shapes["ln_f"].size
        bias = shapes["moe"]["router_bias"].size
        return sum(a.size for a in jax.tree.leaves(shapes)) - gains - bias

    small = {**SIZES, **CONFIG["serve"]["rehearsal"]["config"]}
    assert count.held_params(small) == leaves(small)
    assert count.held_weight_bytes(small) == 2 * leaves(small)
    assert count.held_weight_bytes(SIZES) == 2 * leaves(SIZES) \
        > 2 * count.held_params(SIZES)


def test_a_decode_turn_moves_the_touched_experts_and_the_positions_written():
    p = count.params(SIZES)
    outside = 2 * (count.outside_experts(SIZES) + p["embed"])
    # operators, dense layers and routers 0.61 GB, the head 0.27
    assert outside == pytest.approx(0.878e9, rel=1e-3)
    assert count.decode_turn_bytes(SIZES, 0, 0, 0) == outside
    # all 32 experts of 12 layers: 8.46 GB
    assert count.routed_product_bytes(SIZES, 32) \
        == 12 * 32 * 22020096 == pytest.approx(8.456e9, rel=1e-3)
    assert count.decode_turn_bytes(SIZES, 32, 0, 0) - outside \
        == count.routed_product_bytes(SIZES, 32)
    # 130 requests at position 700: their windows in and out, and 6144 B a
    # position written; NOT the 192 lanes of 2048
    state = count.decode_turn_bytes(SIZES, 32, 130, 700) \
        - count.decode_turn_bytes(SIZES, 32, 0, 0)
    assert state == 130 * (2 * 90112 + 700 * 6144)
    assert count.decode_turn_bytes(SIZES, 32, 130, 700) == pytest.approx(
        9.92e9, rel=1e-3)


def test_a_prefill_needs_four_experts_a_layer_and_half_the_square():
    p = count.params(SIZES)
    active = count.outside_experts(SIZES) + 12 * 4 * p["expert"]
    # 1.67 GFLOP a prompt token in the products, 63 % of it the experts
    assert 2 * active == pytest.approx(1.667e9, rel=1e-3)
    assert 12 * 4 * p["expert"] / active == pytest.approx(0.634, abs=2e-3)
    one = count.prefill_flops(SIZES, 1)
    assert one == pytest.approx(
        2 * active + 3 * 32 * 2 * 2 * 64 * 0.5 + 2 * 65536 * 2048, rel=1e-9)
    long = count.prefill_flops(SIZES, 1024)
    assert long == pytest.approx(
        1024 * 2 * active + 3 * 32 * 2 * 2 * 64 * 1024 * 1024 / 2
        + 2 * 65536 * 2048, rel=1e-9)
    assert count.mean_prefill_flops_per_token(SIZES, [128, 1024]) \
        == pytest.approx((count.prefill_flops(SIZES, 128) + long) / 1152)


class _Traced:
    """A run as the reader sees it: a trace of one chip's ops."""

    def __init__(self, events, facts, platform="tpu"):
        from types import SimpleNamespace

        from perfbench import trace as tr

        self.facts = {"trace": tr.Trace(
            {0: [tr.Event(*e) for e in events]}, {}, {}, {}), **facts}
        self.devices = [SimpleNamespace(platform=platform,
                                        device_kind="TPU v5 lite")]


CALL = ('%ragged-dot-none.{n} = bf16[{rows},2048]{{1,0:T(8,128)(2,1)}} '
        'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
FACTS = {"routed_product_rows": 768, "routed_product_calls_per_turn": 3,
         "routed_product_bytes": 819e6}


def test_the_reader_takes_a_decode_turns_products_and_no_prompts():
    """Six calls of 768 rows (two turns of three) of 1 ms each, a
    prompt's call of 4096 rows, the metadata kernel and another Mosaic
    call: 3 ms a turn; 819 MB over 819 GB/s x 3 ms is a third of the
    roofline."""
    events = [(CALL.format(n=i, rows=768), 1e6 * i, 1e6 * i + 1e6)
              for i in range(6)]
    events += [(CALL.format(n=9, rows=4096), 7e6, 9e6),
               ('%ragged-dot-metadata.1 = (s32[385]{0}, s32[1]{0}) '
                'custom-call(%p), custom_call_target="tpu_custom_call"',
                9e6, 9.5e6),
               ('%decode_attn.3 = bf16[768,2048]{1,0} custom-call(%q), '
                'custom_call_target="tpu_custom_call"', 10e6, 11e6)]
    args = ("ragged-dot-none", "routed_product_rows",
            "routed_product_calls_per_turn")
    run = _Traced(events, FACTS)
    assert grouped_product.read(run, "ms_per_turn", *args) \
        == pytest.approx(3.0)
    assert grouped_product.read(run, "hbm_roofline_pct", *args,
                                "routed_product_bytes") \
        == pytest.approx(100 / 3)


@pytest.mark.parametrize("why", ["no such call", "no facts", "no bytes",
                                 "no chip"])
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(why):
    """A program without the grouped product, or one that does not count
    (the parent of the PR that adds the metric): None, not an error."""
    events = [(CALL.format(n=1, rows=4096 if why == "no such call" else 768),
               0.0, 1e6)]
    facts = dict(FACTS)
    if why == "no facts":
        facts = {}
    if why == "no bytes":
        del facts["routed_product_bytes"]
    run = _Traced(events, facts, "cpu" if why == "no chip" else "tpu")
    got = grouped_product.read(
        run, "hbm_roofline_pct", "ragged-dot-none", "routed_product_rows",
        "routed_product_calls_per_turn", "routed_product_bytes")
    assert got is None
