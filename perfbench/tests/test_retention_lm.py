"""The ``retention_lm`` family's benchmark files: the cell's rehearsal runs
to a ``correct`` result line with the metrics it lists, the configuration
holds the catalog's row, the reference differs from its int8 control, and
the counts of parameters, of the bytes a decode turn and the state pass
must move and of the operations a prefill needs against hand counts."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import harness, retention_lm_count as count
from perfbench import trace as tr
from perfbench.readers import kernel_hbm_roofline, kernel_ms

CELL = "brumby-14b_serve_longform"
HERE = Path(__file__).resolve().parents[1]
CONFIG = json.loads((HERE / "configs" / "brumby-14b.json").read_text())
TRAFFIC = json.loads((HERE / "traffic" / "longform_open.json").read_text())
SIZES = {**CONFIG, "num_hidden_layers": 5}


def _run_cell(capsys, trace):
    harness.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                  "3", "--trace", trace, "--rehearsal"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal_is_correct_and_reports_its_end_to_end_metrics(capsys):
    out = _run_cell(capsys, "0")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["checks"]) == {"served_token_logit_gap",
                                  "slot_state_drift", "step_state_drift",
                                  "requests_failed_or_short"}
    assert set(out["metrics"]) == {"rehearsal_setup_s",
                                   "rehearsal_latency_per_token_p50"}


def test_traced_rehearsal_reports_every_metric_the_cell_lists(capsys):
    """Every per-layer metric that lists the cell, but those that only a
    chip's trace or peak can give."""
    out = _run_cell(capsys, "1")
    assert out["correct"] is True
    listed = {m["name"] for m in harness.Cell(CELL).metrics("per_layer")}
    assert {"state_pass_ms_per_turn.serve", "state_pass_roofline_pct.serve",
            "state_live_share_pct.serve", "recurrent_state_gb.serve",
            "prefill_ms_per_ktoken.serve", "prefill_mfu_pct.serve",
            "decode_hbm_roofline_pct.serve"} <= listed
    assert "kv_cache_gb.serve" not in listed
    chip_only = {"device_idle_pct.serve", "peak_hbm_gb.serve",
                 "decode_hbm_roofline_pct.serve", "prefill_mfu_pct.serve",
                 "state_pass_ms_per_turn.serve",
                 "state_pass_roofline_pct.serve"}
    idle = {n for n in listed if n.startswith("idle_")}
    got = {k[len("rehearsal_"):] for k in out["metrics"]}
    assert got >= listed - chip_only - idle, listed - got
    m = out["metrics"]
    # the rehearsal's state: 3 layers x 4 slots x 2 heads x 256 rows x
    # (16 + 1) float32
    assert m["rehearsal_recurrent_state_gb.serve"]["value"] \
        == pytest.approx(3 * 4 * 2 * 256 * 17 * 4 / 1e9)
    assert 0 < m["rehearsal_state_live_share_pct.serve"]["value"] <= 100
    assert m["rehearsal_prefill_ms_per_ktoken.serve"]["value"] > 0


def test_a_token_altered_where_the_loop_produces_it_is_not_correct(
        capsys, monkeypatch):
    """Since PR 29 the loop takes its tokens from ``dispatch`` (the step
    queued ahead) and ``read``, never from ``DecodeEngine.step``: alter
    them THERE, so that every served token but a request's first is one
    the model did not choose and the next step is fed it."""
    from horovod_tpu.serving.decode import DecodeEngine

    real = DecodeEngine.dispatch

    def altered(self):
        real(self)
        nxt = (self._unread.pop() + 1) % self.cfg.vocab_size
        self.tok = nxt
        self._unread.append(nxt)

    monkeypatch.setattr(DecodeEngine, "dispatch", altered)
    out = _run_cell(capsys, "0")
    assert out["correct"] is False and out["failed"] == 0
    gap = out["checks"]["served_token_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_configuration_holds_the_catalog_row_unchanged():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Brumby-14B-Base")
    assert CONFIG["source"] == row["source_url"]
    cut = set(CONFIG["reduced"])
    assert cut == {"num_hidden_layers"}
    assert {k: CONFIG[k] for k in row["config"] if k not in cut} \
        == {k: v for k, v in row["config"].items() if k not in cut}
    assert (CONFIG["num_hidden_layers"],
            row["config"]["num_hidden_layers"]) == (5, 40)
    entry = next(c for c in harness.load_json(harness.ROOT / "BENCHMARK.json")
                 ["configs"] if c["name"] == "brumby-14b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]
    for item in ("degree", "gate", "normaliser", "qk_norm_and_rope", "state",
                 "init", "kv_switch"):
        assert item in CONFIG["assumed"]
    assert "8 stages" in CONFIG["deployment"] \
        and "BOTH" in CONFIG["deployment"]


def test_the_traffic_is_the_issues():
    assert TRAFFIC["prompt_tokens"] == {
        "median": 768, "sigma": 0.8, "grid": [256, 512, 1024, 2048, 4096]}
    assert TRAFFIC["output_tokens"] == {"median": 256, "sigma": 0.5,
                                        "min": 64, "max": 640}
    assert TRAFFIC["rate_rps"] == pytest.approx(0.8 * TRAFFIC["knee_rps"])
    assert TRAFFIC["preroll_s"] == 10
    serve = CONFIG["serve"]
    assert (serve["max_batch"], serve["cache_len"],
            serve["check_requests"]) == (32, 4096 + 640, 6)


def test_parameters_by_hand():
    p = count.retention_lm_params(SIZES)
    # q and o 5120 x 5120 each, k and v 5120 x 1024 each, the gate 5120 x 8
    assert p["retention"] == 2 * 26_214_400 + 2 * 5_242_880 + 40_960 \
        == 62_955_520
    assert p["ffn"] == 3 * 5120 * 17408 == 267_386_880
    assert p["retention"] + p["ffn"] == 330_342_400          # 330.34 M a layer
    assert p["embed"] == 151_936 * 5120 == 777_912_320
    held = 5 * 330_342_400 + 2 * 777_912_320
    assert 2 * held / 1e9 == pytest.approx(6.415, abs=1e-3)     # GB, bfloat16
    whole = 40 * 330_342_400 + 2 * 777_912_320
    assert whole / 1e9 == pytest.approx(14.77, abs=5e-3)        # B parameters


def test_the_state_by_hand():
    # 128 x 129 / 2 = 8256 rows of the symmetric square, held as 65 x 128
    assert count.state_rows(SIZES) == 8256
    assert count.state_rows(SIZES, pad_to=128) == 8320
    a_slot_a_layer = 8 * 8256 * 129 * 4
    assert a_slot_a_layer == 34_080_768                         # 34.1 MB
    assert count.state_bytes(SIZES, 32) == 32 * 5 * a_slot_a_layer \
        == 5_452_922_880
    # what the program holds: the padded rows (memory, not work)
    assert count.state_bytes(SIZES, 32, pad_to=128) \
        == 32 * 5 * 8 * 8320 * 129 * 4 == 5_495_193_600
    # the kernel's part: the needed rows of S in and out, no normaliser
    assert count.state_pass_bytes(SIZES, 32) \
        == 2 * 32 * 5 * 8 * 128 * 8256 * 4 == 10_821_304_320


def test_a_decode_turn_moves_the_weights_once_and_the_state_twice():
    # 5 layers and the head (the embedding is a lookup), bfloat16: 4.86 GB
    weights = 2 * (5 * 330_342_400 + 777_912_320)
    assert weights / 1e9 == pytest.approx(4.859, abs=1e-3)
    got = count.retention_lm_decode_turn_bytes(SIZES, 32)
    assert got == weights + 2 * 5_452_922_880
    assert got / 1e9 == pytest.approx(15.77, abs=5e-3)
    assert got / 819e9 * 1e3 == pytest.approx(19.25, abs=0.02)  # ms
    assert 2 * 5_452_922_880 / got == pytest.approx(0.692, abs=1e-3)


def test_a_prefill_needs_the_cheaper_form_of_the_retention():
    matmuls = 2.0 * 5 * 330_342_400
    head = 2.0 * 777_912_320
    end_state = 2.0 * 8 * 8256 * 128                # an outer product a key
    for n in (256, 4096):
        quadratic = 40 * 4.0 * 128 * n * n / 2 + end_state * n
        assert count.retention_lm_prefill_flops(SIZES, n) \
            == pytest.approx(matmuls * n + head + 5 * quadratic)
    # a 4096-token prompt: 13.53 TFLOP of matmuls, 5 x (0.172 of weights
    # and products + 0.069 of the state it ends in) = 1.21 of retention
    assert count.retention_lm_prefill_flops(SIZES, 4096) / 1e12 \
        == pytest.approx(14.74, abs=0.01)
    # past 2 x 8256 positions the recurrence is the cheaper form
    n = 20_000
    recurrent = 2.0 * 40 * 8256 * 128 * n + end_state * n
    assert count.retention_lm_prefill_flops(SIZES, n) \
        == pytest.approx(matmuls * n + head + 5 * recurrent)


def test_the_reference_is_not_its_int8_control():
    import jax
    import jax.numpy as jnp

    from perfbench.reference import retention_lm as ref

    sizes = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                 num_hidden_layers=3, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
                 rope_theta=1e6)
    weights = ref.make_weights(jax.random.PRNGKey(3), sizes)
    assert {a.dtype.name for a in jax.tree.leaves(weights)} == {"bfloat16"}
    assert float(weights["layers"]["bg"][0, 0]) == 6.0
    tokens = jnp.arange(1, 40, dtype=jnp.int32)
    want = ref.Forward(sizes).logits(weights, tokens)
    control = ref.Forward(sizes, quant=True).logits(weights, tokens)
    assert float(jnp.abs(control - want).max()) > 0.02 * float(jnp.std(want))
    # the rows asked for are the rows of the whole
    some = ref.Forward(sizes).logits(weights, tokens, 10, 7)
    assert jnp.array_equal(some, want[10:17])


SMALL = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
             num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
             rope_theta=1e6)


def test_what_positions_leave_behind_is_the_recurrence_unrolled():
    """``left_behind``'s closed form against the recurrence as published,
    a position at a time in float64: ``M <- e^gamma M + v k k^T``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.reference import retention_lm as ref

    weights = ref.make_weights(jax.random.PRNGKey(5), SMALL)
    lp = {k: v[1].astype(jnp.float32)
          for k, v in weights["layers"].items()}
    lp["bg"] = jnp.asarray([-0.5, 1.0])             # gates far from one
    x = jax.random.normal(jax.random.PRNGKey(6), (23, 32))
    last = 17                                       # rows past it: padding
    M, n = ref.left_behind(lp, x, last, eps=1e-6, theta=1e6)
    _, k, v, gamma = map(np.float64, ref._heads(
        lp, x, eps=1e-6, theta=1e6, quant=False))
    want_M, want_n = np.zeros((2, 16, 16, 16)), np.zeros((2, 16, 16))
    for t in range(last + 1):
        kk = np.einsum("hi,hj->hij", k[t], k[t])
        decay = np.exp(gamma[t])[:, None, None]
        want_n = decay * want_n + kk
        want_M = decay[..., None] * want_M \
            + np.einsum("hv,hij->hvij", v[t], kk)
    np.testing.assert_allclose(M, want_M, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(n, want_n, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("fault,state,step", [
    (None, (0.0, 1e-4), (0.0, 1e-5)),
    ("state_bf16", (1e-3, 0.1), (1e-3, 0.5)),
    ("no_normaliser", (0.5, 9), None)])
def test_the_state_checks_read_the_states_precision(monkeypatch, fault,
                                                    state, step):
    """Requests served again through ``DecodeEngine`` in float32, more of
    them than slots: the state each leaves lies on the reference's closed
    form, and twins fed the same tokens keep a multiple of the difference
    they began with; a state rounded to bfloat16 a step moves both
    numbers by orders of magnitude (the logits hardly see it: PERF.md
    section 6); a step that forgets ``z`` is far from the reference, and
    its twins' ``z`` no longer differ at all: not a number, not correct."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import retention as R
    from perfbench.jobs import retention_lm_serve as job
    from perfbench.reference import retention_lm as ref
    from perfbench.tools import retention_limits

    if fault:
        for name in ("prefill_request", "decode_step"):
            monkeypatch.setattr(R, name, getattr(R, name))  # restored after
        retention_limits.lay_fault(fault)
    weights = ref.make_weights(jax.random.PRNGKey(9), SMALL)
    rng = np.random.default_rng(9)
    rows = [{"prompt": rng.integers(1, 96, size=p).tolist(),
             "tokens": rng.integers(1, 96, size=n).tolist()}
            for p, n in ((9, 30), (14, 1), (5, 17))]
    twins = job.twin_requests([32, 4, 16], rng, 96)
    assert [(len(r["prompt"]), len(r["tokens"])) for r in twins] \
        == [(4, 1), (4, 1), (4, 13), (4, 13)]
    assert twins[0]["prompt"] == twins[2]["prompt"] != twins[1]["prompt"] \
        == twins[3]["prompt"] and twins[2]["tokens"] == twins[3]["tokens"]
    in_float32 = {**SMALL, "compute_dtype": jnp.float32}
    held = job.served_again(weights, in_float32,
                            {"max_batch": 2, "cache_len": 48}, rows + twins)
    assert [S.shape for S, _ in held] == [(3, 2, 16, 256)] * 7
    got = job.reference_gaps(weights, SMALL, rows, held, 48, 30)
    assert state[0] <= got["state_drift"] <= state[1]
    assert got["tokens_compared"] == 48
    drift = job.step_drift(*held[-4:])
    assert math.isnan(drift) if step is None else step[0] <= drift <= step[1]


# -- the kernel's readers ------------------------------------------------------

MOSAIC = ('%retention_step.10 = (f32[32,8,8,128], f32[5,32,8,128,8320]) '
          'custom-call(...), custom_call_target="tpu_custom_call"')


def _traced_run(ops, platform="tpu", facts=None):
    t = tr.Trace(ops={0: [tr.Event(n, s, e) for n, s, e in ops]},
                 async_ops={}, modules={}, host={})
    return SimpleNamespace(
        facts={"trace": t, "trace_window": (0, 10 ** 12), **(facts or {})},
        devices=[SimpleNamespace(platform=platform,
                                 device_kind="TPU v5 lite")])


def test_the_kernels_readers_are_its_time_a_step_and_bytes_over_peak():
    ms = 10 ** 6
    run = _traced_run(
        [(MOSAIC, 0, 3 * ms), ("%fusion.1 = f32[8] fusion()", 3 * ms, 4 * ms),
         (MOSAIC, 4 * ms, 7 * ms)],
        facts={"steps": 2, "state_pass_bytes": 8.19e8})
    assert kernel_ms.read(run, "retention_step") == pytest.approx(3.0)
    # 0.819 GB at 819 GB/s is 1 ms of the 3
    assert kernel_hbm_roofline.read(
        run, "retention_step", "state_pass_bytes") == pytest.approx(100 / 3)


@pytest.mark.parametrize("why", ["no kernel", "no fact", "not a chip"])
def test_the_kernels_roofline_reads_nothing_where_there_is_nothing(why):
    ms = 10 ** 6
    ops = [("%fusion.1 = f32[8] fusion()", 0, ms)] if why == "no kernel" \
        else [(MOSAIC, 0, ms)]
    facts = {"steps": 1} if why == "no fact" \
        else {"steps": 1, "state_pass_bytes": 1e9}
    run = _traced_run(ops, "cpu" if why == "not a chip" else "tpu", facts)
    assert kernel_hbm_roofline.read(
        run, "retention_step", "state_pass_bytes") is None
