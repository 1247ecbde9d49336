"""The standing guards for the chip path on a box with no chip.

* ``chip_smoke.py`` cannot pass without a TPU, and a failed leg fails it;
* the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
  one in-checkout place whatever the working directory;
* an unknown ``device_kind`` is an error, not a default peak;
* a bare ``hvd.init()`` does not open the JAX backend (on a TPU host that
  takes the chips), and ``hvdrun`` pins one chip per local rank;
* "kernels that compile": a flash LM step lowered for TPU devices (the
  compile-only ``v5e:2x2`` topology this installation's libtpu provides)
  carries the Mosaic-compiled kernel on every mesh shape the repo claims,
  including the ones interpret mode on a CPU mesh could never reject.

Every check that needs a fresh interpreter (jax.config, libtpu) is a probe
in ``tests/chip_probes.py``, started early by ``tests/conftest.py``.
"""

import json
import os

import pytest

import chip_probes
from chip_probes import MESHES, REPO


@pytest.fixture(scope="module")
def probes(request):
    """The running probes: conftest's if collection started them, else
    (this file run some other way) a set of our own."""
    early = getattr(request.config, "_chip_probes", None)
    if early is not None:
        yield early  # conftest closes it
        return
    own = chip_probes.Probes()
    yield own
    own.close()


def test_chip_smoke_refuses_to_run_on_the_cpu(probes):
    rc, out, err = probes.result("smoke_on_cpu")
    assert rc != 0
    assert "platform='cpu'" in err and "JAX_PLATFORMS='cpu'" in err, err
    assert '"ok"' not in out, out


def test_failed_leg_fails_the_run(capsys):
    import jax

    import chip_smoke

    def boom(devices, sizes):
        raise RuntimeError("leg made to raise")

    def fine(devices, sizes):
        return {"compile_s": 0.0, "run_s": 0.0}

    devices = jax.devices()
    assert chip_smoke.run(devices, [("fine", fine), ("boom", boom)],
                          None) != 0
    assert '"ok"' not in capsys.readouterr().out
    assert chip_smoke.run(devices, [("fine", fine)], None) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}


@pytest.mark.slow
def test_legs_pass_a_tiny_rehearsal_on_the_cpu_mesh(monkeypatch):
    """The three legs through the real builders at tiny sizes, the way a
    builder rehearses before spending chip time (not in tier-1: ~25 s)."""
    import jax

    # ServingLoop.run setdefaults this in os.environ; keep it to this test.
    monkeypatch.setenv("HVD_TPU_CORE", "py")

    import chip_smoke
    from horovod_tpu.models import resnet

    lm = dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=128)
    tiny = chip_smoke.Sizes(
        resnet=lambda: resnet.ResNetConfig(blocks=(1, 1, 1, 1), width=8,
                                           num_classes=100),
        image=32, images_per_chip=4,
        lm=dict(lm, max_seq_len=128, attn_impl="flash"),
        lm_seqs_per_chip=2, lm_seq=128, attn_shape=(2, 128, 4, 32),
        serve=dict(lm, max_seq_len=64),
        serve_requests=((3, 6), (5, 4), (9, 3)))
    assert chip_smoke.run(jax.devices()[:4], chip_smoke.LEGS, tiny) == 0


def test_set_cache_dir_is_honoured_untouched(probes):
    rc, out, err = probes.result("cache_set")
    assert rc == 0, err
    returned, configured = json.loads(out.strip().splitlines()[-1])
    assert returned == configured == probes.set_dir
    assert os.listdir(probes.set_dir), "nothing was cached there"


def test_unset_cache_dir_is_one_place_in_the_checkout(probes):
    paths = []
    for name in ("cache_unset_a", "cache_unset_b"):
        rc, out, err = probes.result(name)
        assert rc == 0, err
        returned, configured = json.loads(out.strip().splitlines()[-1])
        assert returned == configured
        paths.append(returned)
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")


def test_unknown_device_kind_raises():
    import device_peaks

    assert device_peaks.peak("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        device_peaks.peak("TPU v9 imaginary")


def test_bare_init_does_not_open_the_backend(probes):
    rc, out, err = probes.result("bare_init")
    assert rc == 0, err
    assert "BACKENDS []" in out, out


def test_hvdrun_pins_one_chip_per_local_rank(monkeypatch):
    from horovod_tpu.runner.hosts import SlotInfo
    from horovod_tpu.runner.launch import _CHIP_PIN_VARS, worker_env

    for var in _CHIP_PIN_VARS:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    envs = [worker_env(SlotInfo("localhost", r, 4, r, 4, 0, 1),
                       "127.0.0.1", 1234) for r in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    # children inherit the cache's place
    assert all(e["JAX_COMPILATION_CACHE_DIR"] == "/some/dir" for e in envs)
    # a rank alone on its host owns every chip there: nothing is pinned
    alone = worker_env(SlotInfo("localhost", 0, 1, 0, 1, 0, 1),
                       "127.0.0.1", 1234)
    assert "TPU_VISIBLE_CHIPS" not in alone
    # the user's own setting wins
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    mine = worker_env(SlotInfo("localhost", 1, 4, 1, 4, 0, 1),
                      "127.0.0.1", 1234)
    assert mine["TPU_VISIBLE_CHIPS"] == "2,3"
    assert "TPU_PROCESS_BOUNDS" not in mine


@pytest.mark.parametrize("i", range(len(MESHES)),
                         ids=[json.dumps(m) for m in MESHES])
def test_flash_lm_step_lowered_for_tpu_carries_the_kernel(probes, i):
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    result = json.loads(out.split("RESULT", 1)[1])
    assert result["device_kind"] == "TPU v5 lite"
    assert result["tpu_custom_call"][i] > 0, result


@pytest.mark.parametrize("kernel",
                         ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_flash_kernels_keep_their_names_in_the_lowered_step(probes, kernel):
    """The compiled step's Mosaic instructions are named after the three
    ``pallas_call``s (``flash_fwd.3``, ...), on every mesh: the names the
    profiler shows and ``perfbench/readers/kernel_ms.py`` matches."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    result = json.loads(out.split("RESULT", 1)[1])
    for names in result["kernel_names"]:
        stems = {n.rsplit(".", 1)[0] for n in names}
        assert kernel in stems, names
        assert stems <= {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}, names


@pytest.mark.parametrize("i", range(len(MESHES)),
                         ids=[json.dumps(m) for m in MESHES])
def test_lowered_lm_step_runs_the_forward_kernel_once_a_layer(probes, i):
    """A checkpointed layer keeps the kernel's output and log-sum-exp
    (``transformer.remat_layer``), so the backward scan's body holds no
    second ``flash_fwd``: the compiled step has as many of them as of
    ``flash_bwd_dq``, one in each scan's body."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    names = json.loads(out.split("RESULT", 1)[1])["kernel_names"][i]
    stems = [n.rsplit(".", 1)[0] for n in names]
    assert stems.count("flash_fwd") == stems.count("flash_bwd_dq") > 0, names


@pytest.mark.parametrize("i", range(len(chip_probes.FLASH_ALONE)),
                         ids=[f"s{s}" for s in chip_probes.FLASH_ALONE])
def test_flash_gradient_alone_compiles_for_the_chip_at_the_cells_blocks(
        probes, i):
    """Mosaic takes the three calls at the training cell's block structure
    (``S`` 2048: four blocks of 512 a row, the pairs in scalar memory) and
    at an ``S`` whose block falls under the chip's 128 lanes (2112: blocks
    of 64), one call each under its name; and no call takes or gives the
    per-row numbers as a column ``f32[..., S, 1]``, which the chip pads to
    128 lanes: they lie along the lanes of ``[BH, S // bq, 1, bq]``."""
    import re

    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    calls = json.loads(out.split("RESULT", 1)[1])["flash_alone"][i]
    seq_len = chip_probes.FLASH_ALONE[i]
    assert sorted(name.rsplit(".", 1)[0] for name, _ in calls) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"], calls
    for name, text in calls:
        assert not re.search(r"f32\[[\d,]*\b%d,1\]" % seq_len, text), (
            name, text)
        assert re.search(r"f32\[2,\d+,1,\d+\]", text), (name, text)


def test_flash_lm_step_turns_nothing_round_about_its_kernel(probes):
    """The flash LM step compiled for ``v5e`` at the training cell's widths
    carries the attention kernel's three Mosaic calls, the forward once,
    beside the rotation's (``rope_lanes``: q and k in the forward body,
    q, k and the two gradients in the backward), and in its two scans'
    bodies no ``copy`` or ``transpose`` of a q, k, v, context or gradient
    (``[8,16,2048,128]``, ``[128,2048,128]``, ``[8,2048,2048]``) but ONE:
    the kept context's way back from the layout XLA gives its stack over
    the layers (``wo``'s weight-gradient product reads it positions-minor;
    ``PERF.md`` section 6, PR 44).  The layer hands the kernels
    ``[B, S, H * D]`` and they read and write it in place; with the
    model's ``[B, S, H, D]`` and the fold to ``[B * H, S, D]`` there were
    eight, 67 and 134 MB each."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    found = json.loads(out.split("RESULT", 1)[1])["flash_layout"]
    assert found["scan_bodies"] == 2, found
    stems = [n.rsplit(".", 1)[0] for n in found["kernels"]]
    assert sorted(set(stems)) == ["flash_bwd_dkv", "flash_bwd_dq",
                                  "flash_fwd", "rope_lanes"], found
    assert [stems.count(n) for n in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rope_lanes")] == [
        1, 1, 1, 6], found
    assert len(found["turned"]) <= 1, found
    assert all("bf16[8,2048,2048]" in shape
               for _, shape in found["turned"]), found


@pytest.mark.parametrize("program,update", [
    ("step", "fusion:scatter"), ("install", "fusion:dynamic-update-slice")])
def test_serving_cache_programs_update_in_place_on_the_chip(
        probes, program, update):
    """Compiled for ``v5e`` at the benchmark's cache shape, ``decode_step``
    and the install produce nothing of a layer lane's size besides the
    in-place row or lane update of the caches they were given: no copy of
    the stack, no lane cut out of it, temporaries under one lane, and both
    caches aliased from input to output."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_cache"][program]
    c = chip_probes.SERVE_CACHE
    lane_bytes = 2 * c["slots"] * c["max_seq_len"] * c["d_model"]  # bfloat16
    assert [op for _, op in got["big_ops"]] == [update, update], got
    assert got["temp_bytes"] < lane_bytes, got
    # both caches, and the two counters beside them (the chip gives a
    # scalar a buffer of 512 bytes)
    assert got["alias_bytes"] == 2 * c["n_layers"] * lane_bytes + 2 * 512, got


@pytest.mark.parametrize("program,update", [
    ("step", "fusion:scatter"), ("prefill", "fusion:dynamic-update-slice")])
def test_dense_decoder_reads_its_projections_in_place_on_the_chip(
        probes, program, update):
    """The engine holds ``wq``, ``wk`` and ``wv`` as one leaf, so its step
    and its prefill, compiled for ``v5e`` at the benchmark's widths from
    the HELD parameters, make one plain 2-D product of them that takes
    the stack and the layer's index: nothing as large as one layer's
    projection comes out of either besides the writes of the keys and
    values (the step's row scatters into the caches, the prefill's
    stacking of its layers' lanes) and the compiler's own asynchronous
    moves into fast memory.  Held as three ``[L, D, H, HD]`` leaves, each
    layer's three are cut out of their stacks by a ``dynamic-slice``
    fusion of their own before a windowed convolution reads them."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_cache"][program]
    prefetch = {"copy-start", "copy-done", "slice-start", "slice-done",
                "custom-call"}
    ops = [op for _, op in got["weight_ops"]]
    assert ops.count(update) == 2, got
    assert set(ops) <= {update} | prefetch, got


@pytest.mark.parametrize("program,updates", [
    ("step", {"fusion:dynamic-update-slice", "fusion:scatter"}),
    ("install", {"fusion:dynamic-update-slice", "dynamic-update-slice"})])
def test_serving_state_programs_update_in_place_on_the_chip(
        probes, program, updates):
    """models/jamba.py's two kinds of slot state, compiled for ``v5e`` at
    the benchmark cell's state shapes: the step and the install produce
    nothing of one layer's recurrent state's size (nor a weight matrix cut
    out of its stack) besides the in-place updates of the state they were
    given (an update-slice a run of Mamba layers, a row scatter a cache an
    attention layer) and the compiler's own asynchronous moves of weights
    into fast memory (``copy-start``, ``slice-start``, the buffers it
    allocates for them); all four state arrays and the two counters are
    aliased from input to output, unpadded, and the temporaries stay under
    one layer's state."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_state"][program]
    c = chip_probes.SERVE_STATE
    d_inner, n_mamba, n_attn = 2 * c["hidden_size"], 6, 2
    layer_bytes = 4 * c["slots"] * 16 * d_inner
    held = (n_mamba * (layer_bytes + 2 * 3 * c["slots"] * d_inner)
            + 2 * n_attn * 2 * c["slots"] * c["max_seq_len"] * 128 + 2 * 512)
    prefetch = {"copy-start", "copy-done", "slice-start", "slice-done",
                "custom-call"}
    assert {op for _, op in got["big_ops"]} <= updates | prefetch, got
    assert {op for _, op in got["big_ops"]} & updates, got
    assert got["temp_bytes"] < layer_bytes, got
    assert got["alias_bytes"] == held, got


@pytest.mark.parametrize("program,updates", [
    ("step", {"fusion:scatter"}),
    ("install", {"fusion:dynamic-update-slice", "dynamic-update-slice"})])
def test_latent_lanes_programs_update_in_place_on_the_chip(
        probes, program, updates):
    """models/latent_moe.py's lanes (a latent and one rotary key a
    position), compiled for ``v5e`` at the benchmark cell's lane shapes:
    the step (the absorbed form, the experts' grouped products) and the
    install produce nothing of one layer's lane of latents besides the
    in-place updates of the two caches they were given and the
    compiler's own asynchronous moves of weights into fast memory: no
    copy of a stack, no lane cut out of it, no expanded key; the two
    caches and the six counters are aliased from input to output and the
    temporaries stay under one lane."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_latent"][program]
    c = chip_probes.SERVE_LATENT
    lane_bytes = 2 * c["slots"] * c["max_seq_len"] * 512        # bfloat16
    # the rotary keys' lane is an eighth of the latents'; a counter is a
    # scalar, and the chip gives a scalar a buffer of 512 bytes
    held = c["num_hidden_layers"] * (lane_bytes + lane_bytes // 8) + 6 * 512
    prefetch = {"copy-start", "copy-done", "slice-start", "slice-done"}
    assert {op for _, op in got["big_ops"]} <= updates | prefetch, got
    assert {op for _, op in got["big_ops"]} & updates, got
    assert got["temp_bytes"] < lane_bytes, got
    assert got["alias_bytes"] == held, got


@pytest.mark.parametrize("program,updates", [
    ("step", {"fusion:scatter"}),
    ("install", {"fusion:dynamic-update-slice", "dynamic-update-slice"})])
def test_sparse_lanes_programs_update_in_place_on_the_chip(
        probes, program, updates):
    """models/latent_moe.py with an indexer and a share of its experts,
    compiled for ``v5e`` at the ``deepseek-v3.2`` cell's lane shapes and
    attention widths (128 heads on one latent, 64 index heads, the 2048
    best of 18432 positions): Mosaic takes the two kernels (``index_select``
    with its 46 counting passes in fast memory, ``sparse_attn``), and the
    step and the install produce nothing of one layer's lane of latents
    besides the in-place updates of the three caches they were given; the
    three caches and the nine counters are aliased from input to
    output."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_sparse"][program]
    c = chip_probes.SERVE_SPARSE
    lane_bytes = 2 * c["slots"] * c["max_seq_len"] * 512        # bfloat16
    held = c["num_hidden_layers"] * (
        lane_bytes + lane_bytes // 8 + lane_bytes // 4) + 9 * 512
    prefetch = {"copy-start", "copy-done", "slice-start", "slice-done"}
    assert {op for _, op in got["big_ops"]} <= updates | prefetch, got
    assert {op for _, op in got["big_ops"]} & updates, got
    assert got["temp_bytes"] < lane_bytes, got
    assert got["alias_bytes"] == held, got


@pytest.mark.parametrize("program,updates", [
    ("step", {"custom-call"}),
    ("install", {"fusion:dynamic-update-slice", "dynamic-update-slice"})])
def test_retention_state_programs_pass_over_the_state_once_on_the_chip(
        probes, program, updates):
    """models/retention.py's state (a [128, 8320] float32 matrix a slot a
    key/value head a layer: 34 MB), compiled for ``v5e`` at the benchmark
    cell's state shapes: the step produces nothing of one layer's state
    matrices besides the kernel ``retention_step``'s in-place pass (no
    dot over the state and then an update of it, no layer cut out of the
    stack), the install nothing besides its in-place writes; both state
    arrays and the two counters are aliased from input to output and the
    temporaries stay under one layer's state."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_retention"][program]
    c = chip_probes.SERVE_RETENTION
    layer_bytes = 4 * c["slots"] * 8 * 128 * 8320
    # S and z (1/128 of it) a layer; a counter is a scalar, and the chip
    # gives a scalar a buffer of 512 bytes
    held = c["num_hidden_layers"] * (layer_bytes + layer_bytes // 128) \
        + 2 * 512
    prefetch = {"copy-start", "copy-done", "slice-start", "slice-done"}
    assert {op for _, op in got["big_ops"]} <= updates | prefetch, got
    assert {op for _, op in got["big_ops"]} & updates, got
    if program == "step":
        assert [n.split(".")[0] for n, op in got["big_ops"]
                if op == "custom-call"] == ["retention_step"], got
    assert got["temp_bytes"] < layer_bytes, got
    assert got["alias_bytes"] == held, got


@pytest.mark.parametrize("program,updates", [
    ("step", {"custom-call", "fusion:scatter"}),
    ("install", {"fusion:dynamic-update-slice", "dynamic-update-slice"})])
def test_ssd_state_programs_update_in_place_on_the_chip(
        probes, program, updates):
    """models/ssd_moe.py's state, compiled for ``v5e`` at the benchmark
    cell's shapes and published widths: the step and the install produce
    nothing of one layer's recurrent state's size besides the in-place
    updates of the state they were given (the step ONE Mosaic call
    ``ssd_step`` a Mamba-2 layer on the whole stacked state, aliased, and a
    row scatter a cache: no fusion over the state, no layer cut out of the
    stack or put back; the install an update-slice a leaf) and the
    compiler's own asynchronous moves: NO
    copy of the routed experts' stacks (held unpadded, 1856 columns are
    laid out the other way round and the grouped product copied all 2.5 GB
    a turn) and none of a key/value lane (held heads-first, both lanes went
    there and back around the scatter); the four state arrays and the ten
    counters are aliased from input to output and the temporaries stay
    under one layer's state."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_ssd"][program]
    c = chip_probes.SERVE_SSD
    layer_bytes = 4 * c["slots"] * 64 * 64 * 128
    held = (4 * (layer_bytes + 2 * 3 * c["slots"] * 6144)
            + 2 * 2 * c["slots"] * c["max_seq_len"] * 2 * 128 + 10 * 512)
    prefetch = {"copy-start", "copy-done", "slice-start", "slice-done"}
    assert {op for _, op in got["big_ops"]} <= updates | prefetch, got
    assert {op for _, op in got["big_ops"]} & updates, got
    assert got["temp_bytes"] < layer_bytes, got
    assert got["alias_bytes"] == held, got


def test_ssd_decode_step_is_one_kernel_a_mamba_layer_on_the_chip(probes):
    """The decode step compiled for ``v5e`` at the cell's shapes: what it
    produces of the recurrent state's size is the result of ONE Mosaic
    call ``ssd_step`` a Mamba-2 layer (ops/pallas_ssd.py: four layers, the
    whole stacked state in and out, aliased), and nothing else of that
    size touches it: no ``fusion`` over ``[Lm, B, ...]`` (XLA's update in
    place and its read-out were two), no copy, no update-slice."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_ssd"]["step"]
    by_op = {}
    for name, op in got["big_ops"]:
        by_op.setdefault(op, []).append(name.split(".")[0])
    assert by_op.pop("custom-call") == ["ssd_step"] * 4, got
    # besides: the two key/value lanes' row scatters
    assert set(by_op) == {"fusion:scatter"}, got


@pytest.mark.parametrize("program,updates", [
    ("step", {"fusion:scatter"}),
    ("install", {"fusion:dynamic-update-slice", "dynamic-update-slice"})])
def test_conv_moe_lanes_update_in_place_on_the_chip(probes, program, updates):
    """models/conv_moe.py's state, compiled for ``v5e`` at the benchmark
    cell's lanes (192 slots x 2048 positions x 8 heads of 64 held side by
    side, 512 a position): the step and the install produce nothing of one
    layer's lane's size besides the in-place updates of the lanes they
    were given (a row scatter a cache; an update-slice a leaf) and the
    compiler's own asynchronous moves.  Held ``[.., 8, 64]`` the last axis
    is padded to the chip's 128 lanes, the lanes take twice their bytes
    and the step copies both whole (2.25 GB each at the cell's depth: it
    does not fit the chip; PR 49).  Every state array and the seven counters
    are aliased from input to output and the temporaries stay under one
    layer's lane: the attention's two products read a lane where it
    lies."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    got = json.loads(out.split("RESULT", 1)[1])["serve_conv"][program]
    c = chip_probes.SERVE_CONV
    lane_bytes = 2 * c["slots"] * c["max_seq_len"] * 8 * 64
    held = (2 * 2 * lane_bytes + 5 * 2 * 2 * c["slots"] * 2048 + 7 * 512)
    prefetch = {"copy-start", "copy-done", "slice-start", "slice-done"}
    assert {op for _, op in got["big_ops"]} <= updates | prefetch, got
    assert {op for _, op in got["big_ops"]} & updates, got
    assert got["temp_bytes"] < lane_bytes, got
    assert got["alias_bytes"] == held, got


@pytest.mark.parametrize("probe,calls", [
    ("serve_conv", 2), ("serve_ssd", 1), ("serve_state", 2)])
def test_grouped_lanes_take_the_decode_kernel_in_place_on_the_chip(
        probes, probe, calls):
    """The three steps that call ``layers._grouped_attention``, compiled for
    ``v5e`` at their cells' lanes: every attention layer (in
    models/jamba.py's loops, every run of them) is ONE Mosaic call
    ``decode_attn`` over the stacked caches as they are held: the
    ``lfm2-8b-a1b`` cell's ``[La, 192, 2048, 8 x 64]`` as they lie (two
    attention layers in this probe, three in the cell), the
    ``nemotron-3-nano-30b-a3b`` cell's ``[1, 96, 4096, 2, 128]`` as 8192
    keys of 128 a slot, the ``jamba2-3b`` cell's ``[2, 64, 1, 1536, 128]``
    without its axis of one.  Each view is free: the step produces NO
    copy, transposition or other fusion of a lane's size besides the rows'
    scatters (the in-place tests above hold the aliased bytes to the state
    alone), so each layout takes the kernel (``layers.lane_block``)."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    step = json.loads(out.split("RESULT", 1)[1])[probe]["step"]
    assert step["kernel_calls"].get("decode_attn") == calls, step
    moved = [[name, op] for name, op in step["big_ops"]
             if op in ("copy", "transpose") or op.startswith("fusion:")
             and op not in ("fusion:scatter", "fusion:dynamic-update-slice")]
    assert moved == [], step


@pytest.mark.parametrize("probe,fused", [
    ("serve_conv", True), ("serve_latent", True), ("serve_sparse", False),
    ("serve_ssd", False)])
def test_routed_experts_take_the_one_kernel_by_shape_on_the_chip(
        probes, probe, fused):
    """The decode steps that call ``experts.routed_ffn``, compiled for
    ``v5e``: at the ``lfm2-8b-a1b`` cell's 192 slots x 4 pairs over 8
    gated experts all held (24 an expert and more: ``experts.one_kernel``)
    Mosaic takes ``routed_ffn_rows`` at the published widths (2048 x 1792
    of stacks held 2048 wide) and no grouped product is left.  The rule
    reads shapes, not models: ``latent_moe``'s probe has the
    ``glm-4.7-flash`` cell's 64 slots x 4 pairs over EIGHT experts where
    the cell has 64 (32 an expert, not 4), so it takes the kernel too, 256
    columns wide; a share of the experts and relu2 experts keep their
    grouped products and have no such call."""
    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    kernels = json.loads(out.split("RESULT", 1)[1])[probe]["step"]["kernels"]
    assert ("routed_ffn_rows" in kernels) is fused, kernels
    assert any(k.startswith("ragged-dot") for k in kernels) is not fused, \
        kernels


@pytest.mark.parametrize("probe", ["serve_cache", "serve_state",
                                   "serve_latent", "serve_retention"])
def test_serving_step_compiled_for_the_chip_converts_no_weight(probes, probe):
    """The engine holds the weights in the compute type (the dense
    decoder's float32 ones rounded once when it is built), so the decode
    step compiled for ``v5e`` has no ``convert`` that produces a bfloat16
    array of a weight's dimensions: given float32 weights, XLA hoists
    those converts out of the layer loop and runs them on every turn."""
    import jax

    from horovod_tpu.models import jamba, latent_moe, retention
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving.decode import slot_model

    rc, out, err = probes.result("lower_for_tpu")
    assert rc == 0, err[-3000:]
    converts = json.loads(out.split("RESULT", 1)[1])[probe]["step"]["converts"]
    sizes, model, config, cast = {
        "serve_cache": (chip_probes.SERVE_CACHE, tfm, tfm.TransformerConfig,
                        chip_probes.DENSE_CAST_LEAVES),
        "serve_state": (chip_probes.SERVE_STATE, jamba, jamba.JambaConfig,
                        chip_probes.JAMBA_CAST_LEAVES),
        "serve_latent": (chip_probes.SERVE_LATENT, latent_moe,
                         latent_moe.LatentMoEConfig,
                         chip_probes.LATENT_MOE_CAST_LEAVES),
        "serve_retention": (chip_probes.SERVE_RETENTION, retention,
                            retention.RetentionConfig,
                            chip_probes.RETENTION_CAST_LEAVES)}[probe]
    cfg = config(**{k: v for k, v in sizes.items() if k != "slots"})
    given = jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0))
    held = jax.eval_shape(slot_model(cfg, cfg.max_seq_len).held, given)
    weights = (chip_probes.weight_dims(given, cast)
               | chip_probes.weight_dims(held, cast))
    assert converts, "the step rounds its activations at least"
    assert [d for d in converts if chip_probes.dims_key(d) in weights] == []
