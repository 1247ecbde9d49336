"""models/retention.py, ops/pallas_retention.py and the slot state behind
``DecodeEngine`` that is a matrix a layer and no cache of positions.

A tiny configuration (3 layers, 4 query heads on 2 key/value heads of 16:
the symmetric square has 136 rows, held as 256), float32 throughout,
seeded weights whose gates are FAR from 1 (0.27 to 0.82: a state that
forgets in a few positions, so a wrong fade shows), on the CPU:

* ``phi(q) . phi(k) = (q . k)^2``, over head sizes;
* the kernel ``retention_step`` (interpreted) is the dot and the update it
  replaces, in place, a layer of the stack at a time;
* the prompt form: ``forward`` equals the benchmark's plain reference
  (``perfbench/reference/retention_lm.py``) on logits, whatever the block
  of query rows, and the state it ENDS in is the step form's, token by
  token;
* prefill and then 24 decode steps through ``DecodeEngine`` against the
  reference's ONE quadratic pass: the two forms are one function;
* a slot's output is independent of its neighbours', and an install
  leaves nothing of the last tenant in ``S`` or ``z``;
* the compiled step and install alias all the state they are given (the
  chip's programs are pinned to one pass in tests/test_chip_smoke.py);
* the two device counters and ``state_live_share`` on ``/stats``; a mesh
  is refused; ``ServingLoop`` end to end over HTTP.
"""

import http.client
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_probes import serve_cache_programs
from horovod_tpu.models import jamba, latent_moe, layers, retention
from horovod_tpu.models import transformer as tfm
from horovod_tpu.models.retention import RetentionConfig
from horovod_tpu.ops.pallas_retention import block_for, retention_step
from horovod_tpu.serving import DecodeEngine, ServingLoop
from horovod_tpu.serving.scheduler import Scheduler
from horovod_tpu.telemetry import registry as tmx
from perfbench.reference import retention_lm as ref

SIZES = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
             num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
             rope_theta=10000.0)
L, KVH, HD, ROWS = 3, 2, 16, 256
CACHE_LEN = 64
CFG = RetentionConfig(max_seq_len=CACHE_LEN,
                      compute_dtype=jnp.float32, param_dtype=jnp.float32,
                      **SIZES)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Prompts of a few dozen positions cross block boundaries."""
    monkeypatch.setattr(retention, "PROMPT_BLOCK", 4)


@pytest.fixture(scope="module")
def params():
    """The reference's seeded weights (bfloat16 values), held in float32,
    with the gates' biases spread over -1 .. 1.5 (the seeded +6 would make
    every gate 0.9975 and a wrong fade invisible in 30 positions)."""
    made = ref.make_weights(jax.random.PRNGKey(7), SIZES)
    made = jax.tree.map(lambda a: a.astype(jnp.float32), made)
    made["layers"]["bg"] = jnp.linspace(-1.0, 1.5, L * KVH).reshape(L, KVH)
    return made


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=n)]


# -- (a) the symmetric square, the state's shape ------------------------------


@pytest.mark.parametrize("head_dim", [2, 8, 16, 64, 128])
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(head_dim):
    rows = RetentionConfig(head_dim=head_dim).state_rows
    assert rows % 128 == 0 and 0 <= rows - head_dim * (head_dim + 1) // 2 < 128
    q, k = jax.random.normal(jax.random.PRNGKey(head_dim), (2, 5, head_dim))
    got = jnp.sum(retention.phi(q, rows) * retention.phi(k, rows), axis=-1)
    np.testing.assert_allclose(got, jnp.sum(q * k, axis=-1) ** 2, rtol=2e-5,
                               atol=1e-5)
    # the rows past the square are zero: they hold nothing
    assert not np.asarray(retention.phi(q, rows))[
        :, head_dim * (head_dim + 1) // 2:].any()


def test_the_published_state_is_a_matrix_a_layer_and_no_lane():
    cell = RetentionConfig(num_hidden_layers=5)
    assert cell.state_rows == 8320 and 8256 <= cell.state_rows <= 8704
    state = jax.eval_shape(lambda: retention.init_state(cell, 32, 4736))
    assert "kv" not in state
    assert jax.tree.map(lambda a: (a.shape, a.dtype.name),
                        state["recurrent"]) == (
        ((5, 32, 8, 128, 8320), "float32"), ((5, 32, 8, 8320), "float32"))
    held = sum(a.size * 4 for a in jax.tree.leaves(state["recurrent"]))
    assert held == 32 * 5 * 8 * 8320 * 129 * 4          # 5.495 GB
    # cache_len sizes nothing: the position cap alone
    assert jax.eval_shape(lambda: retention.init_state(cell, 32, 64)) == state


def test_the_module_shares_the_rotation_the_norm_and_the_feed_forward():
    for name in ("_rope", "_rmsnorm", "_dense_ffn", "_logits", "_at"):
        assert getattr(retention, name) is getattr(layers, name)
        assert getattr(latent_moe, name) is getattr(layers, name)
    assert tfm._rope is layers._rope and jamba._at is layers._at


# -- (b) the kernel -----------------------------------------------------------


@pytest.mark.parametrize("layer,n_q", [(0, 2), (1, 5), (2, 1)])
def test_the_kernel_is_the_dot_and_the_update_it_replaces(layer, n_q):
    B, rows = 3, 384
    assert block_for(rows) == 384 and block_for(8320) == 1664
    keys = jax.random.split(jax.random.PRNGKey(layer), 4)
    S = jax.random.normal(keys[0], (3, B, KVH, HD, rows))
    phi = jax.random.normal(keys[1], (B, KVH, 8, rows))
    v = jax.random.normal(keys[2], (B, KVH, HD))
    decay = jax.random.uniform(keys[3], (B, KVH))
    new, answers = jax.jit(
        lambda *a: retention_step(*a, n_q=n_q))(
            S, layer, phi, v, decay)
    np.testing.assert_allclose(
        answers, jnp.einsum("bkgd,bkvd->bkgv", phi[:, :, :n_q], S[layer],
                            precision="highest"), rtol=1e-5, atol=1e-5)
    want = S.at[layer].set(decay[..., None, None] * S[layer]
                           + v[..., None] * phi[:, :, n_q][:, :, None])
    np.testing.assert_allclose(new, want, rtol=1e-6, atol=1e-6)
    others = [i for i in range(3) if i != layer]
    np.testing.assert_array_equal(new[jnp.asarray(others)],
                                  S[jnp.asarray(others)])


# -- (c) the prompt form --------------------------------------------------------


@pytest.fixture(scope="module")
def reference_logits(params):
    tokens = jnp.asarray(_prompt(1, 23), jnp.int32)
    return tokens, ref.Forward(SIZES).logits(params, tokens)


def test_forward_equals_the_plain_reference(params, reference_logits):
    tokens, want = reference_logits
    got = retention.forward(params, tokens[None], CFG)[0]
    assert got.shape == (23, SIZES["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_a_reference_with_a_wrong_fade_is_not_the_program(params,
                                                          reference_logits):
    """The gates matter at these weights: with every gate at 1 (no fade)
    the same reference moves by far more than the tolerance above."""
    tokens, want = reference_logits
    flat = {**params, "layers": {**params["layers"],
                                 "bg": jnp.full((L, KVH), 30.0)}}
    moved = ref.Forward(SIZES).logits(flat, tokens)
    assert float(jnp.abs(moved - want).max()) > 0.05 * float(jnp.std(want))


@pytest.mark.parametrize("block", [1, 3, 4, 5, 16, 23, 64])
def test_the_prompt_form_is_the_same_across_block_boundaries(
        params, reference_logits, block, monkeypatch):
    """Blocks of query rows that do and do not divide the 23 positions,
    one row a block and one block for all."""
    tokens, want = reference_logits
    monkeypatch.setattr(retention, "PROMPT_BLOCK", block)
    got = retention.forward(params, tokens[None], CFG)[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("length,block", [(1, 4), (7, 4), (8, 4), (13, 5),
                                          (19, 64)])
def test_the_prompt_ends_in_the_state_the_steps_build(params, length, block,
                                                      monkeypatch):
    """One layer's mixer over a prompt at once against the same rows one
    at a time from a zero state: outputs, and the S and z it ends in."""
    monkeypatch.setattr(retention, "PROMPT_BLOCK", block)
    cfg = CFG
    lp = jamba._at(params["layers"], 1)
    x = jax.random.normal(jax.random.PRNGKey(length), (2, length, 32))
    out, (s_end, z_end) = retention._retention(x, lp, cfg)
    Ss = jnp.zeros((1, 2, KVH, HD, ROWS))
    zs = jnp.zeros((1, 2, KVH, ROWS))
    rows = []
    for t in range(length):
        y, (Ss, zs) = retention._retention(
            x[:, t:t + 1], lp, cfg, (Ss, zs, 0, jnp.full((2,), t)))
        rows.append(y)
    np.testing.assert_allclose(out, jnp.concatenate(rows, axis=1),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(s_end, Ss[0], rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(z_end, zs[0], rtol=2e-4, atol=1e-5)
    assert float(jnp.abs(z_end).max()) > 0


# -- (d) the engine ---------------------------------------------------------------


class Spy:
    """An engine whose two programs also hand their logits to the test."""

    def __init__(self, params, max_batch):
        self.engine = DecodeEngine(params, CFG, max_batch=max_batch,
                                   cache_len=CACHE_LEN)
        self.first, self.steps = {}, []
        step, install = self.engine._step, self.engine._install

        def spy_step(*args):
            out = step(*args)
            self.steps.append(np.asarray(out[0]))
            return out

        def spy_install(state, tok, pos, slot, logits, *rest):
            self.first[int(slot)] = np.asarray(logits)
            return install(state, tok, pos, slot, logits, *rest)

        self.engine._step, self.engine._install = spy_step, spy_install


def _served(spy, slot, first_step, n_steps):
    rows = [spy.first[slot]] + [s[slot] for s in
                                spy.steps[first_step:first_step + n_steps]]
    return np.stack(rows)


def _alone(params, prompt, served_logits):
    """The REFERENCE over the prompt and the greedy tokens of the served
    logits: the rows that predict each served token and the next."""
    tokens = [int(np.argmax(r)) for r in served_logits]
    seq = jnp.asarray(prompt + tokens[:-1], jnp.int32)
    return np.asarray(ref.Forward(SIZES).logits(params, seq)
                      [len(prompt) - 1:])


def test_prefill_then_24_steps_are_the_references_one_pass(params):
    """The prompt form (the prefill, its state installed) and then the
    step form 24 times against ONE quadratic pass of the reference."""
    spy = Spy(params, 2)
    prompt = _prompt(5, 11)
    spy.engine.prefill(1, prompt)
    for _ in range(24):
        spy.engine.step()
    got = _served(spy, 1, 0, 24)
    np.testing.assert_allclose(got, _alone(params, prompt, got),
                               rtol=3e-4, atol=3e-5)
    assert int(spy.engine.pos[1]) == 11 + 24 and int(spy.engine.pos[0]) == 0


def test_neighbouring_slots_get_what_the_reference_gives_each_alone(params):
    """Three requests of different lengths, admitted at different turns
    into slots 0, 1, 2 of 3; the middle one retires first."""
    spy = Spy(params, 3)
    eng = spy.engine
    a, b, c = _prompt(10, 5), _prompt(11, 9), _prompt(12, 2)
    eng.prefill(1, a)
    for _ in range(3):
        eng.step()                                          # steps 0-2
    eng.prefill(0, b)
    for _ in range(2):
        eng.step()                                          # steps 3-4
    eng.prefill(2, c)
    for _ in range(4):
        eng.step()                                          # steps 5-8
    eng.clear(1)
    for _ in range(3):
        eng.step()                                          # steps 9-11
    for slot, prompt, first_step, n in ((1, a, 0, 9), (0, b, 3, 9),
                                        (2, c, 5, 7)):
        got = _served(spy, slot, first_step, n)
        np.testing.assert_allclose(got, _alone(params, prompt, got),
                                   rtol=3e-4, atol=3e-5, err_msg=str(slot))


def test_a_reused_slot_holds_nothing_of_its_last_tenant(params):
    """Slot 1 serves a long request, retires, idles three steps beside a
    live neighbour (its state is stepped all the while) and is given a
    short prompt: its S and z after the install, and every logit after
    it, are bit for bit those of an engine that never held the first."""
    old, new, beside = _prompt(20, 17), _prompt(21, 3), _prompt(22, 6)

    def serve_new(engine_spy, after_old):
        eng = engine_spy.engine
        eng.prefill(0, beside)
        if after_old:
            eng.prefill(1, old)
            for _ in range(8):
                eng.step()
            eng.clear(1)
        for _ in range(3 if after_old else 11):
            eng.step()                   # the neighbour at the same position
        eng.prefill(1, new)
        mark = len(engine_spy.steps)
        state = jax.tree.map(np.asarray, eng.state["recurrent"])
        for _ in range(10):
            eng.step()
        return state, _served(engine_spy, 1, mark, 10)

    (used_s, used_z), used = serve_new(Spy(params, 2), True)
    (fresh_s, fresh_z), fresh = serve_new(Spy(params, 2), False)
    np.testing.assert_array_equal(used, fresh)
    np.testing.assert_array_equal(used_s[:, 1], fresh_s[:, 1])
    np.testing.assert_array_equal(used_z[:, 1], fresh_z[:, 1])
    assert float(np.abs(used_s[:, 1]).max()) > 0
    np.testing.assert_allclose(used, _alone(params, new, used),
                               rtol=3e-4, atol=3e-5)


def test_a_mesh_is_refused_by_name(params):
    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="recurrent state"):
        DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN,
                     mesh=mesh)


def test_the_engine_reports_what_it_holds_by_kind(params):
    tmx.configure(True)
    try:
        DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN)
        gauges = tmx.snapshot()["gauges"]
    finally:
        tmx.configure(False)
    assert gauges['hvd_serve_state_bytes{kind="kv"}'] == 0
    assert gauges['hvd_serve_state_bytes{kind="recurrent"}'] \
        == L * 2 * KVH * ROWS * (HD + 1) * 4


def test_the_counters_say_what_share_of_the_state_pass_served_a_request(
        params):
    """Live slots and slots held, a layer a step, summed on the device;
    they reach the registry beside an admission's read and only when it
    is on; their ratio is ``state_live_share`` on ``/stats``."""
    B = 4
    sched = Scheduler(max_batch=B, max_queue=4, cache_len=CACHE_LEN)
    engine = DecodeEngine(params, CFG, max_batch=B, cache_len=CACHE_LEN)
    engine.prefill(2, _prompt(40, 5))
    for _ in range(3):
        engine.step()                   # 1 of 4 live
    assert engine._published == {}      # registry off: never read
    tmx.configure(True)
    try:
        engine.prefill(0, _prompt(41, 3))       # publishes
        for _ in range(2):
            engine.step()               # 2 of 4 live
        engine.clear(2)
        engine.step()                   # 1 of 4 live
        engine.publish_counters()
        counters = tmx.snapshot()["counters"]
        live, held = retention.COUNTERS
        assert counters[held] == L * B * 6
        assert counters[live] == L * (3 * 1 + 2 * 2 + 1)
        assert sched.stats()["state_live_share"] == round(8 / 24, 4)
        assert set(retention.COUNTERS) <= set(tmx.known_metrics())
    finally:
        tmx.configure(False)


# -- (e) the compiled programs ---------------------------------------------------

B_PIN = 4
PIN = RetentionConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=3, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, max_seq_len=64,
                      compute_dtype=jnp.float32, param_dtype=jnp.float32)
S_ELEMS = 3 * B_PIN * 2 * 16 * 256       # [L, B, KVH, HD, D]
Z_ELEMS = 3 * B_PIN * 2 * 256


@pytest.mark.parametrize("program", ["step", "install"])
def test_compiled_program_aliases_all_the_state_it_was_given(program):
    """Both state arrays and the two counters are aliased from input to
    output of both programs, and the install produces nothing of the
    state's size besides its in-place writes.  (On the CPU the kernel is
    interpreted and its loop copies; the chip's programs are pinned to
    ONE pass over the state, the kernel's, in tests/test_chip_smoke.py.)"""
    got = serve_cache_programs(PIN, B_PIN, S_ELEMS // 3)[program]
    assert got["alias_bytes"] == 4 * (S_ELEMS + Z_ELEMS) + 2 * 4
    if program == "install":
        assert {op for _, op in got["big_ops"]} <= {
            "fusion:dynamic-update-slice", "dynamic-update-slice"}, got
        assert got["temp_bytes"] < 4 * S_ELEMS // 3, got


def test_prefill_and_step_donate_the_state_they_were_given(params):
    engine = DecodeEngine(params, CFG, max_batch=2, cache_len=CACHE_LEN)
    before = jax.tree.leaves(engine.state)
    engine.prefill(1, [3, 14, 15])
    assert all(a.is_deleted() for a in before)
    before = jax.tree.leaves(engine.state)
    engine.step()
    assert all(a.is_deleted() for a in before)
    assert int(engine.pos[1]) == 4


# -- (f) the whole server --------------------------------------------------------


def _post(port, prompt, max_new):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/generate", json.dumps(
            {"prompt": prompt, "max_new_tokens": max_new}))
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


@pytest.mark.timeout(240)
def test_serving_loop_serves_the_config_over_http(params, monkeypatch):
    """``ServingLoop`` → ``Scheduler`` → ``DecodeEngine`` with nothing but
    the config's type to say which model: three requests at once over
    HTTP into two slots, each answered with the greedy tokens ``forward``
    gives it."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HVD_TPU_CORE", "py")   # ServingLoop.run setdefaults
    hvd.shutdown()
    ready, box = threading.Event(), {}

    def on_ready(port):
        box["port"] = port
        ready.set()

    loop = ServingLoop(params, CFG, port=0, max_batch=2, max_queue=16,
                       cache_len=CACHE_LEN, host="127.0.0.1",
                       on_ready=on_ready)

    def serve():
        try:
            loop.run()
        except BaseException as e:
            box["error"] = e
            ready.set()
            raise

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    requests = [(_prompt(30, 4), 6), (_prompt(31, 7), 9), (_prompt(32, 2), 5)]
    replies = [None] * len(requests)
    try:
        assert ready.wait(120) and "error" not in box, box
        posts = [threading.Thread(
            target=lambda i=i, r=r: replies.__setitem__(
                i, _post(box["port"], *r))) for i, r in enumerate(requests)]
        for t in posts:
            t.start()
        for t in posts:
            t.join(180)
    finally:
        loop.stop()
        thread.join(60)
        hvd.shutdown()
    assert not thread.is_alive() and "error" not in box, box
    for (prompt, max_new), (status, body) in zip(requests, replies):
        assert status == 200, body
        tokens = [int(t) for t in body["tokens"]]
        assert len(tokens) == max_new
        seq = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
        logits = np.asarray(
            retention.forward(params, seq, CFG)[0, len(prompt) - 1:])
        best = np.sort(logits, axis=-1)
        assert float((best[:, -1] - best[:, -2]).min()) > 1e-5
        assert tokens == [int(t) for t in logits.argmax(-1)]
