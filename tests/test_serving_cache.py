"""The serving slot cache is one resident pair of buffers.

``DecodeEngine`` keeps the dense decoder's K and V (``engine.state["kv"]``)
as ``[L, max_batch, cache_len, H, HD]``.  Two programs write them: the decode step (B new rows a layer) and the
install that ends a prefill (one slot's lane).  Both take the caches
donated and update them in place.  What is pinned here, on the CPU:

* the compiled programs: nothing of the cache's size comes out of either
  besides the in-place update of the donated input, both caches are
  aliased from input to output, and the temporaries stay lanes below a
  cache (``tests/test_chip_smoke.py`` pins the stricter form, nothing of a
  *lane's* size, on the programs compiled for the chip);
* the engine: after ``prefill`` and after ``step`` the buffers it held
  before are deleted (donated, not copied);
* a mesh: the caches leave both programs sharded as ``KV_CACHE_SPEC`` says,
  and the tokens are those of the engine without a mesh;
* what the step reads: its logits with the attention kernel
  (ops/pallas_decode_attention.py) are those of the masked read of the
  whole lane it replaced, over 40 steps of uneven slots; the two device
  counters of what was read and held, and ``attn_read_share`` on ``/stats``.

And the parameters an engine holds (``engine.params``): every leaf the
model's forward casts to the compute type at its use is held in that type,
rounded once when the engine is built, so that no program converts a
weight on every call; the dense decoder's ``wq``, ``wk`` and ``wv`` are held
as one leaf, ``wqkv``, so that its step and its prefill make one product of
them and read it in place from the stack (``tests/test_chip_smoke.py`` pins
that on the programs compiled for the chip).  Pinned here:

* the mathematics: ``decode_step`` and ``prefill_request`` return from the
  held pytree, to the bit, what they return from the float32 one and from
  the three leaves in the compute type, on a model whose norm gains bfloat16
  cannot hold (a cast of every leaf moves the logits);
* the built engine, dense and ``models/jamba.py``'s: no float32 leaf where
  the forward casts, nothing in the step as the engine hands it to the
  compiler that converts to a weight's shape (``tests/test_chip_smoke.py``
  pins the same on the step compiled for the chip), a leaf already in its
  type held as the given buffer, ``hvd_serve_param_bytes{dtype}``;
* a mesh: the held leaves keep the sharding of the given ones, and under
  ``tp`` the three projections stay three;
* training and ``generate`` on ``init``'s parameters: three products, the
  programs they were.

And the names of the three programs an engine compiles (``serve_step``,
``serve_prefill_s<N>``, ``serve_install``: what a profile's ``XLA Modules``
line shows), with the programs otherwise what the unnamed functions lower
to.

And the seam itself, over one small config of each of the seven served
forms (``FORMS``): the lowered text of six forms' step and prefill as
sha256 digests, four of them taken before the install, the counter merge and the shared
layer code moved to ``models/layers.py``; the ONE install's contract (the
slot whole, every other slot and the counters untouched, nothing left of
the last tenant); the device counters' names against the registry and its
declared ``/stats`` shares; that no model file imports another and the
server names no counter; what ``slot_model`` defaults for a module that
omits ``STATE_SPEC`` and ``serving_params``; and ``stats()`` printing each
declared share.
"""

import dataclasses
import hashlib
import inspect
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_probes import (DENSE_CAST_LEAVES, JAMBA_CAST_LEAVES, converts_to,
                         dims_key, serve_cache_programs, weight_dims)
from horovod_tpu.models import (conv_moe, jamba, latent_moe, layers,
                                retention, ssd_moe)
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import pallas_decode_attention as pda
from horovod_tpu.parallel.mesh import make_mesh, sharding_for
from horovod_tpu.serving import decode
from horovod_tpu.serving.decode import DecodeEngine
from horovod_tpu.serving.scheduler import Scheduler
from horovod_tpu.telemetry import registry as tmx
from test_pallas_decode_attention import masked_read, uneven_steps

L, B, S, H, HD, V = 6, 4, 256, 2, 16, 64
LANE_ELEMS = B * S * H * HD


@pytest.fixture(scope="module")
def model():
    cfg = tfm.TransformerConfig(
        vocab_size=V, d_model=H * HD, n_layers=L, n_heads=H, d_ff=64,
        max_seq_len=S, compute_dtype=jnp.float32, remat=False)
    return cfg, tfm.init(jax.random.PRNGKey(0), cfg)


# On the CPU the interpreter stands in for the step's attention kernel
# (ops/pallas_decode_attention.py) and copies what it reads, so what is
# pinned of the step here is the row scatter a cache and the aliasing; that
# nothing of a lane's size comes out of it is pinned on the program compiled
# for the chip (tests/test_chip_smoke.py), where Mosaic compiles the kernel.
@pytest.mark.parametrize("program,update,temp_lanes", [
    ("step", "fusion:scatter", None),
    ("install", "fusion:dynamic-update-slice", 1)])
def test_compiled_program_updates_the_donated_caches_in_place(
        model, program, update, temp_lanes):
    cfg, _ = model
    got = serve_cache_programs(cfg, B, L * LANE_ELEMS)[program]
    lane_bytes = 4 * LANE_ELEMS
    ops = [op for _, op in got["big_ops"]]
    assert ops.count(update) == 2, got
    # both caches, and the two uint32 counters beside them
    assert got["alias_bytes"] == 2 * L * lane_bytes + 2 * 4
    if temp_lanes is not None:
        assert ops == [update, update], got
        assert got["temp_bytes"] < temp_lanes * lane_bytes


def test_prefill_and_step_donate_the_caches_they_were_given(model):
    cfg, params = model
    engine = DecodeEngine(params, cfg, max_batch=B, cache_len=S)
    before = engine.state["kv"]
    engine.prefill(1, [3, 14, 15])
    assert all(a.is_deleted() for a in before)
    before = engine.state["kv"]
    engine.step()
    assert all(a.is_deleted() for a in before)
    ks, vs = engine.state["kv"]
    assert ks.shape == vs.shape == (L, B, S, H, HD)
    assert int(engine.pos[1]) == 4


def test_mesh_keeps_the_cache_sharding_through_both_programs(model):
    """Heads over ``tp``: the caches come out of the install and of the
    step sharded as they went in, and a sharded cache changes no token."""
    cfg, params = model
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    want = sharding_for(mesh, tfm.KV_CACHE_SPEC)
    plain = DecodeEngine(params, cfg, max_batch=B, cache_len=S)
    sharded = DecodeEngine(params, cfg, max_batch=B, cache_len=S, mesh=mesh)
    assert sharded.state["kv"][0].sharding.is_equivalent_to(want, 5)

    def tokens(engine):
        first = engine.prefill(2, [5, 14, 15, 9])
        return [first] + [int(engine.step()[2]) for _ in range(3)]

    assert tokens(sharded) == tokens(plain)
    for cache in sharded.state["kv"]:
        assert cache.sharding.is_equivalent_to(want, 5)
        assert len(cache.sharding.device_set) == 2


def test_step_with_the_kernel_equals_the_masked_read_of_the_whole_lane(
        model, monkeypatch):
    """Blocks of 32 positions in a lane of 256: over the 40 steps the
    three live slots cross block ends at 32, 64, 96 and 224, beside a
    free slot."""
    cfg, params = model
    monkeypatch.setattr(pda, "BLOCK", 32)
    lengths = [3, 0, 61, 200]

    def logits():
        return uneven_steps(
            jax.jit(lambda p: tfm.prefill_request(params, p, cfg, S)),
            decode.slot_model(cfg, S).install,
            jax.jit(lambda tok, pos, state: tfm.decode_step(
                params, tok, pos, state, cfg)),
            tfm.init_state(cfg, B, S), lengths, V)[0]

    got = logits()
    monkeypatch.setattr(pda, "decode_attention", masked_read)
    want = logits()
    live = [b for b, n in enumerate(lengths) if n]
    np.testing.assert_allclose(got[:, live], want[:, live], rtol=2e-4,
                               atol=2e-5)
    assert np.isfinite(got).all()


def test_mesh_without_tp_runs_the_kernel_and_with_tp_the_masked_read(model):
    """The code observes the mesh, there is no knob: under ``dp`` alone the
    kernel runs (inside a shard_map, everything replicated), under ``tp``
    the masked read of the whole lane; the tokens are the same, and only
    the counters tell which ran."""
    cfg, params = model

    def served(mesh):
        engine = DecodeEngine(params, cfg, max_batch=B, cache_len=S,
                              mesh=mesh)
        tokens = [engine.prefill(2, [5, 14, 15, 9])]
        tokens += [int(engine.step()[2]) for _ in range(3)]
        return tokens, engine.counters()

    plain, counted = served(None)
    read, held = (counted[name] for name in tfm.ATTN_COUNTERS)
    assert held == 3 * L * B * S
    assert read == 3 * L * pda.block_for(S, shared=False)
    for axes, reads in [({"dp": 2}, read), ({"tp": 2}, held)]:
        tokens, counted = served(
            make_mesh(axes, devices=jax.devices()[:2]))
        assert tokens == plain, axes
        assert counted[tfm.ATTN_COUNTERS[0]] == reads, axes


def test_attn_read_share_is_what_the_steps_read_of_what_the_lanes_hold(
        model):
    """1.0 for a full table at the lanes' end; for one live slot the
    blocks up to its position over the table's; the counters reach the
    registry beside an admission's read and only when it is on."""
    cfg, params = model
    block = pda.block_for(S, shared=False)
    sched = Scheduler(max_batch=B, max_queue=4, cache_len=S)
    engine = DecodeEngine(params, cfg, max_batch=B, cache_len=S)
    engine.pos = jnp.full((B,), S - 1, jnp.int32)
    engine.step()
    assert engine._published == {}              # registry off: never read
    tmx.configure(True)
    try:
        engine.publish_counters()
        assert sched.stats()["attn_read_share"] == 1.0
        counters = tmx.snapshot()["counters"]
        assert counters["hvd_serve_attn_positions_held_total"] == L * B * S
        assert counters["hvd_serve_attn_positions_read_total"] == L * B * S
    finally:
        tmx.configure(False)
    fresh = DecodeEngine(params, cfg, max_batch=B, cache_len=S)
    tmx.configure(True)
    try:
        fresh.prefill(2, list(range(1, 1 + 70)))    # position 70: 1 block
        for _ in range(3):
            fresh.step()
        fresh.publish_counters()
        assert sched.stats()["attn_read_share"] == pytest.approx(
            (70 // block + 1) * block / (B * S))
        assert set(tfm.ATTN_COUNTERS) <= set(tmx.known_metrics())
    finally:
        tmx.configure(False)


# -- the parameters an engine holds ---------------------------------------------

BF16 = jnp.dtype(jnp.bfloat16)
F32 = jnp.dtype(jnp.float32)


def _dense_bf16():
    """Float32 weights of a bfloat16 model, as a trainer hands them over,
    with seeded norm gains that bfloat16 cannot hold."""
    cfg = tfm.TransformerConfig(
        vocab_size=V, d_model=H * HD, n_layers=L, n_heads=H, d_ff=64,
        max_seq_len=S, remat=False)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 3))

    def gain(g):
        return 1.0 + 0.1 * jax.random.normal(next(keys), g.shape, g.dtype)

    params["ln_f"] = gain(params["ln_f"])
    for name in ("ln1", "ln2"):
        params["layers"][name] = gain(params["layers"][name])
    assert cfg.compute_dtype == jnp.bfloat16
    return cfg, params, DENSE_CAST_LEAVES


def _jamba_bf16():
    """Weights in the published bfloat16, as the benchmark's job hands
    them over."""
    cfg = jamba.JambaConfig(
        vocab_size=V, hidden_size=32, intermediate_size=48,
        num_hidden_layers=4, num_attention_heads=2, num_key_value_heads=1,
        attn_layer_period=4, attn_layer_offset=1, mamba_d_state=4,
        mamba_expand=2, mamba_dt_rank=6, max_seq_len=S)
    assert cfg.compute_dtype == cfg.param_dtype == jnp.bfloat16
    return cfg, jamba.init(jax.random.PRNGKey(0), cfg), JAMBA_CAST_LEAVES


@pytest.fixture(scope="module", params=[_dense_bf16, _jamba_bf16],
                ids=["dense", "jamba"])
def built(request):
    """(cfg, the given parameters, the names of the leaves the forward
    casts, the engine built from them, the gauges it set)."""
    cfg, given, cast = request.param()
    tmx.configure(True)
    try:
        engine = DecodeEngine(given, cfg, max_batch=B, cache_len=S)
        gauges = tmx.snapshot()["gauges"]
    finally:
        tmx.configure(False)
    return cfg, given, cast, engine, gauges


def _named(params):
    return [(path[-1].key, leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(params)]


QKV = ("wq", "wk", "wv")


def _three_leaves(params, cfg, cast):
    """``params`` with every leaf in ``cast`` in the compute type and
    nothing joined: what the dense decoder's engine held before ``wqkv``,
    and holds under ``tp``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (a.astype(cfg.compute_dtype)
                         if path[-1].key in cast else a), params)


def _joined(layers):
    """q's columns, then k's, then v's: [L, D, 3 H HD]."""
    return jnp.concatenate(
        [layers[name].reshape(*layers[name].shape[:2], -1) for name in QKV],
        axis=-1)


def test_engine_holds_in_the_compute_type_what_the_forward_casts(built):
    cfg, given, cast, engine, _ = built
    want = dict(_named(_three_leaves(given, cfg, cast)))
    if isinstance(cfg, tfm.TransformerConfig):
        want["wqkv"] = _joined(want)
        for name in QKV:
            del want[name]
        assert want["wqkv"].shape == (L, H * HD, 3 * H * HD)
    held = dict(_named(engine.params))
    assert sorted(held) == sorted(want)
    was = dict(_named(given))
    for name, leaf in held.items():
        if name in cast:
            assert leaf.dtype == cfg.compute_dtype, name
            np.testing.assert_array_equal(leaf, want[name], err_msg=name)
        else:
            assert leaf is was[name], name
    assert set(was) | {"wqkv"} >= cast


def test_step_the_engine_compiles_converts_no_weight(built):
    """Nothing in the step, as the engine hands it to the compiler,
    produces an array of the compute type with a weight's dimensions (the
    whole stacked leaf's, one layer's slice's, or a transpose of either)
    by a convert; the float32 pytree's step has one for every use."""
    cfg, given, cast, engine, _ = built
    weights = weight_dims(given, cast) | weight_dims(engine.params, cast)

    def weight_converts(params):
        text = engine._step.lower(
            params, engine.tok, engine.pos, engine.state
        ).compiler_ir(dialect="hlo").as_hlo_text()
        return [dims for dims in converts_to(text, BF16.name)
                if dims_key(dims) in weights]

    assert weight_converts(engine.params) == []
    for form in (given, engine.params):     # three projections, and one
        as_float32 = jax.tree.map(lambda a: a.astype(F32), form)
        assert len(weight_converts(as_float32)) >= len(
            [name for name, _ in _named(form) if name in cast])


@pytest.mark.parametrize("program", ["step", "prefill", "install"])
def test_engine_names_the_programs_it_compiles(built, program):
    """The three programs carry the engine's own names, the same for
    every model behind the seam (a profile's ``XLA Modules`` line and the
    benchmark's ``readers/module_ms.py`` read them), and are otherwise, to
    the letter, what the model's unnamed functions lower to."""
    cfg, _, _, engine, _ = built
    prompt = jnp.arange(1, 9, dtype=jnp.int32)
    if program == "step":
        name, named = decode.STEP_PROGRAM, engine._step
        bare = jax.jit(engine.model.step, donate_argnums=(3,))
        args = (engine.params, engine.tok, engine.pos, engine.state)
    elif program == "prefill":
        scratch = DecodeEngine(engine.params, cfg, max_batch=1, cache_len=S)
        scratch.prefill(0, list(range(1, 9)))
        name = f"{decode.PREFILL_PROGRAM}_s8"
        (named,) = scratch._prefills.values()
        bare = jax.jit(engine.model.prefill)
        args = (engine.params, prompt)
    else:
        name, named = decode.INSTALL_PROGRAM, engine._install
        bare = jax.jit(partial(decode.install, engine.model),
                       donate_argnums=(0,))
        logits, request = jax.eval_shape(
            engine.model.prefill, engine.params, prompt)
        args = (engine.state, engine.tok, engine.pos, np.int32(1), logits,
                request, np.int32(8))
    text = named.lower(*args).as_text()
    assert text.startswith(f"module @jit_{name} "), text[:80]
    unnamed = bare.lower(*args).as_text()
    assert unnamed.startswith("module @jit__unknown ")   # why it is named
    assert text.replace(f"@jit_{name} ", "@jit__unknown ", 1) == unnamed


def test_leaf_already_in_its_type_is_held_as_the_given_buffer(built):
    """The held form given again is held as it is, ``wqkv`` too; of three
    projections in the compute type only the joined leaf is new."""
    cfg, given, cast, engine, _ = built
    again = DecodeEngine(engine.params, cfg, max_batch=1, cache_len=S)
    for held, was in zip(jax.tree.leaves(again.params),
                         jax.tree.leaves(engine.params)):
        assert held is was
    three = _three_leaves(given, cfg, cast)
    was = dict(_named(three))
    fresh = dict(_named(
        DecodeEngine(three, cfg, max_batch=1, cache_len=S).params))
    assert {name for name in fresh if fresh[name] is not was.get(name)} == (
        {"wqkv"} if isinstance(cfg, tfm.TransformerConfig) else set())


def test_param_bytes_gauge_reads_what_the_engine_holds_by_dtype(built):
    cfg, given, cast, _, gauges = built
    want = {}
    for name, leaf in _named(given):
        dtype = BF16 if name in cast else leaf.dtype
        want[dtype.name] = want.get(dtype.name, 0) + leaf.size * dtype.itemsize
    got = {series: v for series, v in gauges.items()
           if series.startswith("hvd_serve_param_bytes")}
    assert got == {'hvd_serve_param_bytes{dtype="%s"}' % name: float(n)
                   for name, n in want.items()}


@pytest.mark.parametrize("program", ["decode_step", "prefill_request"])
def test_held_parameters_give_the_float32_results_to_the_bit(program):
    cfg, given, cast = _dense_bf16()
    held = tfm.serving_params(given, cfg)
    assert "wqkv" in held["layers"] and not set(QKV) & set(held["layers"])
    three = _three_leaves(given, cfg, cast)
    gains = [given["ln_f"], given["layers"]["ln1"], given["layers"]["ln2"]]
    assert all((g.astype(BF16).astype(F32) != g).any() for g in gains)
    every_leaf = jax.tree.map(lambda a: a.astype(BF16), given)
    if program == "decode_step":
        def run(params):
            tok, pos = jnp.asarray([5, 9, 0, 33]), jnp.asarray([0, 3, 0, 7])
            out = None, tfm.init_state(cfg, B, S)
            for i in range(3):   # the later steps read what the first wrote
                out = tfm.decode_step(params, tok + i, pos + i, out[1], cfg)
            return out
    else:
        def run(params):
            prompt = jnp.asarray([3, 14, 15, 9, 26, 5], jnp.int32)
            return tfm.prefill_request(params, prompt, cfg, S)
    want, got, apart, blanket = (
        jax.jit(run)(p) for p in (given, held, three, every_leaf))
    for other in (want, apart):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(other)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
    assert not np.array_equal(blanket[0], want[0])


def test_mesh_keeps_the_sharding_of_the_parameters_it_casts():
    cfg, given, cast = _dense_bf16()
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    shardings = jax.tree.map(
        lambda spec: sharding_for(mesh, spec), tfm.param_specs(cfg),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    sharded = jax.device_put(given, shardings)
    engine = DecodeEngine(sharded, cfg, max_batch=B, cache_len=S, mesh=mesh)
    for (name, held), (_, was) in zip(_named(engine.params),
                                      _named(sharded)):
        assert held.sharding.is_equivalent_to(was.sharding, was.ndim), name
        assert held.dtype == (BF16 if name in cast else F32), name
    # heads over tp: joined columns would not split by heads, so the three
    # projections are held as given
    assert "wqkv" not in engine.params["layers"]
    for name in QKV:
        held = engine.params["layers"][name]
        assert len(held.sharding.device_set) == 2, name
        assert held.sharding.shard_shape(held.shape) == (L, H * HD, H // 2, HD)
    plain = DecodeEngine(given, cfg, max_batch=B, cache_len=S)
    assert "wqkv" in plain.params["layers"]
    assert engine.prefill(2, [5, 14, 15, 9]) == plain.prefill(2, [5, 14, 15, 9])
    np.testing.assert_array_equal(engine.step()[2], plain.step()[2])


@pytest.mark.parametrize("program", ["apply", "loss_fn", "generate"])
def test_init_parameters_lower_to_three_products_and_no_joined_one(program):
    """Training and ``generate`` take ``init``'s parameters, and
    ``_attention`` reads off the keys which products to make: their
    programs hold the three ``[D, H, HD]`` products and nothing of the
    joined width, which only the held form's program has."""
    cfg, given, _ = _dense_bf16()
    tokens = jnp.zeros((2, 8), jnp.int32)
    run = {"apply": lambda p: tfm.apply(p, tokens, cfg),
           "loss_fn": lambda p: tfm.loss_fn(p, tokens, tokens, cfg),
           "generate": lambda p: tfm.generate(
               p, tokens, cfg, max_new_tokens=4)}[program]
    joined_width = f"x{3 * H * HD}x"                    # tensor<...x96xbf16>
    projection = f"tensor<{H * HD}x{H}x{HD}xbf16>"      # one layer's wq
    text = jax.jit(run).lower(given).as_text()
    assert joined_width not in text
    assert text.count(projection) >= 3
    held = tfm.serving_params(given, cfg)
    joined = jax.jit(run).lower(held).as_text()
    assert joined_width in joined and projection not in joined
    for a, b in zip(jax.tree.leaves(jax.jit(run)(held)),
                    jax.tree.leaves(jax.jit(run)(given))):
        np.testing.assert_array_equal(a, b)


# -- the seam: what a model module presents, and the one builder ----------------

SEAM = {"init_state": ["cfg", "max_batch", "cache_len"],
        "prefill_request": ["params", "prompt", "cfg", "cache_len"],
        "decode_step": ["params", "tok", "pos", "state", "cfg"]}
MAY_OMIT = {"serving_params": ["params", "cfg"]}


@pytest.mark.parametrize("make", [_dense_bf16, _jamba_bf16],
                         ids=["dense", "jamba"])
def test_model_module_presents_the_seam_the_one_builder_takes(make):
    cfg, given, _ = make()
    module = decode.MODELS[type(cfg)]
    for name, takes in {**SEAM, **MAY_OMIT}.items():
        if name in MAY_OMIT and not hasattr(module, name):
            continue
        has = inspect.signature(getattr(module, name)).parameters
        assert list(has)[:len(takes)] == takes, name
        # what a module takes besides (the dense decoder's step: the
        # mesh its state is sharded over) the builder may leave out
        assert all(p.default is not p.empty
                   for p in list(has.values())[len(takes):]), name
    model = decode.slot_model(cfg, S)
    for part, name in [(model.init_state, "init_state"),
                       (model.prefill, "prefill_request"),
                       (model.step, "decode_step")]:
        assert part.func is getattr(module, name), name
    # the install is the shared one, told where the module's slots lie
    assert model.install.func is layers.install_request
    assert model.install.keywords == {"axes": module.SLOT_AXES}
    assert model.spec is getattr(module, "STATE_SPEC", None)
    if hasattr(module, "serving_params"):
        assert model.held.func is module.serving_params
    else:
        assert model.held(given) is given
    # and the parts fit: a request's state installs into a batch's, which
    # a step takes and gives back in the shapes it came in
    params = model.held(given)
    state = model.init_state(2)
    logits, request = model.prefill(params, jnp.asarray([3, 14, 15]))
    assert logits.shape == (V,)
    # a request's state is a slot's share of the batch's: every kind
    # but the counters, which no slot owns
    slots = {k: v for k, v in state.items() if k != "counters"}
    assert jax.tree.structure(request) == jax.tree.structure(slots)
    assert (jax.tree.structure(state)
            == jax.tree.structure(model.init_state(1)))
    state = model.install(state, 1, request)
    logits, after = model.step(params, jnp.asarray([0, 9]),
                               jnp.asarray([0, 3]), state)
    assert logits.shape == (2, V)
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), after)
            == jax.tree.map(lambda a: (a.shape, a.dtype), state))


# -- the seam over the seven served forms --------------------------------------
#
# One small config a served form, the fixtures' widths of tests/test_jamba.py,
# test_latent_moe.py, test_sparse_latent_moe.py and test_retention.py in the
# published types: each takes every branch of its form (attention and Mamba
# runs; dense and expert layers; the indexer, a share of the experts, grouped
# routing and YaRN; the retention's prompt blocks; the three layer kinds of
# tests/test_ssd_moe.py's pattern with a share of its experts; both operator
# kinds over a dense layer then expert layers of tests/test_conv_moe.py, the
# experts' stacks held wider than published).

YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 16, "mscale": 1.0,
        "mscale_all_dim": 1.0}
LATENT = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
              moe_intermediate_size=16, num_hidden_layers=3,
              first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=12,
              kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4,
              v_head_dim=8, n_shared_experts=1, max_seq_len=64, attn_block=8)
FORMS = {
    "olmo-1b": tfm.TransformerConfig(
        vocab_size=96, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=64),
    "jamba2-3b": jamba.JambaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=1,
        attn_layer_period=4, attn_layer_offset=1, mamba_d_state=4,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=6, max_seq_len=64,
        scan_chunk=4),
    "glm-4.7-flash": latent_moe.LatentMoEConfig(
        n_routed_experts=8, num_experts_per_tok=2, routed_scaling_factor=1.8,
        rms_norm_eps=1e-5, rope_theta=1e6, **LATENT),
    "deepseek-v3.2": latent_moe.LatentMoEConfig(
        n_routed_experts=16, num_experts_per_tok=3, routed_scaling_factor=2.5,
        rms_norm_eps=1e-6, rope_theta=10000.0, n_group=4, topk_group=2,
        index_n_heads=4, index_head_dim=8, index_topk=8, rope_scaling=YARN,
        experts_held=4, expert_first=4, **LATENT),
    "brumby-14b": retention.RetentionConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=10000.0, max_seq_len=64),
    "nemotron-3-nano-30b-a3b": ssd_moe.SsdMoEConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=10,
        hybrid_override_pattern="EMEM*EMEM*", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
        mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
        n_routed_experts=8, num_experts_per_tok=3, max_seq_len=64,
        experts_held=4, expert_first=4),
    "lfm2-8b-a1b": conv_moe.ConvMoEConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=24, num_hidden_layers=9,
        layer_types=("conv",) + ("conv", "conv", "full_attention", "conv") * 2,
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        num_experts=8, num_experts_per_tok=3, max_seq_len=64)}
SLOTS, CACHE_LEN, PROMPT = 4, 64, 24

# sha256 of the lowered text (StableHLO, no locations; jax 0.9.0) of the
# engine's step and prefill (PROMPT tokens) for six of FORMS through
# ``decode.slot_model``, as tests/test_pallas_attention.py pins the dense
# decoder's four, taken on the commit before the install, the counter merge,
# ``_logits`` and the shared layer code moved to models/layers.py
# (``retention.PROMPT_BLOCK`` 8).  A change that alters what a served form
# computes, or the order it computes it in, lands here: change a digest only
# with that form's cells' numbers in hand.
LOWERED_BEFORE = {
    # the three steps through ``layers._grouped_attention`` taken on PR 52's
    # finished change, with their cells' numbers (PERF.md section 6): each
    # attention layer reads its lanes through ``layers.lane_reader`` (the
    # kernel ``decode_attn``, the list of blocks made once a step), and
    # ``jamba2-3b``'s and ``nemotron-3-nano-30b-a3b``'s states hold the two
    # ``hvd_serve_attn_positions_*`` counters; the six prefills are as they
    # were
    "jamba2-3b:serve_step":
        "f6b5e05bfdc4c223d7bee67ba8146fd86c9c445255daf6b27fb3b5b3614fe00d",
    "jamba2-3b:serve_prefill":
        "9864fa04f07c36dbfae741723850d462e76e301c1abd5422a8d0617a7aaba7ca",
    "glm-4.7-flash:serve_step":
        "baa03dc05cd0a0fb075080aacf98b0a005a34c46e1410cf0264f7639371529d0",
    "glm-4.7-flash:serve_prefill":
        "96cdada0cc3f5fda188834de5142835135349f0cd6079206f6c2814a77cc683c",
    "deepseek-v3.2:serve_step":
        "b849e0d8f5a5ea6cd17bd6fa24b037cf596c663a6d295bdbc3913911595b21f4",
    "deepseek-v3.2:serve_prefill":
        "6da620e95f68d495fa8f3a76352267d8bb825fc361dd5d29367a260ce5b2b957",
    "brumby-14b:serve_step":
        "045e7d287c5889bb2ef8b6e04e8d45e04fbb2a6d108a3e51d6f8bd7a90c917c5",
    "brumby-14b:serve_prefill":
        "2fdbfa717b5e079a1127aa9028da2babdb410e8a33da44054df4847d285524e1",
    # taken on PR 47's finished change, with its cell's numbers (PERF.md):
    # the state step is the kernel ``ssd_step`` and the state lies ``[Lm,
    # B, G, N, W]``, so a prefill ends in a transposition of its end state
    "nemotron-3-nano-30b-a3b:serve_step":
        "88f6d0458f8af09aae0cfb1b3e9dafcacd2c2db5d45ef45df18250b148076df8",
    "nemotron-3-nano-30b-a3b:serve_prefill":
        "ebe7c2a04f1496c883279d7699db55dffcf38d5f82a32bdf60e42989a289f5c2",
    # taken on PR 50's finished change, with its cell's numbers (PERF.md):
    # the step holds ``hvd_moe_fused_layer_turns_total`` (its 12 pairs over
    # 8 experts keep the grouped products), the 24-token prompt's 72 pairs
    # take the one kernel ``routed_ffn_rows``
    "lfm2-8b-a1b:serve_step":
        "5364d7cae9b54df32b6f3f4f7362f549c43cf58d700e74d2109f738a79297363",
    "lfm2-8b-a1b:serve_prefill":
        "21241a0a51988e5be6fca07097ed110c6015e702addd4789e68a97de5f2d5f76"}


@pytest.mark.parametrize("which", list(LOWERED_BEFORE))
def test_served_forms_lower_as_before_the_shared_seam(which, monkeypatch):
    monkeypatch.setattr(retention, "PROMPT_BLOCK", 8)
    form, program = which.split(":")
    cfg = FORMS[form]

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def specs(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    model = decode.slot_model(cfg, CACHE_LEN)
    held = specs(jax.eval_shape(
        lambda k: model.held(decode.MODELS[type(cfg)].init(k, cfg)),
        jax.random.PRNGKey(0)))
    if program == decode.STEP_PROGRAM:
        lowered = jax.jit(decode.named(decode.STEP_PROGRAM, model.step),
                          donate_argnums=(3,)).lower(
            held, spec((SLOTS,)), spec((SLOTS,)),
            specs(jax.eval_shape(lambda: model.init_state(SLOTS))))
    else:
        lowered = jax.jit(decode.named(decode.PREFILL_PROGRAM,
                                       model.prefill)).lower(
            held, spec((PROMPT,)))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() \
        == LOWERED_BEFORE[which]


def _slot_leaves(model_axes, state):
    """[(kind, leaf on the host, the axis its slots lie along)] of a
    state."""
    return [(kind, np.asarray(leaf), axis)
            for kind, axes in model_axes.items() if kind in state
            for leaf, axis in zip(jax.tree.leaves(state[kind]),
                                  jax.tree.leaves(axes))]


@pytest.mark.parametrize("form", list(FORMS))
def test_the_one_install_writes_a_slot_whole_and_nothing_else(form):
    """After the shared install every slot-kind leaf of the slot is the
    request's, every other slot's bytes are as they were, the counters are
    moved on by what the request's prefill counted (nothing, for most
    forms) and by nothing else, and an install of zeros leaves nothing of
    the last tenant."""
    cfg = FORMS[form]
    module = decode.MODELS[type(cfg)]
    model = decode.slot_model(cfg, CACHE_LEN)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def seeded(tree):
        return jax.tree.map(lambda a: jax.random.normal(
            next(keys), a.shape).astype(a.dtype), tree)

    zeros = model.init_state(SLOTS)
    # every kind the state holds but the counters lies along a declared axis
    assert set(zeros) - {"counters"} <= set(module.SLOT_AXES)
    before = seeded({k: v for k, v in zeros.items() if k != "counters"})
    if "counters" in zeros:
        before["counters"] = {name: jnp.uint32(7 + i) for i, name
                              in enumerate(zeros["counters"])}
    # what the form's prefill hands over, in shape; the values seeded
    request = seeded(jax.eval_shape(
        lambda k: model.prefill(model.held(module.init(k, cfg)),
                                jnp.arange(1, 10))[1], jax.random.PRNGKey(0)))
    counted = {name: jnp.uint32(3 + i)
               for i, name in enumerate(request.get("counted", {}))}
    if counted:
        request["counted"] = counted
    install = jax.jit(model.install)        # the slot traced, as the engine's
    after = install(before, 2, request)
    assert jax.tree.structure(after) == jax.tree.structure(before)
    want = jax.device_get(before.get("counters"))
    for name, n in counted.items():
        want[name] = want[name] + np.uint32(n)
    np.testing.assert_equal(jax.device_get(after.get("counters")), want)
    wrote = 0
    for (kind, got, axis), (_, was, _), (_, new, _) in zip(
            _slot_leaves(module.SLOT_AXES, after),
            _slot_leaves(module.SLOT_AXES, before),
            _slot_leaves(module.SLOT_AXES, request)):
        assert new.shape[axis] == 1 and got.shape[axis] == SLOTS, kind
        np.testing.assert_array_equal(np.take(got, [2], axis), new, kind)
        np.testing.assert_array_equal(np.delete(got, 2, axis),
                                      np.delete(was, 2, axis), kind)
        wrote += 1
    assert wrote == len(jax.tree.leaves(request)) - len(counted) > 0
    emptied = install(after, 2, jax.tree.map(jnp.zeros_like, request))
    for kind, got, axis in _slot_leaves(module.SLOT_AXES, emptied):
        assert not np.take(got, 2, axis).any(), kind


def _device_counters(form):
    state = jax.eval_shape(
        lambda: decode.slot_model(FORMS[form], CACHE_LEN).init_state(SLOTS))
    return set(state.get("counters", {}))


@pytest.mark.parametrize("form", list(FORMS))
def test_a_form_counts_under_registered_names_and_shares_are_whole(form):
    """Every name a form's state counts under is a counter of the registry,
    a form that holds one counter of a declared share holds the other, and
    no share is declared of counters that no form holds."""
    known = tmx.known_metrics()
    names = _device_counters(form)
    assert all(known[name]["kind"] == "counter" for name in names)
    every = set().union(*(_device_counters(f) for f in FORMS))
    for key, (part, whole) in tmx.stats_shares().items():
        assert (part in names) == (whole in names), key
        assert {part, whole} <= every, key


def test_model_files_import_no_other_and_the_server_names_no_counter():
    """The arrows point one way: ops/ <- layers, experts <- the model files
    <- serving/decode.py; and what a model counts is named by the model and
    the registry, never by the scheduler or the engine."""
    import ast
    from pathlib import Path

    pkg = Path(decode.__file__).resolve().parent.parent
    models = ("transformer", "jamba", "latent_moe", "retention", "ssd_moe",
              "conv_moe", "resnet")
    for name in models + ("layers", "experts"):
        tree = ast.parse((pkg / "models" / f"{name}.py").read_text())
        imported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                imported.add(node.module)
                imported |= {f"{node.module}.{a.name}" for a in node.names}
            elif isinstance(node, ast.Import):
                imported |= {a.name for a in node.names}
        others = {f"horovod_tpu.models.{m}" for m in models if m != name}
        assert not imported & others, (name, imported & others)
    declared = set().union(*(_device_counters(f) for f in FORMS))
    declared |= {c for pair in tmx.stats_shares().values() for c in pair}
    assert declared >= set(layers.ATTN_COUNTERS)
    for path in ("serving/scheduler.py", "serving/decode.py"):
        text = (pkg / path).read_text()
        assert [name for name in sorted(declared) if name in text] == []


@dataclasses.dataclass(frozen=True)
class BareConfig:
    max_seq_len: int = 8


# A module that presents what it must and nothing it may omit: its slots lie
# along axis 0, its state is one row a slot, and its step adds the token.
BARE = types.SimpleNamespace(
    SLOT_AXES={"recurrent": (0,)},
    init_state=lambda cfg, max_batch, cache_len: {
        "recurrent": (jnp.zeros((max_batch, 3), jnp.float32),)},
    prefill_request=lambda params, prompt, cfg, cache_len: (
        params["w"] * jnp.sum(prompt),
        {"recurrent": (jnp.sum(prompt)[None, None] * params["w"][None, :3],)}),
    decode_step=lambda params, tok, pos, state, cfg: (
        state["recurrent"][0] @ params["w"][:3, None] * params["w"][None],
        {"recurrent": (state["recurrent"][0] + tok[:, None],)}))


@pytest.mark.parametrize("mesh", [False, True],
                         ids=["held_as_given", "refuses_a_mesh"])
def test_a_module_that_omits_what_it_may_is_held_as_given_and_unsharded(
        monkeypatch, mesh):
    monkeypatch.setitem(decode.MODELS, BareConfig, BARE)
    params = {"w": jnp.arange(1.0, 6.0)}
    if mesh:
        with pytest.raises(NotImplementedError,
                           match="serving a BareConfig under a mesh: its "
                                 "state has no sharding spec"):
            DecodeEngine(params, BareConfig(), max_batch=2, mesh=make_mesh(
                {"dp": 2}, devices=jax.devices()[:2]))
        return
    engine = DecodeEngine(params, BareConfig(), max_batch=2)
    assert engine.params is params and engine.model.spec is None
    assert engine.prefill(1, [2, 3]) == 4       # argmax of w * 5
    np.testing.assert_array_equal(engine.state["recurrent"][0],
                                  [[0, 0, 0], [5, 10, 15]])
    engine.step()
    np.testing.assert_array_equal(engine.state["recurrent"][0],
                                  [[0, 0, 0], [9, 14, 19]])
    assert engine.counters() == {}


def test_stats_prints_each_declared_share_and_omits_it_at_a_zero_whole():
    shares = tmx.stats_shares()
    assert set(shares) >= {"attn_read_share", "attn_selected_share",
                           "state_live_share"}
    sched = Scheduler(max_batch=B, max_queue=4, cache_len=S)
    tmx.configure(True)
    try:
        for part, _ in shares.values():     # a part alone makes no share
            tmx.inc_counter(part, 2)
        assert not set(shares) & set(sched.stats())
        for i, (_, whole) in enumerate(shares.values()):
            tmx.inc_counter(whole, 3 + i)
        stats = sched.stats()
        assert ({key: stats[key] for key in shares}
                == {key: round(2 / (3 + i), 4) for i, key in enumerate(shares)})
    finally:
        tmx.configure(False)
    assert not set(shares) & set(sched.stats())
