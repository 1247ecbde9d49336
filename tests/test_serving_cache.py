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
  and the tokens are those of the engine without a mesh.
"""

import jax
import jax.numpy as jnp
import pytest

from chip_probes import serve_cache_programs
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import make_mesh, sharding_for
from horovod_tpu.serving.decode import DecodeEngine

L, B, S, H, HD, V = 6, 4, 256, 2, 16, 64
LANE_ELEMS = B * S * H * HD


@pytest.fixture(scope="module")
def model():
    cfg = tfm.TransformerConfig(
        vocab_size=V, d_model=H * HD, n_layers=L, n_heads=H, d_ff=64,
        max_seq_len=S, compute_dtype=jnp.float32, remat=False)
    return cfg, tfm.init(jax.random.PRNGKey(0), cfg)


# The CPU backend's matrix product wants its K and V lane transposed, which
# costs the step two lanes of temporaries; the chip's compiler reads the
# lane where it lies (tests/test_chip_smoke.py).
@pytest.mark.parametrize("program,update,temp_lanes", [
    ("step", "fusion:scatter", 3),
    ("install", "fusion:dynamic-update-slice", 1)])
def test_compiled_program_updates_the_donated_caches_in_place(
        model, program, update, temp_lanes):
    cfg, _ = model
    got = serve_cache_programs(cfg, B, L * LANE_ELEMS)[program]
    lane_bytes = 4 * LANE_ELEMS
    assert [op for _, op in got["big_ops"]] == [update, update], got
    assert got["alias_bytes"] == 2 * L * lane_bytes
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
