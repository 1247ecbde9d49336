"""Sequence-parallel attention vs the full-attention oracle on the
virtual 8-device mesh — exactness, not approximation, is the contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import mesh as mesh_mod
from horovod_tpu.parallel import ring_attention as ra


def _qkv(rng, B=2, S=32, H=4, D=16):
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [True, False])
def test_sharded_attention_matches_full(eight_devices, rng, impl, causal):
    mesh = mesh_mod.make_mesh({"sp": 8}, devices=eight_devices)
    q, k, v = _qkv(rng, H=8)  # ulysses needs H % sp == 0
    want = ra.full_attention(q, k, v, causal=causal)
    fn = ra.make_sharded_attention(mesh, impl=impl, causal=causal)
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_dp_sp_mesh(eight_devices, rng):
    mesh = mesh_mod.make_mesh({"dp": 2, "sp": 4}, devices=eight_devices)
    q, k, v = _qkv(rng, B=4, S=16)
    want = ra.full_attention(q, k, v)
    fn = ra.make_sharded_attention(mesh, impl="ring")
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_gradients_match(eight_devices, rng):
    """d(out)/d(q,k,v) through the ring matches the oracle's gradients —
    the property training actually needs."""
    mesh = mesh_mod.make_mesh({"sp": 4}, devices=eight_devices[:4])
    q, k, v = _qkv(rng, B=1, S=16, H=2, D=8)
    fn = ra.make_sharded_attention(mesh, impl="ring")

    def loss_sharded(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(ra.full_attention(q, k, v) ** 2)

    g1 = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_head_divisibility(eight_devices, rng):
    mesh = mesh_mod.make_mesh({"sp": 8}, devices=eight_devices)
    q, k, v = _qkv(rng, H=4)  # 4 heads, 8-way sp → invalid
    fn = ra.make_sharded_attention(mesh, impl="ulysses")
    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(fn)(q, k, v)


def test_bad_impl_name(eight_devices):
    mesh = mesh_mod.make_mesh({"sp": 8}, devices=eight_devices)
    with pytest.raises(ValueError, match="impl"):
        ra.make_sharded_attention(mesh, impl="flash")


def test_transformer_ring_attention_matches_dense(eight_devices):
    """Flagship integration: the transformer with attn_impl='ring' on a
    dp×sp mesh produces the same logits as the dense GSPMD path."""
    import dataclasses

    from horovod_tpu.models import transformer as tfm

    mesh = mesh_mod.make_mesh({"dp": 2, "sp": 4}, devices=eight_devices)
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=32, compute_dtype=jnp.float32, attn_impl="ring")
    cfg_dense = dataclasses.replace(cfg, attn_impl="dense")
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 16)), jnp.int32)

    ring_logits, _ = jax.jit(
        lambda p, t: tfm.apply(p, t, cfg, mesh=mesh))(params, toks)
    dense_logits, _ = jax.jit(
        lambda p, t: tfm.apply(p, t, cfg_dense, mesh=mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(ring_logits),
                               np.asarray(dense_logits),
                               rtol=2e-4, atol=2e-4)


def test_checkpointed_ring_layer_keeps_no_hop(eight_devices):
    """The ring calls the flash kernel once a hop through the rule that
    names the model's own call's output and log-sum-exp for a layer's
    checkpoint.  A hop's carry no name, so a checkpointed ring layer keeps
    what a bare ``jax.checkpoint`` keeps, its arguments, and not ``sp``
    hops' partial outputs."""
    from jax._src.ad_checkpoint import saved_residuals

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops.pallas_attention import SAVED_NAMES, block_pairs

    mesh = mesh_mod.make_mesh({"sp": 4}, devices=eight_devices[:4])
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=32, compute_dtype=jnp.float32, attn_impl="ring")
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.ones((2, 32, 32), jnp.float32)

    def residuals(layer):
        return [(str(aval), src) for aval, src in saved_residuals(
            lambda x, lp: layer(x, lp, cfg, mesh)[0], x, lp)]

    saved = residuals(tfm.remat_layer())
    assert saved == residuals(
        jax.checkpoint(tfm._layer, static_argnums=(2, 3)))
    # ... and, for the re-forward of the hops' kernel, the three vectors of
    # its schedule (pallas_attention.block_pairs: one pair at a shard of 8
    # positions): constants of the trace, those three and nothing else
    steps = len(block_pairs(32 // 4, 8, 8, False)[0])
    constants = [(aval, src) for aval, src in saved
                 if "from the argument" not in src]
    assert constants == [(f"int32[{steps}]", "from a constant")] * 3, saved
    # the names sit in the kernel's forward RULE, which only a
    # differentiated trace runs: the hops do run it, and name nothing
    grad = str(jax.make_jaxpr(jax.grad(
        lambda x, lp: tfm._layer(x, lp, cfg, mesh)[0].sum()))(x, lp))
    assert "flash_bwd_dq" in grad
    assert not any(name in grad for name in SAVED_NAMES)
