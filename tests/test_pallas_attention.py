"""Pallas flash-attention kernel vs the dense oracle (interpret mode on
the CPU backend; the same kernels compile to Mosaic on TPU)."""

import math

import numpy as np
import pytest

from horovod_tpu.ops.pallas_attention import flash_attention


def _ref_attn(jax, q, k, v, causal=True):
    import jax.numpy as jnp

    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bshk,bthk->bhst", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bthk->bshk", p, v)


def _qkv(jax, seed=0, B=2, S=128, H=4, D=32):
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_dense(jax, causal):
    q, k, v = _qkv(jax)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    expect = _ref_attn(jax, q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_grads_match_dense(jax):
    import jax.numpy as jnp

    q, k, v = _qkv(jax, seed=1)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=64,
                                       block_k=64) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(_ref_attn(jax, q, k, v) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_uneven_blocks(jax):
    # S not divisible by the requested block: _pick_block degrades.
    q, k, v = _qkv(jax, seed=2, S=96)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    expect = _ref_attn(jax, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_transformer_flash_impl_matches_dense(jax):
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                d_ff=64, max_seq_len=64, compute_dtype=jnp.float32)
    cfg_d = tfm.TransformerConfig(attn_impl="dense", **base)
    cfg_f = tfm.TransformerConfig(attn_impl="flash", **base)
    params = tfm.init(jax.random.PRNGKey(0), cfg_d)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, 64)), jnp.int32)
    ld, _ = tfm.apply(params, toks, cfg_d)
    lf, _ = tfm.apply(params, toks, cfg_f)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                               rtol=5e-4, atol=5e-4)


def test_transformer_flash_under_dp_mesh(jax, eight_devices):
    # dp>1: the flash call must route through the manual-dp shard_map
    # wrapper (a pallas_call has no GSPMD partitioning rule).
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import mesh as mesh_mod

    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                d_ff=64, max_seq_len=64, compute_dtype=jnp.float32)
    cfg_f = tfm.TransformerConfig(attn_impl="flash", **base)
    cfg_d = tfm.TransformerConfig(**base)
    mesh = mesh_mod.make_mesh({"dp": 2}, devices=eight_devices[:2])
    params = tfm.init(jax.random.PRNGKey(0), cfg_f)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 64)), jnp.int32)
    lf, _ = jax.jit(
        lambda p, t: tfm.apply(p, t, cfg_f, mesh=mesh))(params, toks)
    ld, _ = tfm.apply(params, toks, cfg_d)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("attn_impl", ["flash", "dense"])
def test_checkpointed_layer_keeps_the_kernels_two_results(jax, attn_impl):
    """``remat`` changes what a layer keeps, never the numbers: the loss
    and every gradient leaf are those of the step that keeps everything.
    A checkpointed flash layer keeps its arguments, the kernel's output
    and its log-sum-exp (so its re-forward does not call the kernel
    again); a dense one keeps what a bare ``jax.checkpoint`` keeps."""
    import dataclasses

    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals

    from horovod_tpu.models import transformer as tfm

    B, S, H, D = 2, 64, 4, 8
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=H * D, n_layers=2, n_heads=H, d_ff=64,
        max_seq_len=S, compute_dtype=jnp.float32, attn_impl=attn_impl)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (B, S)), jnp.int32)

    def loss_and_grads(remat):
        return jax.value_and_grad(tfm.loss_fn)(
            params, toks, jnp.roll(toks, -1, 1),
            dataclasses.replace(cfg, remat=remat))

    (kept_loss, kept), (loss, grads) = map(loss_and_grads, (False, True))
    np.testing.assert_allclose(float(loss), float(kept_loss), rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(kept)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)

    x = jnp.ones((B, S, H * D), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])

    def residuals(layer):
        return [(str(aval), src) for aval, src in saved_residuals(
            lambda x, lp: layer(x, lp, cfg, None)[0], x, lp)]

    saved = residuals(tfm.remat_layer())
    made = [r for r in saved if "from the argument" not in r[1]]
    assert ("float32[2,64,32]", "from the argument x") in saved
    if attn_impl == "dense":
        assert saved == residuals(
            jax.checkpoint(tfm._layer, static_argnums=(2, 3)))
        assert not made
    else:
        (o, o_src), (lse, lse_src) = made
        assert o == f"float32[{B * H},{S},{D}]", made
        assert "pallas_attention.py" in o_src, made
        assert lse == f"float32[{B * H},{S}]", made
        assert "named 'flash_lse'" in lse_src, made
        # the step's program: one forward kernel call a layer, in the
        # forward scan's body; none in the backward scan's
        step = str(jax.make_jaxpr(lambda p: loss_and_grads(True))(params))
        assert step.count("name=flash_fwd") == step.count(
            "name=flash_bwd_dq") > 0
