"""Pallas flash-attention kernel vs the dense oracle (interpret mode on
the CPU backend; the same kernels compile to Mosaic on TPU)."""

import math

import numpy as np
import pytest

from horovod_tpu.ops.pallas_attention import (block_pairs, flash_attention,
                                              flash_attention_lse)


def _ref_attn_lse(jax, q, k, v, causal):
    """The dense oracle in float32: (context, log-sum-exp [B, S, H])."""
    import jax.numpy as jnp

    S, D = q.shape[1], q.shape[3]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    logits = jnp.einsum("bshk,bthk->bhst", q, k) / math.sqrt(D)
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None],
                           logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)                  # [B, H, S]
    out = jnp.einsum("bhst,bthk->bshk", jnp.exp(logits - lse[..., None]), v)
    return out, jnp.moveaxis(lse, 1, 2)


def _ref_attn(jax, q, k, v, causal=True):
    return _ref_attn_lse(jax, q, k, v, causal)[0]


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


# blocks a row: one; four; four query blocks on two key blocks and the
# other way round (the diagonal crosses a pair by its bounds, not qi == ki)
BLOCKS = {"one": (128, 128), "four": (32, 32), "q32_k64": (32, 64),
          "q64_k32": (64, 32)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lse_cotangent", [False, True],
                         ids=["o_only", "lse_cotangent"])
@pytest.mark.parametrize("blocks", list(BLOCKS))
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
def test_flash_matches_dense_whatever_the_schedule(jax, causal, blocks,
                                                   lse_cotangent, dtype):
    """Output, log-sum-exp and the three gradients against the dense
    oracle, over what decides the calls' schedule: causal or not (the list
    of pairs), the blocks a row (none skipped or masked with one; the
    diagonal through unequal blocks), a cotangent on the log-sum-exp (a
    ring hop's) or none, and the inputs' type (bfloat16 rounds the
    probabilities before their products, the oracle does not)."""
    import jax.numpy as jnp

    bq, bk = BLOCKS[blocks]
    q, k, v = (x.astype(dtype) for x in _qkv(jax, seed=3, B=1, H=2))
    rs = np.random.RandomState(4)
    w = jnp.asarray(rs.randn(*q.shape), jnp.float32)
    u = jnp.asarray(rs.randn(1, 128, 2), jnp.float32) * lse_cotangent

    def loss(attend):
        def f(q, k, v):
            o, lse = attend(q, k, v)
            return jnp.sum(o * w) + jnp.sum(lse * u), (o, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, got), got_grads = loss(lambda q, k, v: flash_attention_lse(
        q, k, v, causal=causal, block_q=bq, block_k=bk))(q, k, v)
    (_, want), want_grads = loss(lambda q, k, v: _ref_attn_lse(
        jax, q, k, v, causal))(q, k, v)
    fwd, bwd = {"float32": (2e-5, 2e-4), "bfloat16": (2e-2, 5e-2)}[dtype]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=fwd, atol=fwd)
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=bwd, atol=bwd)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (a kernel's
    body too)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("causal,steps", [(True, 10), (False, 16)])
def test_causal_calls_step_only_the_pairs_the_mask_needs(jax, causal, steps):
    """At four blocks a row a causal call's grid is 10 pairs a head of the
    16, each of the three calls', and a non-causal call's all 16; a query
    block's pairs (for ``flash_bwd_dkv`` a key block's) are consecutive,
    ascending, flagged first and last."""
    import jax.numpy as jnp

    q, k, v = _qkv(jax, B=1, H=2)
    grids = {}
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32)), argnums=(0, 1, 2))
    for eqn in _eqns(jax.make_jaxpr(grad)(q, k, v).jaxpr):
        if eqn.primitive.name == "pallas_call":
            grids.setdefault(eqn.params["name"], set()).add(
                tuple(eqn.params["grid_mapping"].grid))
    assert grids == {name: {(2, steps)} for name in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}

    for by_key in (False, True):
        qi, ki, flags = block_pairs(128, 32, 32, causal, by_key)
        pairs = list(zip(qi.tolist(), ki.tolist()))
        assert len(pairs) == steps
        assert set(pairs) == {(i, j) for i in range(4) for j in range(4)
                              if j <= i or not causal}
        order = [(j, i) for i, j in pairs] if by_key else pairs
        assert order == sorted(order)
        rows = [row for row, _ in order]
        assert [bool(f & 1) for f in flags] == [
            n == 0 or rows[n - 1] != row for n, row in enumerate(rows)]
        assert [bool(f & 2) for f in flags] == [
            n + 1 == steps or rows[n + 1] != row
            for n, row in enumerate(rows)]
        assert not (flags & ~3).any()
    # unequal blocks: a pair is needed by the blocks' bounds
    qi, ki, _ = block_pairs(128, 32, 64, True)
    assert list(zip(qi.tolist(), ki.tolist())) == [
        (0, 0), (1, 0), (2, 0), (2, 1), (3, 0), (3, 1)]


def test_block_pairs_are_read_only_and_bounded():
    """The cache hands every call the same vectors, so they cannot be
    written; and a schedule too long for scalar memory (the full grid
    before it needed none) is refused by name, not by Mosaic."""
    from horovod_tpu.ops.pallas_attention import MAX_PAIRS

    for column in block_pairs(128, 32, 32, True):
        with pytest.raises(ValueError, match="read-only"):
            column[0] = 7
    side = math.isqrt(MAX_PAIRS)
    assert len(block_pairs(8 * side, 8, 8, False)[0]) == MAX_PAIRS
    with pytest.raises(ValueError, match="pairs a head"):
        block_pairs(8 * side + 8, 8, 8, False)


def test_flash_layer_grad_holds_no_column_of_row_numbers(jax):
    """The log-sum-exp, ``delta`` and the lse cotangent travel along the
    lanes: nowhere in the gradient of a flash layer, the kernels' operands
    and results included, is there an array of ``BH * S`` numbers whose
    last dimension is 1 (the chip pads such a one to 128 lanes: 134 MB a
    layer at the training cell's shape for 1 MB of numbers)."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    B, S, H, D = 2, 128, 4, 8
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=H * D, n_layers=2, n_heads=H, d_ff=64,
        max_seq_len=S, compute_dtype=jnp.float32, attn_impl="flash",
        remat=True)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((B, S), jnp.int32)
    step = jax.make_jaxpr(jax.grad(
        lambda p: tfm.loss_fn(p, toks, toks, cfg)))(params)
    calls, columns = 0, []
    for eqn in _eqns(step.jaxpr):
        calls += eqn.primitive.name == "pallas_call"
        for var in (*eqn.invars, *eqn.outvars):
            shape = getattr(var.aval, "shape", ())
            if shape and shape[-1] == 1 and math.prod(shape) == B * H * S:
                columns.append((eqn.primitive.name, shape))
    assert calls >= 3
    assert not columns, columns


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


# ---------------------------------------------------------------------------
# operands read where they lie: [B, S, H * D]
# ---------------------------------------------------------------------------

# (H, B, D, causal, blocks a row, what is called, the operands' form): every
# value of each against the others where an index-map mistake would show
# (a head taken for a row needs H != B, both over 1; a lane block taken in
# units of 128 needs D = 256 with H over 1).
IN_PLACE = [
    (1, 1, 128, True, "one", "o_bf16", "flat"),
    (1, 2, 256, False, "four", "partials_f32", "4d"),
    (1, 2, 128, True, "four", "partials_f32", "4d"),
    (3, 2, 128, True, "four", "o_bf16", "flat"),
    (3, 2, 128, False, "four", "partials_f32", "flat"),
    (3, 1, 256, True, "four", "partials_f32", "4d"),
    (3, 2, 256, False, "one", "o_bf16", "4d"),
    (3, 1, 128, False, "one", "o_bf16", "4d"),
    (16, 2, 128, True, "four", "o_bf16", "4d"),
    (16, 1, 128, False, "one", "partials_f32", "flat"),
    (16, 2, 256, True, "one", "partials_f32", "flat"),
    (16, 1, 256, False, "four", "o_bf16", "flat"),
]


@pytest.mark.parametrize(
    "H,B,D,causal,blocks,call,form", IN_PLACE,
    ids=["h%d-b%d-d%d-%s-%s-%s-%s" % (
        c[0], c[1], c[2], "causal" if c[3] else "full", *c[4:])
        for c in IN_PLACE])
def test_in_place_calls_match_dense(jax, H, B, D, causal, blocks, call, form):
    """Where ``D`` is a whole number of lane tiles the three calls cut a
    head's blocks out of ``[B, S, H * D]``: output, log-sum-exp and the
    gradients of q, k and v against the dense oracle, bfloat16 operands,
    through ``flash_attention`` (a bfloat16 context) and through
    ``flash_attention_lse`` (float32 partials, a cotangent on the
    log-sum-exp), handed ``[B, S, H, D]`` or ``[B, S, H * D]``."""
    import jax.numpy as jnp

    block = 32
    S = block * {"one": 1, "four": 4}[blocks]
    rs = np.random.RandomState(H * 1000 + B * 100 + D)
    q, k, v = (jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)
               for _ in range(3))
    w = jnp.asarray(rs.randn(B, S, H, D), jnp.float32)
    u = jnp.asarray(rs.randn(B, S, H), jnp.float32)
    u = u * (call == "partials_f32")

    def flash(q, k, v):
        given, heads = (q, k, v), {}
        if form == "flat":
            given = (x.reshape(B, S, H * D) for x in given)
            heads = {"n_heads": H}
        kw = dict(causal=causal, block_q=block, block_k=block, **heads)
        if call == "o_bf16":
            o = flash_attention(*given, **kw)
            assert o.dtype == jnp.bfloat16
            lse = jnp.zeros((B, S, H), jnp.float32)
        else:
            o, lse = flash_attention_lse(*given, **kw)
            assert o.dtype == lse.dtype == jnp.float32
        assert o.shape == ((B, S, H * D) if form == "flat" else q.shape)
        return o.reshape(q.shape), lse

    def loss(attend):
        def f(q, k, v):
            o, lse = attend(q, k, v)
            return jnp.sum(o * w) + jnp.sum(lse * u), (o, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, got), got_grads = loss(flash)(q, k, v)
    (_, want), want_grads = loss(
        lambda q, k, v: _ref_attn_lse(jax, q, k, v, causal))(q, k, v)
    if call == "o_bf16":
        got, want = got[:1], want[:1]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == jnp.bfloat16 and a.shape == q.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("D,operands", [(128, (2, 64, 3 * 128)),
                                        (64, (2 * 3, 64, 64))],
                         ids=["d128_in_place", "d64_folded"])
def test_head_dim_decides_how_the_same_calls_read(jax, D, operands):
    """One set of calls, two ways to them, read off ``D`` alone: a multiple
    of 128 and every operand and result of the three calls is
    ``[B, S, H * D]`` with nothing transposed on the way; any other and
    the heads are folded in front of them, ``[B * H, S, D]``.  Either way
    the grid is (heads, pairs) and the numbers are the oracle's."""
    import jax.numpy as jnp

    B, S, H = 2, 64, 3
    rs = np.random.RandomState(D)
    q, k, v = (jnp.asarray(rs.randn(B, S, H * D), jnp.float32)
               for _ in range(3))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, n_heads=H, block_q=32,
                                       block_k=32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    calls, turned = {}, []
    for eqn in _eqns(jax.make_jaxpr(grad)(q, k, v).jaxpr):
        if eqn.primitive.name == "pallas_call":
            big = {v.aval.shape for v in (*eqn.invars, *eqn.outvars)
                   if math.prod(v.aval.shape) == B * S * H * D}
            calls[eqn.params["name"]] = (
                big, tuple(eqn.params["grid_mapping"].grid))
        elif eqn.primitive.name == "transpose":
            turned += [v.aval.shape for v in eqn.outvars
                       if math.prod(v.aval.shape) == B * S * H * D]
    assert calls == {name: ({operands}, (B * H, 3)) for name in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    assert bool(turned) == (D == 64), turned

    def ref(q, k, v):
        return jnp.sum(_ref_attn(jax, *(x.reshape(B, S, H, D)
                                        for x in (q, k, v))) ** 2)

    for a, b in zip(grad(q, k, v), jax.grad(ref, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flat_operands_need_their_heads(jax):
    import jax.numpy as jnp

    x = jnp.zeros((1, 32, 256), jnp.float32)
    with pytest.raises(ValueError, match="n_heads"):
        flash_attention(x, x, x)
    with pytest.raises(ValueError, match="n_heads"):
        flash_attention(x, x, x, n_heads=3)
    with pytest.raises(ValueError, match="n_heads=3"):
        flash_attention(*(3 * [x.reshape(1, 32, 2, 128)]), n_heads=3)


# ---------------------------------------------------------------------------
# the model's flash branch: q, k, v and the context as [B, S, H * HD]
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("H,HD", [(3, 128), (16, 128), (2, 256), (4, 64)])
def test_flat_rotation_is_rope_bit_for_bit(jax, H, HD, dtype):
    """``_rope_flat`` on [B, S, H * HD] gives ``_rope``'s numbers on
    [B, S, H, HD], eagerly and under ``jit`` (bfloat16: bit for bit;
    float32: to the last bit): through the kernel of
    ops/pallas_rope.py where HD is a whole number of 128-lane tiles
    (interpreted here), through ``_rope`` itself where it is not; and its
    gradient is ``_rope``'s."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    B, S = 2, 24
    rs = np.random.RandomState(H)
    x = jnp.asarray(rs.randn(B, S, H, HD), dtype)
    w = jnp.asarray(rs.randn(B, S, H, HD), jnp.float32)

    def calls(fn, *args):
        return sum(e.primitive.name == "pallas_call"
                   for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr))

    flat = lambda x: tfm._rope_flat(x, H, 10000.0)
    assert bool(calls(flat, x.reshape(B, S, H * HD))) == (HD % 128 == 0)
    for run in (lambda f: f, jax.jit):
        want = run(lambda x: tfm._rope(x, 10000.0))(x)
        got = run(flat)(x.reshape(B, S, H * HD))
        assert got.dtype == want.dtype and got.shape == (B, S, H * HD)
        # float32: the same products and sums, which a compiler may
        # contract into fused multiply-adds differently: the last bit
        np.testing.assert_allclose(
            np.asarray(got.reshape(B, S, H, HD), np.float32),
            np.asarray(want, np.float32), rtol=0,
            atol=0 if dtype == "bfloat16" else 5e-7)
    want = jax.grad(lambda x: jnp.sum(tfm._rope(x, 10000.0) * w))(x)
    got = jax.grad(lambda x: jnp.sum(
        flat(x).reshape(B, S, H, HD) * w))(x.reshape(B, S, H * HD))
    tol = 1e-6 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got.reshape(B, S, H, HD), np.float32),
        np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("held", ["init", "serving"])
@pytest.mark.parametrize("H,HD", [(2, 128), (4, 8)],
                         ids=["hd128_in_place", "hd8_folded"])
def test_flash_branch_of_the_layer_matches_the_dense_branch(jax, H, HD, held):
    """The layer through the flash branch (one 2-D product a projection,
    the rotation along the lanes, the kernel on ``[B, S, H * HD]``, ``wo``
    contracting it whole) against the dense branch: its output, the keys
    and values it hands on, and the gradient of every parameter, the
    parameters in the shapes ``init`` makes them ([D, H, HD], [H, HD, D])
    or as a serving engine holds them (``wqkv``)."""
    import dataclasses

    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm

    B, S = 2, 64
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=H * HD, n_layers=1, n_heads=H, d_ff=64,
        max_seq_len=S, compute_dtype=jnp.float32, attn_impl="dense")
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    assert params["layers"]["wq"].shape == (1, H * HD, H, HD)
    assert params["layers"]["wo"].shape == (1, H, HD, H * HD)
    if held == "serving":
        params = tfm.serving_params(params, cfg)
        assert "wqkv" in params["layers"]
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(B, S, H * HD), jnp.float32)
    w = jnp.asarray(rs.randn(B, S, H * HD), jnp.float32)

    def run(attn_impl):
        c = dataclasses.replace(cfg, attn_impl=attn_impl)

        def f(lp, x):
            y, _, kept = tfm._layer(x, lp, c)
            return jnp.sum(y * w), (y, kept)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(lp, x)

    (_, (y_f, kept_f)), grads_f = run("flash")
    (_, (y_d, kept_d)), grads_d = run("dense")
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_d),
                               rtol=5e-4, atol=5e-4)
    for a, b in zip(kept_f, kept_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)
    assert jax.tree.structure(grads_f) == jax.tree.structure(grads_d)
    for a, b in zip(jax.tree.leaves(grads_f), jax.tree.leaves(grads_d)):
        assert a.shape == b.shape
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=5e-4, atol=5e-4)


# sha256 of the lowered text (StableHLO, no locations; jax 0.9.0) of the
# dense decoder's programs that share ``_attention`` with the flash branch
# and must not feel it, at the rehearsal widths of ``olmo-1b_serve_chat``,
# on the commit before the flash branch kept ``[B, S, H * HD]``: the
# engine's step and prefill (``serving_params``: ``wqkv``), and ``_prefill``
# and ``decode_step`` over ``init``'s parameters (``generate()``'s).  A
# change that alters what these compute, or the order they compute it in,
# lands here: change a digest only with ``olmo-1b_serve_chat``'s numbers in
# hand.
SERVING_LOWERED_BEFORE = {
    "engine_step":
        "9e9d8943c19de7bc8d0c1110794b977d230c7cae4fed402eea90e104243d403a",
    "engine_prefill":
        "cf529077012b5c0f254ac54ad26e4e55978d54a72420a3407ba2eeaf1865c4d6",
    "prefill":
        "7e71bd28d9812f3d476d886620a2649f809f209ab4e025c525fb667b269243ce",
    "decode_step":
        "addcb3eda93c590bfe81b54cef97f5d57c569cc5766b51f936205afd7f1654b3"}


@pytest.mark.parametrize("program", list(SERVING_LOWERED_BEFORE))
def test_serving_programs_lower_as_before_the_flat_flash_branch(jax, program):
    import hashlib

    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving import decode

    cfg = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                n_heads=4, d_ff=128, max_seq_len=64,
                                attn_impl="flash")
    slots, cache_len, prompt = 4, 64, 24

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def specs(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    model = decode.slot_model(cfg, cache_len)
    key = jax.random.PRNGKey(0)
    init = specs(jax.eval_shape(lambda k: tfm.init(k, cfg), key))
    held = specs(jax.eval_shape(lambda k: model.held(tfm.init(k, cfg)), key))
    state = specs(jax.eval_shape(lambda: model.init_state(slots)))
    if program == "engine_step":
        lowered = jax.jit(decode.named(decode.STEP_PROGRAM, model.step),
                          donate_argnums=(3,)).lower(
            held, spec((slots,)), spec((slots,)), state)
    elif program == "engine_prefill":
        lowered = jax.jit(decode.named(decode.PREFILL_PROGRAM,
                                       model.prefill)).lower(
            held, spec((prompt,)))
    elif program == "prefill":
        lowered = jax.jit(
            lambda p, t: tfm._prefill(p, t, cfg, cache_len)).lower(
            init, spec((slots, prompt)))
    else:
        lowered = jax.jit(
            lambda p, t, pos, st: tfm.decode_step(p, t, pos, st, cfg)).lower(
            init, spec((slots,)), spec((slots,)), state)
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() \
        == SERVING_LOWERED_BEFORE[program]
