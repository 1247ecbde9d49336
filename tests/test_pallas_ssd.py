"""ops/pallas_ssd.py interpreted on the CPU: the kernel ``ssd_step`` against
the plain recurrence step (``models/ssd_moe.py:_ssd_step``) on the slots
that hold a request, and what it leaves alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.ssd_moe import _ssd_step
from horovod_tpu.ops import pallas_ssd

L, B, H, P, N = 3, 6, 8, 4, 16
LIVE = {"all": [1, 1, 1, 1, 1, 1], "none": [0, 0, 0, 0, 0, 0],
        "one": [0, 0, 1, 0, 0, 0], "alternating": [1, 0, 1, 0, 1, 0],
        "last": [0, 0, 0, 0, 0, 1]}


def _inputs(seed, groups):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((B, H)) - 2)),
                     jnp.float32)
    a = jnp.asarray(-rng.uniform(1.0, 16.0, size=(H,)), jnp.float32)
    return (normal(L, B, H, P, N),
            (normal(B, H, P), dt, a, normal(B, groups, N),
             normal(B, groups, N)))


@pytest.mark.parametrize("heads_a_group", [1, 8])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("pattern", sorted(LIVE))
def test_kernel_steps_the_live_slots_and_no_other(pattern, layer,
                                                  heads_a_group):
    """A live slot's new state and its answers are the plain step's to
    float32 rounding (the read-out sums in another order); a free slot's
    state, and every other layer's, is bit for bit what it was and its
    answers are zeros: with no slot live, all of it."""
    G = H // heads_a_group
    live = np.asarray(LIVE[pattern], bool)
    S, step = _inputs(len(pattern) + 10 * layer + heads_a_group, G)
    want_y, want_s = _ssd_step(S[layer], *step)
    got_s, got_y = jax.jit(
        lambda s, l: pallas_ssd.ssd_step(
            s, l, pallas_ssd.live_slots(jnp.asarray(live)), *step),
        donate_argnums=(0,))(pallas_ssd.from_heads(S, G), jnp.int32(layer))
    assert got_s.shape == (L, B, G, N, heads_a_group * P)
    got_s = np.asarray(pallas_ssd.to_heads(got_s, H))
    np.testing.assert_allclose(got_y[live], want_y[live], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_s[layer][live], want_s[live], rtol=1e-6,
                               atol=1e-6)
    assert not np.asarray(got_y)[~live].any()
    others = [l for l in range(L) if l != layer]
    np.testing.assert_array_equal(got_s[layer][~live],
                                  np.asarray(S)[layer][~live])
    np.testing.assert_array_equal(got_s[others], np.asarray(S)[others])


@pytest.mark.parametrize("pattern", sorted(LIVE))
def test_work_list_is_the_live_slots_in_order(pattern):
    """The live slots first, then the last of them again (slot 0 where
    there is none), and how many there are."""
    live = np.asarray(LIVE[pattern], bool)
    slots, count, mask = pallas_ssd.live_slots(jnp.asarray(live))
    on = np.flatnonzero(live)
    assert count.shape == (1,) and int(count[0]) == len(on)
    assert slots.dtype == jnp.int32
    tail = on[-1] if len(on) else 0
    assert np.asarray(slots).tolist() == on.tolist() + [tail] * (B - len(on))
    np.testing.assert_array_equal(mask, live)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_state_layout_round_trips(groups):
    """``from_heads`` puts ``S[h, p, n]`` at ``[h // R, n, (h % R) P + p]``
    and ``to_heads`` takes it back."""
    S = jnp.arange(2 * H * P * N, dtype=jnp.float32).reshape(2, H, P, N)
    got = pallas_ssd.from_heads(S, groups)
    R = H // groups
    assert got.shape == (2, groups, N, R * P)
    h, p, n = 5, 3, 7
    assert got[1, h // R, n, (h % R) * P + p] == S[1, h, p, n]
    np.testing.assert_array_equal(pallas_ssd.to_heads(got, H), S)


@pytest.mark.parametrize("pattern", sorted(LIVE))
def test_kernel_writes_back_only_what_it_computed(pattern, monkeypatch):
    """The same under Pallas's TPU interpreter, whose output buffers start
    as NaN and are written back as the chip's pipeline writes them (the
    plain interpreter starts an aliased output from its input, and so
    cannot see a block that was written back without being computed): the
    steps past the count and the turn with no live slot leave every free
    slot's state as it was, and nothing that is not a number comes back."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def on_tpu_interpreter(name, kernel, *args, **kwargs):
        return pl.pallas_call(
            kernel, name=name, interpret=pltpu.InterpretParams(
                uninitialized_memory="nan"), **kwargs)(*args)

    monkeypatch.setattr(pallas_ssd, "_pallas_call", on_tpu_interpreter)
    G, layer = 2, 1
    live = np.asarray(LIVE[pattern], bool)
    S, step = _inputs(len(pattern), G)
    want_y, want_s = _ssd_step(S[layer], *step)
    got_s, got_y = jax.jit(lambda s: pallas_ssd.ssd_step(
        s, layer, pallas_ssd.live_slots(jnp.asarray(live)), *step))(
            pallas_ssd.from_heads(S, G))
    got_s = np.asarray(pallas_ssd.to_heads(got_s, H))
    want = np.asarray(S).copy()
    want[layer][live] = np.asarray(want_s)[live]
    np.testing.assert_allclose(got_s, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got_s[layer][~live], want[layer][~live])
    np.testing.assert_allclose(
        got_y, np.where(live[:, None, None], want_y, 0.0), rtol=1e-5,
        atol=1e-5)
