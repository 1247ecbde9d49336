"""Time a decode turn's Mamba-2 state step alone, on the chip, at the
``nemotron-3-nano-30b-a3b`` cell's state (4 Mamba-2 layers, 96 slots, 64
heads of [64, 128] float32 a slot a layer: 0.81 GB), as a decode step runs
it: one call a layer inside a ``fori_loop`` whose carry is the stacked
state, donated.

    chiprun -- python3 tools/ssd_step_probe.py [--forms xla,kernel,mxu]
        [--live 64,96,0]

One JSON line a form and a number of live slots: milliseconds a TURN (all
layers), and the share of the memory roofline that is (one read and one
write of the LIVE slots' state over the chip's bytes/s x that time).  The
forms:

* ``kernel``: ``ops/pallas_ssd.py:ssd_step`` (the state ``[L, B, G, N,
  W]``: the read-out's sum runs down the sublanes; the live slots are the
  grid); ``kernel@16``: the same with 16 rows a tile of its loop
  (``pallas_ssd.TILE``);
* ``xla``: the update in place and then the read-out as a multiply and a
  sum over every slot (``models/ssd_moe.py:_ssd_step`` on a layer cut out
  of ``[L, B, H, P, N]`` and put back: what the step was before the
  kernel);
* ``mxu``: the other read-out this file keeps for the comparison alone: the
  state as it was, ``N`` along the lanes, the same work list, the read-out
  as a product of the state's rows, STREAMED, with ``C`` in the columns of
  a stationary [128, 128] operand at ``precision=highest``, the answers
  taken off the result's diagonal.

Every form starts from the same state and steps the same inputs, and the
line says how far its answers and its new state lie from ``xla``'s on the
live slots, and whether the free slots' state is bit for bit what it was
(``--live 0`` is the turn with no request at all).

A number from a CPU run of this file is the interpreter's, not the
kernel's: it refuses to run without a TPU.
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from horovod_tpu.models.layers import _at, _put  # noqa: E402
from horovod_tpu.models.ssd_moe import _ssd_step  # noqa: E402
from horovod_tpu.ops import pallas_ssd  # noqa: E402
from horovod_tpu.ops.pallas_attention import _pallas_call  # noqa: E402

L, B, H, P, G, N = 4, 96, 64, 64, 8, 128
HBM_BYTES_PER_S = 819e9     # TPU v5e (perfbench/peaks.py)
HI = lax.Precision.HIGHEST
LANES = 128
TILE = pallas_ssd.TILE      # the kernel's own rows a tile


def _mxu_kernel(layer_ref, slots_ref, count_ref, b_ref, c_ref, decay_ref,
                dx_ref, s_ref, y_ref, out_ref, cols_scr):
    """One live slot, ``N`` along the lanes.  b, c [G, N]; decay, dx, y
    [H P / 128, 128] (row k: the values of state rows 128 k ..); s, out
    [H, P, N]; cols_scr [128, 2 H P / 128]."""
    i = pl.program_id(0)
    count = count_ref[0]
    heads = LANES // P                  # heads in 128 rows of the state
    blocks = decay_ref.shape[0]         # 128-row blocks a slot
    per_group = blocks // G

    @pl.when(i < count)
    def _step():
        cols_scr[:, :blocks] = decay_ref[...].T
        cols_scr[:, blocks:] = dx_ref[...].T
        c_cols = c_ref[...].T                               # [N, G]
        eye = (lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
               == lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
        for g in range(G):
            c_mat = jnp.broadcast_to(c_cols[:, g:g + 1], (N, LANES))
            b_row = b_ref[g:g + 1, :]
            for k in range(g * per_group, (g + 1) * per_group):
                at = slice(k * heads, (k + 1) * heads)
                s = s_ref[at].reshape(LANES, N)
                s = cols_scr[:, k:k + 1] * s \
                    + cols_scr[:, blocks + k:blocks + k + 1] * b_row
                out_ref[at] = s.reshape(heads, P, N)
                res = jnp.dot(s, c_mat, precision=HI,
                              preferred_element_type=jnp.float32)
                y_ref[k:k + 1, :] = jnp.sum(jnp.where(eye, res, 0.0),
                                            axis=0, keepdims=True)

    @pl.when((count == 0) & (i == 0))
    def _through():
        out_ref[...] = s_ref[...]


def mxu_step(S, layer, work, x, dt, a, b_in, c_out):
    """``ssd_step``'s contract on ``S`` [L, B, H, P, N]."""
    slots, count, live = work
    blocks = H * P // LANES
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    dx = dt[..., None] * x

    def of_slot(i, layer_ref, slots_ref, count_ref):
        return slots_ref[i], 0, 0

    def of_state(i, layer_ref, slots_ref, count_ref):
        return layer_ref[0], slots_ref[i], 0, 0, 0

    group = pl.BlockSpec((None, G, N), of_slot)
    rows = pl.BlockSpec((None, blocks, LANES), of_slot)
    state = pl.BlockSpec((None, None, H, P, N), of_state)
    y, S = _pallas_call(
        "ssd_step_mxu", _mxu_kernel,
        jnp.asarray(layer, jnp.int32).reshape(1), slots, count, b_in, c_out,
        decay.reshape(B, blocks, LANES), dx.reshape(B, blocks, LANES), S,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[group, group, rows, rows, state],
            out_specs=[rows, state],
            scratch_shapes=[pltpu.VMEM((LANES, 2 * blocks), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, blocks, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        input_output_aliases={7: 1})
    return S, jnp.where(live[:, None, None], y.reshape(B, H, P), 0.0)


def xla_step(S, layer, work, x, dt, a, b_in, c_out):
    y, new = _ssd_step(_at(S, layer), x, dt, a, b_in, c_out)
    return _put(S, layer, new), jnp.where(work[2][:, None, None], y, 0.0)


FORMS = {"xla": (xla_step, False), "mxu": (mxu_step, False),
         "kernel": (pallas_ssd.ssd_step, True)}


def turn(one_layer):
    def run(S, live, x, dt, a, b_in, c_out):
        work = pallas_ssd.live_slots(live)

        def layer(l, carry):
            S, total = carry
            S, y = one_layer(S, l, work, x, dt, a, b_in, c_out)
            return S, total + y
        return lax.fori_loop(0, L, layer,
                             (S, jnp.zeros((B, H, P), jnp.float32)))
    return jax.jit(run, donate_argnums=(0,))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="xla,kernel,kernel@16,kernel@32,mxu")
    ap.add_argument("--live", default="64,96,0")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    keys = jax.random.split(jax.random.PRNGKey(47), 6)
    x = jax.random.normal(keys[0], (B, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, H)) - 2)
    neg_a = -jax.random.uniform(keys[2], (H,), jnp.float32, 1.0, 16.0)
    b_in = jax.random.normal(keys[3], (B, G, N), jnp.float32)
    c_out = jax.random.normal(keys[4], (B, G, N), jnp.float32)
    fresh = jax.jit(lambda k: jax.random.normal(k, (L, B, H, P, N),
                                                jnp.float32))
    to_kernel = jax.jit(lambda s: pallas_ssd.from_heads(s, G),
                        donate_argnums=(0,))
    to_heads = jax.jit(lambda s: pallas_ssd.to_heads(s, H),
                       donate_argnums=(0,))
    slot_bytes = L * H * P * N * 4

    def one_form(form, n_live, live, want):
        name, _, tile = form.partition("@")
        one_layer, lanes_last = FORMS[name]
        # kernel@16: the kernel with that many rows a tile
        pallas_ssd.TILE = int(tile) if tile else TILE
        fn = turn(one_layer)
        args = (live, x, dt, neg_a, b_in, c_out)
        S = fresh(keys[5])
        if lanes_last:
            S = to_kernel(S)
        S, y = fn(S, *args)
        first = jax.device_get(
            (to_heads(jnp.copy(S)) if lanes_last else S, y))
        t0 = time.perf_counter()
        for _ in range(20):
            S, _ = fn(S, *args)
        jax.block_until_ready(S)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        del S
        line = {"form": form, "live": n_live,
                "ms_a_turn": round(ms, 4),
                "live_roofline_pct": round(
                    100 * 2 * n_live * slot_bytes
                    / (HBM_BYTES_PER_S * ms * 1e-3), 2),
                "device": dev.device_kind}
        if form == "xla":
            want = first
        elif want is not None:
            on = np.asarray(live)
            start = np.asarray(fresh(keys[5]))
            line["state_max_diff"] = float(np.abs(
                first[0][:, on] - want[0][:, on]).max(initial=0.0))
            line["y_max_diff"] = float(np.abs(
                first[1] - np.where(on[:, None, None], want[1],
                                    0.0)).max())
            line["free_slots_untouched"] = bool(
                (first[0][:, ~on] == start[:, ~on]).all())
            del start
        print(json.dumps(line), flush=True)
        return want

    for n_live in (int(n) for n in a.live.split(",")):
        # the live slots spread over the table, as a served table's are
        live = jnp.asarray((np.arange(B) * n_live) // B
                           != (np.arange(B) - 1) * n_live // B)
        want = None
        for form in a.forms.split(","):
            try:
                want = one_form(form, n_live, live, want)
            except Exception as e:  # a form Mosaic refuses: say so, go on
                print(json.dumps({"form": form, "live": n_live,
                                  "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
