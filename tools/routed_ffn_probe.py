"""Time a decode turn's routed experts alone, on the chip, at the
``lfm2-8b-a1b`` cell's shapes: 12 expert layers of 32 gated experts, the
stacks ``[12, 32, 2048, 2048]`` bfloat16 (9.66 GB held, 1792 columns
published), 192 slots x 4 = 768 (row, expert) pairs of which the live
slots' are in a group, one call a layer at a static index as
``models/conv_moe.py`` makes them.

    chiprun -- python3 tools/routed_ffn_probe.py [--live 116,192,24]
        [--forms ragged,kernel@64x256,...] [--skew 0.25]

One JSON line a form and a number of live slots: milliseconds a TURN (all
layers) on the host's clock over 20 turns, and GB/s of NEEDED bytes: the
published three matrices of every expert with a row, once a layer
(``perfbench/conv_moe_lm_count.py:routed_product_bytes`` counts the same).
The forms:

* ``ragged``: step 2 of ``models/experts.py:routed_ffn`` as it was, three
  ``jax.lax.ragged_dot`` calls over the ``L x E`` groups;
* ``kernel``: ``ops/pallas_routed_ffn.py:routed_ffn_rows`` as the module
  holds it; ``kernel@RxC``: the same with windows of ``R`` rows
  (``pallas_routed_ffn.ROWS``) and grid steps of ``C`` columns
  (``COLUMNS``).

The live rows choose 4 distinct experts each, by scores that lean on some
experts (``--skew``: the spread of a bias an expert; 0.25 drew a fullest
expert 2.0 and 1.8 times the mean at 116 and 192 live slots, where the
cell counts 1.6 to 1.75); the line says what was drawn.  Every form runs the same pairs and the line says how far its
result lies from ``ragged``'s.

A number from a CPU run of this file is the interpreter's, not the
kernel's: it refuses to run without a TPU.
"""

import argparse
import functools
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

from horovod_tpu.ops import pallas_routed_ffn as prf  # noqa: E402

L, E, D, F, HELD, SLOTS, K = 12, 32, 2048, 1792, 2048, 192, 4
PAIRS = SLOTS * K
BF16 = jnp.bfloat16
TILES = (prf.ROWS, prf.COLUMNS)     # the kernel's own


def ragged_rows(xs, counts, layer, w_in, w_gate, w_out, width=None):
    groups = lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), counts, (layer * E,))

    def grouped(rows, w):
        return lax.ragged_dot(rows, w.reshape((L * E,) + w.shape[2:]),
                              groups)

    h = grouped(xs, w_in) * jax.nn.silu(grouped(xs, w_gate))
    ys = grouped(h, w_out)
    return jnp.where((jnp.arange(PAIRS) < jnp.sum(counts))[:, None], ys, 0)


def turn(one_layer):
    def run(xs, counts, w_in, w_gate, w_out):
        total = jnp.zeros(xs.shape, jnp.float32)
        for l in range(L):
            ys = one_layer(xs, counts, l, w_in, w_gate, w_out, F)
            total = total + ys.astype(jnp.float32)
            xs = xs + ys * 0.01             # a layer waits for the last
        return total
    return jax.jit(run)


def draw(rng, n_live, skew):
    """(counts [E], the fullest over the mean of the touched) for
    ``n_live`` rows of 4 distinct experts each."""
    bias = skew * rng.standard_normal(E)
    scores = rng.gumbel(size=(n_live, E)) + bias
    chosen = np.argsort(-scores, axis=1)[:, :K]
    counts = np.bincount(chosen.ravel(), minlength=E)
    return counts.astype(np.int32), counts.max() / counts[counts > 0].mean()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="ragged,kernel")
    ap.add_argument("--live", default="116,192,24")
    ap.add_argument("--skew", type=float, default=0.25)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    keys = jax.random.split(jax.random.PRNGKey(50), 4)

    @functools.partial(jax.jit, static_argnums=(1,))
    def stack(key, out):
        """One layer's experts drawn, the others the same numbers scaled
        a layer (no layer's result is another's); zeros past F."""
        one = jax.random.normal(key, (E, F, D) if out else (E, D, F),
                                BF16) * 0.02
        grown = 1.0 + 0.01 * jnp.arange(L, dtype=jnp.float32)
        pad = [(0, 0)] * 4
        pad[2 if out else 3] = (0, HELD - F)
        return jnp.pad(one[None] * grown[:, None, None, None].astype(BF16),
                       pad)

    w_in, w_gate, w_out = (stack(keys[0], False), stack(keys[1], False),
                           stack(keys[2], True))
    xs = jax.random.normal(keys[3], (PAIRS, D), BF16)
    rng = np.random.default_rng(50)

    for n_live in (int(n) for n in a.live.split(",")):
        counts, skewed = draw(rng, n_live, a.skew)
        need = L * int((counts > 0).sum()) * 3 * D * F * 2
        args = (xs, jnp.asarray(counts), w_in, w_gate, w_out)
        want = None
        for form in a.forms.split(","):
            name, _, tiles = form.partition("@")
            line = {"form": form, "live_slots": n_live,
                    "pairs_in_groups": int(counts.sum()),
                    "experts_touched": int((counts > 0).sum()),
                    "fullest_over_mean": round(float(skewed), 3)}
            try:
                if name == "ragged":
                    fn = turn(ragged_rows)
                else:
                    prf.ROWS, prf.COLUMNS = TILES
                    if tiles:
                        rows, cols = (int(n) for n in tiles.split("x"))
                        prf.ROWS, prf.COLUMNS = rows, (cols,)
                    line["rows"], line["columns"] = (prf.ROWS,
                                                     prf.columns(F, HELD)[0])
                    fn = turn(prf.routed_ffn_rows)
                got = np.asarray(fn(*args))
                t0 = time.perf_counter()
                for _ in range(20):
                    out = fn(*args)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / 20 * 1e3
                line.update(ms_a_turn=round(ms, 4),
                            needed_gb_per_s=round(need / ms / 1e6, 1),
                            device=dev.device_kind)
                if name == "ragged":
                    want = got
                elif want is not None:
                    line["max_diff"] = float(np.abs(got - want).max())
                    line["max_abs"] = float(np.abs(want).max())
            except Exception as e:  # a form Mosaic refuses: say so, go on
                line["error"] = repr(e)[:300]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
