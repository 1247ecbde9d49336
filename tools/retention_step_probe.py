"""Time the step form's pass over a retention layer's state alone, on the
chip, at the ``brumby-14b`` cell's shape (32 slots, 5 layers, 8 key/value
heads, a 128 x 8320 float32 matrix a slot a head: 5.45 GB), as a decode
step runs it: one call a layer inside a ``fori_loop`` whose carry is the
stacked state, donated.

    chiprun -- python3 tools/retention_step_probe.py [--forms scale,dot,kernel,reduce,phi]

One JSON line a form: milliseconds a TURN (all layers), and the share of
the memory roofline that is (2 x the state's NEEDED bytes, its 8256 rows
without the 64 of padding, over the chip's bytes/s x that time).  The forms:

* ``kernel``: ``ops/pallas_retention.py:retention_step`` (one read, one
  write);
* ``dot``: the answers as an XLA dot at ``precision=highest`` and then the
  update in place (what ``models/retention.py`` would be without a kernel);
* ``reduce``: the same with the answers as a multiply and a sum, for XLA
  to fuse with the update if it can;
* ``scale``: ``S <- decay S`` alone, the chip's own rate for one read and
  one write of the state;
* ``phi``: the symmetric squares of a turn's queries and keys alone
  (``models/retention.py:phi``), which every form needs.

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
from jax import lax  # noqa: E402

from horovod_tpu.models import retention as R  # noqa: E402
from horovod_tpu.ops.pallas_retention import retention_step  # noqa: E402

L, B, KVH, G, HD = 5, 32, 8, 5, 128
HBM_BYTES_PER_S = 819e9     # TPU v5e (perfbench/peaks.py)
HI = lax.Precision.HIGHEST


def _dot(S, layer, phi, v, decay):
    s_old = lax.dynamic_index_in_dim(S, layer, 0, keepdims=False)
    acc = jnp.einsum("bkgd,bkvd->bkgv", phi[:, :, :G], s_old, precision=HI)
    new = decay[..., None, None] * s_old \
        + v[..., None] * phi[:, :, G][:, :, None]
    return lax.dynamic_update_index_in_dim(S, new, layer, 0), acc


def _reduce(S, layer, phi, v, decay):
    s_old = lax.dynamic_index_in_dim(S, layer, 0, keepdims=False)
    acc = jnp.sum(phi[:, :, :G, None, :] * s_old[:, :, None], axis=-1)
    new = decay[..., None, None] * s_old \
        + v[..., None] * phi[:, :, G][:, :, None]
    return lax.dynamic_update_index_in_dim(S, new, layer, 0), acc


def _scale(S, layer, phi, v, decay):
    s_old = lax.dynamic_index_in_dim(S, layer, 0, keepdims=False)
    return (lax.dynamic_update_index_in_dim(
        S, decay[..., None, None] * s_old, layer, 0),
        jnp.zeros((B, KVH, G, HD), jnp.float32))


def turn(one_layer):
    def run(S, phi, v, decay):
        def layer(l, carry):
            S, total = carry
            S, acc = one_layer(S, l, phi, v, decay)
            return S, total + acc
        return lax.fori_loop(
            0, L, layer, (S, jnp.zeros((B, KVH, G, HD), jnp.float32)))
    return jax.jit(run, donate_argnums=(0,))


def timed(fn, *args, n=10):
    """Milliseconds a call of ``fn`` that donates nothing, and its result."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="scale,dot,kernel,reduce,phi")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    D = R.RetentionConfig().state_rows
    keys = jax.random.split(jax.random.PRNGKey(32), 4)
    qk = jax.random.normal(keys[0], (B, KVH, 8, HD), jnp.bfloat16)
    phi = jax.jit(lambda x: R.phi(x, D))(qk)
    v = jax.random.normal(keys[1], (B, KVH, HD), jnp.float32)
    decay = jax.random.uniform(keys[2], (B, KVH), jnp.float32, 0.9, 1.0)
    fresh = jax.jit(lambda k: jax.random.normal(k, (L, B, KVH, HD, D),
                                                jnp.float32))
    state_bytes = L * B * KVH * HD * D * 4
    needed_bytes = L * B * KVH * HD * (HD * (HD + 1) // 2) * 4
    forms = {"scale": _scale, "dot": _dot, "reduce": _reduce,
             "kernel": lambda S, l, phi, v, d: retention_step(
                 S, l, phi, v, d, n_q=G)}
    want = None
    for form in a.forms.split(","):
        if form == "phi":
            ms, _ = timed(jax.jit(lambda x: R.phi(x, D)), qk, n=20)
            print(json.dumps({"form": "phi", "ms_a_turn": round(ms * L, 4),
                              "device": dev.device_kind}), flush=True)
            continue
        fn = turn(forms[form])
        # every form starts from the same state: the answers compare
        S, first = fn(fresh(keys[3]), phi, v, decay)
        t0 = time.perf_counter()
        for _ in range(10):
            S, _ = fn(S, phi, v, decay)
        jax.block_until_ready(S)
        ms = (time.perf_counter() - t0) / 10 * 1e3
        del S
        if form == "dot":
            want = first
        line = {"form": form, "ms_a_turn": round(ms, 4),
                "roofline_pct": round(100 * 2 * needed_bytes / (
                    HBM_BYTES_PER_S * ms * 1e-3), 2),
                "state_gb": round(state_bytes / 1e9, 4),
                "finite": bool(jnp.isfinite(first).all()),
                "device": dev.device_kind}
        if want is not None and form != "scale":
            line["max_rel_diff_to_dot"] = float(
                jnp.max(jnp.abs(first - want)) / jnp.max(jnp.abs(want)))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
