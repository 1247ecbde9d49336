"""Time the flash attention kernel's three calls (ops/pallas_attention.py:
``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``) alone on the chip, at
the LM training cell's shape or a given one.

    chiprun -- python3 tools/flash_attn_probe.py [--shape B,H,S,D]
        [--non-causal] [--lse] [--flat] [--steps 10]

It runs ``jax.grad`` of ``flash_attention`` (``--lse``:
``flash_attention_lse`` with a cotangent on the log-sum-exp, a ring hop's
call; ``--flat``: of operands ``[B, S, H * D]`` with ``n_heads``, which
the model's flash branch passes and the calls read in place where ``D`` is
a multiple of 128, not ``[B, S, H, D]``, whose reshape to that form is a
copy on the chip) ``--steps`` times under the profiler and reads the
device's own clock, as the cell's per-kernel metrics do (``perfbench/readers/
kernel_ms.py``: self time of the Mosaic calls by instruction name).  One
JSON line a call: milliseconds a call and the share of the call's matmul
roofline (2, 3 and 4 products of S x S x D a head, half of each where
causal, at the chip's bfloat16 peak); then one line with what else ran on
the device, the ops by self time a step: the transposes around the call,
``delta``, and whatever XLA does to the per-row vectors.  A number from a
CPU run of this file is the interpreter's, not the kernel's: it refuses to
run without a TPU.
"""

import argparse
import json
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import device_peaks  # noqa: E402
from horovod_tpu.ops import pallas_attention as pa  # noqa: E402
from perfbench import trace as tr  # noqa: E402

# matmuls of S x S x D a head in each call
MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,16,2048,128",
                    help="B,H,S,D (default: olmo-1b_train_s2048's)")
    ap.add_argument("--non-causal", action="store_true")
    ap.add_argument("--lse", action="store_true")
    ap.add_argument("--flat", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    peak = device_peaks.peak(dev.device_kind).bf16_flops
    B, H, S, D = map(int, a.shape.split(","))
    causal = not a.non_causal
    shape, heads = ((B, S, H * D), {"n_heads": H}) if a.flat else (
        (B, S, H, D), {})
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in jax.random.split(jax.random.PRNGKey(0), 4))

    def loss(q, k, v):
        if a.lse:
            o, lse = pa.flash_attention_lse(q, k, v, causal=causal, **heads)
            return jnp.sum(o * w) + jnp.sum(lse)
        o = pa.flash_attention(q, k, v, causal=causal, **heads)
        return jnp.sum(o.astype(jnp.float32) * w)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    jax.block_until_ready(grad(q, k, v))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(a.steps):
                out = grad(q, k, v)
            jax.block_until_ready(out)
        trace = tr.load(tr.find_xplane(tmp))
    chip = sorted(trace.ops)[0]
    needed = 2.0 * S * S * D * B * H * (0.5 if causal else 1.0)
    for name, matmuls in MATMULS.items():
        seconds, calls = tr.op_seconds(
            trace, chip, lambda n: tr.is_mosaic_call(n)
            and name in n.split("=", 1)[0])
        print(json.dumps({
            "call": name, "shape": [B, H, S, D], "causal": causal,
            "lse_cotangent": a.lse, "flat": a.flat, "calls": calls,
            "ms_a_call": round(1e3 * seconds / max(calls, 1), 4),
            "matmul_roofline_pct": round(
                100 * matmuls * needed / peak / (seconds / calls), 2)
            if calls else None,
            "device": dev.device_kind}), flush=True)
    print(json.dumps({
        "ops_ms_a_step": [[n, round(1e3 * s / a.steps, 4)]
                          for n, s in tr.top_ops(trace, chip, 16)],
        "busy_ms_a_step": round(
            1e3 * tr.busy(trace)["busy_s"][chip] / a.steps, 4)}),
        flush=True)


if __name__ == "__main__":
    main()
