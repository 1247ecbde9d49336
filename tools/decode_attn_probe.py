"""Time the decode attention kernel (ops/pallas_decode_attention.py) alone
against the masked read of the whole lane it replaced, on the chip, at the
dense decoder's serving shape of the benchmark and a few fillings of the
table.  (Not at latent attention's: standing alone, its rotary keys reach
the kernel in another layout than the engine's state has and XLA
transposes them once a layer, 46 ms a turn that no cell pays: read that
shape's ``decode_attn`` in the cell's own trace, PERF.md section 6.)

    chiprun -- python3 tools/decode_attn_probe.py [--blocks 128,256,512]

One JSON line a (shape, filling, block): milliseconds for ONE call over
all layers of the stack (a decode turn's attention), the same for the
masked read, the widest difference of the two outputs, and the share of
the lanes' positions the kernel's blocks cover.  A number from a CPU run
of this file is the interpreter's, not the kernel's: it refuses to run
without a TPU.
"""

import argparse
import json
import math
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from horovod_tpu.ops import pallas_decode_attention as pda  # noqa: E402
from test_pallas_decode_attention import masked_read  # noqa: E402

SHAPES = {
    # olmo-1b_serve_chat: 32 slots x 1536, 16 heads of 128, K and V
    "heads": dict(L=8, B=32, T=1536, hq=16, dims=(128,), dv=128),
}


def fillings(shape, B, T, rng):
    """pos [B] of a few tables: name -> positions (0 = free slot)."""
    def table(live, lo, hi):
        pos = np.zeros(B, np.int32)
        at = rng.choice(B, live, replace=False)
        pos[at] = rng.integers(lo, hi, live)
        return pos

    return {"chat_3of32": table(3, 200, 500),
            "loaded_20of32": table(20, 64, 1280),
            "full": np.full(B, T - 1, np.int32)}


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="")
    ap.add_argument("--shapes", default="heads")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    rng = np.random.default_rng(31)
    for shape in a.shapes.split(","):
        c = SHAPES[shape]
        L, B, T, hq = c["L"], c["B"], c["T"], c["hq"]
        key = iter(jax.random.split(jax.random.PRNGKey(0), 8))

        def normal(*dims):
            return jax.random.normal(next(key), dims, jnp.bfloat16)

        keys, value = (normal(L, B, T, hq, 128),), normal(L, B, T, hq, 128)
        q = tuple(normal(L, B, hq, d) for d in c["dims"])
        scale = 1.0 / math.sqrt(sum(c["dims"]))

        def turn(attend, block=None):
            def run(q, keys, value, pos):
                # as a model's step does: the list of blocks made once
                work = block and pda.work_list(pos, T, block)

                def layer(l, acc):
                    out = attend([qp[l] for qp in q], keys, value, l, pos,
                                 scale=scale, block=block, work=work)
                    return acc + out.astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, L, layer, jnp.zeros((B, hq, c["dv"]), jnp.float32))
            return jax.jit(run)

        dense = turn(masked_read)
        blocks = [int(b) for b in a.blocks.split(",") if b] or [
            pda.block_for(T, shared=False)]
        for name, pos in fillings(shape, B, T, rng).items():
            pos = jnp.asarray(pos)
            dense_ms, want = timed(dense, q, keys, value, pos)
            for block in blocks:
                # two buffers an array, under the kernel's 16 MB of VMEM
                if T % block or 4 * block * hq * (
                        sum(c["dims"]) + c["dv"]) > 12 << 20:
                    continue
                ms, got = timed(turn(pda.decode_attention, block), q, keys,
                                value, pos)
                read = int(jnp.sum(pda.blocks_read(pos, block))) * block
                live = np.asarray(pos) > 0
                print(json.dumps({
                    "shape": shape, "filling": name, "block": block,
                    "kernel_ms_a_turn": round(ms, 4),
                    "masked_ms_a_turn": round(dense_ms, 4),
                    "max_abs_diff": float(jnp.max(jnp.abs(
                        (got - want)[live]))),
                    "finite": bool(jnp.isfinite(got).all()),
                    "read_share": round(read / (B * T), 4),
                    "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
