"""Time the decode attention kernel (ops/pallas_decode_attention.py) alone
against the masked read of the whole lane it replaced, on the chip, at the
dense decoder's serving shape of the benchmark and a few fillings of the
table.  (Not at latent attention's: standing alone, its rotary keys reach
the kernel in another layout than the engine's state has and XLA
transposes them once a layer, 46 ms a turn that no cell pays: read that
shape's ``decode_attn`` in the cell's own trace, PERF.md section 6.)

    chiprun -- python3 tools/decode_attn_probe.py [--blocks 128,256,512]
        [--shapes heads,merged,positions_first,heads_first]

One JSON line a (shape, filling, block): milliseconds for ONE call over
all layers of the stack (a decode turn's attention), the same for the
masked read, the widest difference of the two outputs, and the share of
the lanes' positions the kernel's blocks cover.  The grouped shapes
(``GROUPED``: the three layouts of ``layers._grouped_attention`` at their
cells' lanes) time that function itself, projections and the rows' write
included, a turn's layers over lanes given away, with
``layers.lane_reader`` at each block and with the masked read.  A number
from a CPU run of this file is the interpreter's, not the kernel's: it
refuses to run without a TPU.
"""

import argparse
import json
import math
import sys
import time
from pathlib import Path
from unittest import mock

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from horovod_tpu.models import layers  # noqa: E402
from horovod_tpu.ops import pallas_decode_attention as pda  # noqa: E402
from test_pallas_decode_attention import masked_read  # noqa: E402

SHAPES = {
    # olmo-1b_serve_chat: 32 slots x 1536, 16 heads of 128, K and V
    "heads": dict(L=8, B=32, T=1536, hq=16, dims=(128,), dv=128),
}

# layers._grouped_attention's three layouts at their cells' lanes, and the
# tables each is timed at: (busy slots, lowest, highest position).
GROUPED = {
    # lfm2-8b-a1b_serve_assistants: 192 slots x 2048, 32 heads on 8 of 64
    "merged": dict(L=3, B=192, T=2048, D=2048, hq=32, kvh=8, hd=64,
                   tables={"cell_60of192": (60, 100, 1000),
                           "loaded_120of192": (120, 100, 1000),
                           "full": (192, 2047, 2048)}),
    # nemotron-3-nano-30b-a3b_serve_agents: 96 slots x 4096, 32 on 2 of 128
    "positions_first": dict(L=1, B=96, T=4096, D=2688, hq=32, kvh=2, hd=128,
                            tables={"cell_50of96": (50, 500, 3500),
                                    "full": (96, 4095, 4096)}),
    # jamba2-3b_serve_reason: 64 slots x 1536, 20 heads on 1 of 128
    "heads_first": dict(L=2, B=64, T=1536, D=2560, hq=20, kvh=1, hd=128,
                        tables={"cell_30of64": (30, 200, 1400),
                                "full": (64, 1535, 1536)}),
}


def grouped(shape, blocks, rng, dev):
    """One line a (table, block) of ``GROUPED[shape]``."""
    c = GROUPED[shape]
    L, B, T, kvh, hd = c["L"], c["B"], c["T"], c["kvh"], c["hd"]
    def normal(draw, *dims, scale=1.0):
        key = jax.random.fold_in(jax.random.PRNGKey(1), draw)
        return (scale * jax.random.normal(key, dims, jnp.float32)
                ).astype(jnp.bfloat16)

    lp = {"wq": normal(0, L, c["D"], c["hq"], hd, scale=c["D"] ** -0.5),
          "wk": normal(1, L, c["D"], kvh, hd, scale=c["D"] ** -0.5),
          "wv": normal(2, L, c["D"], kvh, hd, scale=c["D"] ** -0.5),
          "wo": normal(3, L, c["hq"], hd, c["D"], scale=0.02)}
    x = normal(4, B, 1, c["D"])
    lane = {"merged": (L, B, T, kvh * hd), "positions_first": (L, B, T, kvh, hd),
            "heads_first": (L, B, kvh, T, hd)}[shape]

    def turn(block):
        """The turn's attention layers, the kernel at ``block`` or, with
        None, the masked read."""
        def run(x, lp, ks, vs, pos):
            read = None
            if block is not None:
                with mock.patch.dict(layers.LANE_BLOCKS, {shape: block}):
                    read = layers.lane_reader(shape, ks, pos)

            def layer(l, carry):
                acc, ks, vs = carry
                y, (ks, vs) = layers._grouped_attention(
                    x, layers._at(lp, l), jnp.bfloat16,
                    (ks, vs, l, pos, read), layout=shape)
                return acc + y.astype(jnp.float32), ks, vs
            return jax.lax.fori_loop(
                0, L, layer, (jnp.zeros(x.shape, jnp.float32), ks, vs))
        return jax.jit(run, donate_argnums=(2, 3))

    def timed_turn(block, pos, n=20):
        # the same lanes for every form: they are given away to a turn
        ks, vs = normal(5, *lane), normal(6, *lane)
        fn = turn(block)
        out, ks, vs = fn(x, lp, ks, vs, pos)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out, ks, vs = fn(x, lp, ks, vs, pos)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3, out

    for name, (live, lo, hi) in c["tables"].items():
        pos = np.zeros(B, np.int32)
        pos[rng.choice(B, live, replace=False)] = rng.integers(lo, hi, live)
        busy = pos > 0
        pos = jnp.asarray(pos)
        dense_ms, want = timed_turn(None, pos)
        for block in blocks or [layers.lane_block(
                shape, jax.ShapeDtypeStruct(lane, jnp.bfloat16))]:
            if T % block:
                continue
            ms, got = timed_turn(block, pos)
            read = int(jnp.sum(pda.blocks_read(pos, block))) * block
            print(json.dumps({
                "shape": shape, "filling": name, "block": block,
                "kernel_ms_a_turn": round(ms, 4),
                "masked_ms_a_turn": round(dense_ms, 4),
                "max_abs_diff": float(jnp.max(jnp.abs((got - want)[busy]))),
                "finite": bool(jnp.isfinite(got).all()),
                "read_share": round(read / (B * T), 4),
                "device": dev.device_kind}), flush=True)


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
        if shape in GROUPED:
            grouped(shape, [int(b) for b in a.blocks.split(",") if b], rng,
                    dev)
            continue
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
