"""Measure the gradient-allreduce / backward-compute overlap fraction.

The analytic 8→256-chip scaling model (docs/benchmarks.md) needs the
fraction of collective time that XLA hides under backward compute; r4
asserted 2/3.  This tool replaces the assertion with a measurement of
what the compiler actually schedules:

1. build the data-parallel train step (grouped in-graph allreduce, the
   compiled-regime gradient path) over an 8-device mesh;
2. compile it and read back the *optimized, scheduled* HLO;
3. walk the entry schedule: every ``all-reduce-start``/``-done`` pair
   brackets the window XLA gave that collective to complete
   asynchronously; sum the estimated cost of independent compute
   instructions inside each window;
4. report ``overlap_fraction`` = hidden-collective-time / total
   collective-time, where a collective's time is its bytes over ICI
   bandwidth and compute time is flops over peak (both per-instruction
   estimates — crude constants, but the *fraction* is dominated by the
   schedule structure, not the constants).

On the TPU platform the compiler runs its latency-hiding scheduler and
emits async pairs; run there for the real number (compilation is enough,
no execution needed).  With no TPU the tool exits non-zero.  A CPU run is
a rehearsal asked for by name (``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``): tiny models, and
since CPU collectives stay synchronous it reports overlap 0 with a note,
which is itself evidence the measurement keys on the real scheduler
rather than wishful parsing.

Usage::

    python tools/measure_overlap.py [--model resnet|transformer]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import device_peaks  # noqa: E402

# The chip whose peaks weight a CPU rehearsal's estimates (a real run uses
# the peaks of the device it compiled for).
REHEARSAL_KIND = "TPU v5 lite"


_F32 = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
        "s8": 1, "u8": 1, "pred": 1}


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO shape string like ``f32[128,256]{1,0}``."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape):
        dtype, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _F32.get(dtype, 4)
    return total


# The opcode follows the result shape, which ends with a layout `}`,
# a bare `]`, or a tuple `)`; matching there keeps lines that merely
# *consume* an all-reduce result classified by their own opcode.
_OPCODE_RE = re.compile(r"[\]\})]\s+([a-z][\w-]*)\(")

_COMPUTE_OPS = {"fusion", "convolution", "dot", "custom-call", "copy",
                "transpose", "reshape", "broadcast", "reduce",
                "reduce-window", "select-and-scatter", "concatenate",
                "dynamic-slice", "dynamic-update-slice", "scatter",
                "gather", "while", "conditional", "sort", "iota", "pad",
                "slice", "add", "multiply", "subtract", "divide"}


def opcode(rhs: str):
    m = _OPCODE_RE.search(rhs)
    return m.group(1) if m else None


def _inst_cost(rhs: str, peak: device_peaks.Peak) -> float:
    """Seconds-estimate for one instruction: result bytes over HBM
    bandwidth (memory-bound estimate; big matmuls run longer than this,
    so compute windows are *under*-credited — conservative for the
    overlap fraction)."""
    return shape_bytes(rhs) / peak.hbm_bytes_per_s


# One shared collective-op vocabulary for the entry walk and the
# non-entry diagnostic (a second hand-maintained list would drift).
_COLLECTIVE_BASES = {"all-reduce", "reduce-scatter", "all-gather",
                     "all-to-all", "collective-permute",
                     "collective-broadcast"}


def _coll_base(op: str):
    """('all-reduce', '-start') for 'all-reduce-start'; ('fusion', '')
    for non-collectives."""
    for suf in ("-start", "-done"):
        if op.endswith(suf):
            return op[: -len(suf)], suf
    return op, ""


def _wire_factor(base: str, n_dev: int) -> float:
    """Payload multiples crossing the slowest link, by collective."""
    if base == "all-reduce":
        return 2 * (n_dev - 1) / n_dev
    if base in ("reduce-scatter", "all-gather"):
        return (n_dev - 1) / n_dev
    return 1.0


def _ring_bytes(rhs: str, op: str) -> int:
    """Payload bytes N of a collective instruction, where the wire
    factors above are defined against N = the FULL (unsharded) buffer.

    all-reduce (incl. variadic): operand shapes sum to N; HLO dumps
    that print operands as bare ``%names`` fall back to the result
    shape — halved for ``-start``, whose result is an
    (operands, results) alias tuple carrying the payload twice.

    all-gather / reduce-scatter / permute / all-to-all: exactly one of
    input/output is the full buffer (the other is the shard), so N is
    the LARGEST single shape anywhere on the line — summing would mix
    shard and full, and the operand-preference rule would undercount
    all-gather by n_dev (its operand is the shard)."""
    base, _ = _coll_base(op)
    if base == "all-reduce":
        after = rhs.split(op + "(", 1)[-1]
        b = shape_bytes(after)
        if b:
            return b
        before = rhs.split(op + "(", 1)[0]
        b = shape_bytes(before)
        return b // 2 if op.endswith("-start") else b
    best = 0
    for m in re.finditer(r"\w+\[[\d,]*\]", rhs):
        best = max(best, shape_bytes(m.group(0)))
    return best


def _coll_cost(rhs: str, op: str, n_dev: int,
               peak: device_peaks.Peak) -> float:
    """Wire time for one collective instruction over one ICI link (a
    ring direction rides one)."""
    base, _ = _coll_base(op)
    return (_wire_factor(base, n_dev) * _ring_bytes(rhs, op)
            / (peak.ici_bytes_per_s / peak.ici_links))


def entry_bounds(all_lines):
    """``(start, end)`` line positions of the entry computation, its
    ``ENTRY`` line to its closing zero-indent brace; the whole text where
    there is no ``ENTRY`` line."""
    entry_start = entry_end = None
    for i, ln in enumerate(all_lines):
        if entry_start is None:
            if "ENTRY" in ln:
                entry_start = i
        elif ln.rstrip() == "}":
            entry_end = i
            break
    if entry_start is None:
        return 0, len(all_lines)
    return entry_start, len(all_lines) if entry_end is None else entry_end


def topology_devices(name: str):
    """The devices of a TPU topology that is not there (``"v5e:2x2"``):
    libtpu builds it with no such hardware present, to compile ahead of
    time for it."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=name).devices


def measure(hlo: str, n_dev: int, peak: device_peaks.Peak):
    """Timeline simulation over the scheduled entry computation, costs
    weighted by ``peak`` (the fraction is dominated by the schedule
    structure, not the constants).

    In-flight async collectives accumulate hidden time as compute
    instructions execute (FIFO drain — concurrent rings roughly
    serialize on the shared ICI links, and a unit of compute time can
    hide at most one unit of total collective time, so no window ever
    double-credits the same instruction).  At ``all-reduce-done`` any
    remaining time is exposed (the program blocks on it).
    """
    # Bound the entry computation at its closing zero-indent brace —
    # HLO text does not guarantee ENTRY is the last computation, and
    # walking a trailing computation's instructions would contaminate
    # the schedule simulation.  Bounds are POSITIONS, not line text:
    # instruction names are only unique per computation, so a body line
    # can be byte-identical to an entry line.
    all_lines = hlo.splitlines()
    entry_start, entry_end = entry_bounds(all_lines)
    lines = [ln.strip()
             for ln in all_lines[entry_start:entry_end] if "=" in ln]
    in_flight: dict = {}   # start-instruction name -> remaining seconds
    total_coll = hidden = 0.0
    async_pairs = sync_ars = 0
    for ln in lines:
        lhs, rhs = ln.split("=", 1)
        op = opcode(rhs)
        if op is None:
            continue
        base, kind = _coll_base(op)
        if base in _COLLECTIVE_BASES:
            if kind == "-start":
                name = lhs.strip().lstrip("%")
                cost = _coll_cost(rhs, op, n_dev, peak)
                in_flight[name] = cost
                total_coll += cost
                async_pairs += 1
            elif kind == "-done":
                m = re.search(r"%([\w.\-]+)",
                              rhs.split(op + "(", 1)[-1])
                if m:
                    in_flight.pop(m.group(1), None)
            else:
                sync_ars += 1
                total_coll += _coll_cost(rhs, op, n_dev, peak)
        elif op in _COMPUTE_OPS and in_flight:
            rem = _inst_cost(rhs, peak)
            for k in list(in_flight):
                take = min(in_flight[k], rem)
                in_flight[k] -= take
                hidden += take
                rem -= take
                if in_flight[k] <= 0:
                    del in_flight[k]
                if rem <= 0:
                    break
    # Collectives inside non-entry computations (scan/while bodies,
    # fusion subcomputations) are invisible to the entry walk; report
    # the count so a capture where the gradient sync compiled into a
    # loop body reads as "incomplete" rather than silently measuring
    # only part of the traffic.
    non_entry = 0
    for i, ln in enumerate(all_lines):
        if entry_start <= i < entry_end:
            continue
        s = ln.strip()
        if "=" in s:
            op = opcode(s.split("=", 1)[1])
            if op:
                base, kind = _coll_base(op)
                if base in _COLLECTIVE_BASES and kind != "-done":
                    non_entry += 1
    return {
        "async_collective_pairs": async_pairs,
        "sync_collectives": sync_ars,
        "non_entry_collectives": non_entry,
        "total_collective_s_est": total_coll,
        "hidden_s_est": hidden,
        "overlap_fraction": (hidden / total_coll) if total_coll else None,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet",
                    choices=["resnet", "transformer"])
    ap.add_argument("--out", default=None,
                    help="also write the JSON result here")
    args = ap.parse_args()

    import jax

    from horovod_tpu.utils.platform import (
        accelerator_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    devices = accelerator_devices(cpu_by_name=True)
    platform = devices[0].platform
    n = min(8, len(devices))
    if n < 2:
        # One chip: compile ahead of time for an 8-chip topology of the
        # same kind (libtpu builds it with no such hardware present).
        devices = topology_devices("v5e:2x4")
        platform, n = devices[0].platform, 8
    peak = device_peaks.peak(
        REHEARSAL_KIND if platform == "cpu" else devices[0].device_kind)

    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import optimizer as opt_mod
    from horovod_tpu.parallel import train as train_mod

    mesh = mesh_mod.make_mesh({"dp": n}, devices=devices[:n])
    if args.model == "resnet":
        from horovod_tpu.models import resnet

        cfg = resnet.resnet50_config() if platform == "tpu" else \
            resnet.ResNetConfig(blocks=(1, 1, 1, 1), width=8,
                                num_classes=100,
                                compute_dtype=jnp.float32)
        size = 224 if platform == "tpu" else 32
        batch = 32 if platform == "tpu" else 8
        dist = opt_mod.DistributedOptimizer(
            optax.sgd(0.01, momentum=0.9), axis=("dp",))
        step, init = train_mod.make_resnet_train_step_hvd(cfg, mesh, dist)
        x = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32)
        y = jax.ShapeDtypeStruct((batch,), jnp.int32)
        state = jax.eval_shape(init, jax.random.PRNGKey(0))
        lowered = step.lower(state, x, y)
    else:
        from horovod_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(
            vocab_size=32768, d_model=1024, n_layers=8, n_heads=16,
            d_ff=4096, max_seq_len=1024, attn_impl="flash") \
            if platform == "tpu" else tfm.TransformerConfig(
                vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                d_ff=128, max_seq_len=64, compute_dtype=jnp.float32)
        batch, seq = (8, 1024) if platform == "tpu" else (8, 64)
        step, init = train_mod.make_transformer_train_step(cfg, mesh)
        toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        state = jax.eval_shape(init, jax.random.PRNGKey(0))
        lowered = step.lower(state, toks, toks)

    compiled = lowered.compile()
    hlo = compiled.as_text()
    result = {"model": args.model, "platform": platform,
              "device_kind": devices[0].device_kind, "n_dev": n,
              "weights_from": peak.source, **measure(hlo, n, peak)}
    if not result["async_collective_pairs"] and platform != "tpu":
        result["note"] = ("no async collective pairs in this platform's "
                          "schedule (CPU collectives are synchronous); "
                          "run on TPU for the real number")
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
