"""Weak-scaling efficiency benchmark.

Parity: the reference's headline claim is scaling efficiency on 512 GPUs
(README.rst:74-77, docs/benchmarks.rst:8-13 — throughput at N devices /
(N x throughput at 1 device)).  This harness measures the same quantity
over a ``jax.sharding.Mesh``: per-device batch held constant, data
parallelism widened over the device list, gradient reduction through the
framework's ``DistributedOptimizer`` (fused in-graph allreduce).

On a TPU pod, run under the pod launcher and the mesh spans real chips
over ICI; on a dev box, set
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
to validate the mechanics on virtual devices (the numbers then reflect
host contention, not ICI).

    python examples/scaling_benchmark.py --devices 1,2,4,8 --model tiny
"""

from __future__ import annotations

import os
import sys

# Runnable straight from a checkout: put the repo root on sys.path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(
        description="Weak-scaling efficiency benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "tiny"])
    p.add_argument("--batch-per-device", type=int, default=32)
    p.add_argument("--devices", default="",
                   help="comma-separated device counts (default: "
                        "1,2,4,... up to every available device)")
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import resnet
    from horovod_tpu.ops.compression import Compression
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import optimizer as opt_mod
    from horovod_tpu.parallel import train as train_mod
    from horovod_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    all_devices = jax.devices()
    if args.devices:
        counts = [int(c) for c in args.devices.split(",")]
    else:
        counts, c = [], 1
        while c <= len(all_devices):
            counts.append(c)
            c *= 2
    if max(counts) > len(all_devices):
        raise SystemExit(f"asked for {max(counts)} devices, "
                         f"have {len(all_devices)}")

    if args.model == "tiny":
        cfg = resnet.ResNetConfig(blocks=(1, 1, 1, 1), width=8,
                                  num_classes=100,
                                  compute_dtype=jnp.float32)
        size = 32
    else:
        cfg = {"resnet50": resnet.resnet50_config,
               "resnet101": resnet.resnet101_config}[args.model]()
        size = 224

    compression = (Compression.fp16 if args.fp16_allreduce
                   else Compression.none)
    rs = np.random.RandomState(0)
    results = {}
    for n in counts:
        mesh = mesh_mod.make_mesh({"dp": n}, devices=all_devices[:n])
        opt = opt_mod.DistributedOptimizer(
            optax.sgd(0.01, momentum=0.9), axis=("dp",),
            compression=compression)
        step, init = train_mod.make_resnet_train_step_hvd(cfg, mesh, opt)
        state = init(jax.random.PRNGKey(0))
        batch = args.batch_per_device * n
        # Shards go from the host straight to their devices (the step's
        # dp sharding), not through device 0.
        batch_sharding = NamedSharding(mesh, P("dp"))
        images = jax.device_put(
            rs.rand(batch, size, size, 3).astype(np.float32),
            batch_sharding)
        labels = jax.device_put(
            rs.randint(0, cfg.num_classes, (batch,)).astype(np.int32),
            batch_sharding)
        for _ in range(args.num_warmup_batches):
            state, _loss = step(state, images, labels)
        jax.block_until_ready(state)
        rates = []
        for _ in range(args.num_iters):
            t0 = time.perf_counter()
            for _ in range(args.num_batches_per_iter):
                state, _loss = step(state, images, labels)
            jax.block_until_ready(state)
            dt = time.perf_counter() - t0
            rates.append(batch * args.num_batches_per_iter / dt)
        results[n] = float(np.mean(rates))
        print(f"{n:4d} device(s): {results[n]:10.1f} img/sec total, "
              f"{results[n] / n:10.1f} img/sec/device")

    base = counts[0]
    table = {}
    for n in counts:
        eff = results[n] / (results[base] * n / base)
        table[n] = round(eff, 4)
        print(f"scaling efficiency {base}->{n}: {eff * 100:.1f}%")
    print(json.dumps({
        "platform": all_devices[0].platform,
        "device_kind": all_devices[0].device_kind,
        "metric": "weak_scaling_efficiency",
        "value": table[counts[-1]],
        "unit": f"fraction_of_linear_{base}to{counts[-1]}",
        "per_count": table,
        "img_per_sec": {str(k): round(v, 1) for k, v in results.items()},
    }))


if __name__ == "__main__":
    main()
