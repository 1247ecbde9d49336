"""ResNet-50 synthetic benchmark, JAX/TPU edition.

Parity: ``examples/tensorflow2_synthetic_benchmark.py`` in the
reference (same defaults: ResNet-50, batch 32, 10 warmup batches, 10
iters of 10 batches; same --fp16-allreduce toggle; same img/sec ± CI
output format).  Two modes:

* default (single process): data-parallel over every local device with
  the in-graph XLA collective path — the TPU performance regime.
* under ``hvdrun -np N`` (HVD_SIZE > 1): classic Horovod regime — one
  process per device (``hvdrun`` pins one chip per local rank), eager
  gradient allreduce through the coordination engine.
"""

from __future__ import annotations

import os
import sys

# Runnable straight from a checkout: put the repo root on sys.path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(
        description="JAX synthetic benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "resnet152",
                            "resnet18", "tiny"])
    p.add_argument("--batch-size", type=int, default=32,
                   help="input batch size per device")
    p.add_argument("--num-warmup-batches", type=int, default=10)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--fp16-allreduce", action="store_true",
                   help="compress gradient allreduce to 16 bit")
    p.add_argument("--bridge", action="store_true",
                   help="multi-process mode: jit the WHOLE train step; "
                        "the gradient reduction rides the engine via "
                        "the host-callback bridge (ops/bridge.py) "
                        "instead of eager op-by-op dispatch")
    p.add_argument("--image-size", type=int, default=0,
                   help="override input resolution (0 = 224, or 32 for "
                        "--model tiny)")
    return p.parse_args()


def build_model(args):
    import jax.numpy as jnp

    from horovod_tpu.models import resnet

    if args.model == "tiny":
        cfg = resnet.ResNetConfig(blocks=(1, 1, 1, 1), width=8,
                                  num_classes=100,
                                  compute_dtype=jnp.float32)
        size = args.image_size or 32
    else:
        cfg = {"resnet50": resnet.resnet50_config,
               "resnet101": resnet.resnet101_config,
               "resnet152": resnet.resnet152_config,
               "resnet18": resnet.resnet18_config}[args.model]()
        size = args.image_size or 224
    return cfg, size


def log(rank, msg):
    if rank == 0:
        print(msg, flush=True)


def run_ingraph(args):
    """Single process, all local devices, in-graph collectives."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import train as train_mod
    from horovod_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    cfg, size = build_model(args)
    devices = jax.devices()
    mesh = mesh_mod.make_mesh({"dp": len(devices)})
    if args.fp16_allreduce:
        # In-graph mode computes in bfloat16 already (the model's
        # compute_dtype), so the gradient collective is 16-bit natively;
        # the flag matters for the eager (multi-process) mode below.
        log(0, "--fp16-allreduce: in-graph gradients already ride the "
               "ICI in bfloat16 (model compute dtype)")
    step, init = train_mod.make_resnet_train_step(
        cfg, mesh, optax.sgd(0.01, momentum=0.9))
    state = init(jax.random.PRNGKey(0))

    n = len(devices)
    rs = np.random.RandomState(0)
    # Each device gets its shard of the batch straight from the host
    # (the step's own dp sharding), not a copy staged through device 0.
    batch_sharding = NamedSharding(mesh, P("dp"))
    images = jax.device_put(
        rs.rand(args.batch_size * n, size, size, 3).astype(np.float32),
        batch_sharding)
    labels = jax.device_put(
        rs.randint(0, cfg.num_classes,
                   (args.batch_size * n,)).astype(np.int32),
        batch_sharding)

    log(0, f"Model: {args.model}  Batch size: {args.batch_size} "
           f"x {n} device(s) ({devices[0].platform}, "
           f"{devices[0].device_kind}), in-graph mode")
    for _ in range(args.num_warmup_batches):
        state, loss = step(state, images, labels)
    jax.block_until_ready(loss)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            state, loss = step(state, images, labels)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        rate = args.batch_size * n * args.num_batches_per_iter / dt
        log(0, f"Iter #{i}: {rate:.1f} img/sec total")
        img_secs.append(rate / n)
    report(img_secs, n, 0)


def run_eager(args):
    """N processes under hvdrun, eager allreduce (classic regime)."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.models import resnet
    from horovod_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    rank, nproc = hvd.rank(), hvd.size()
    # Every rank, not only rank 0: under hvdrun each must own a different
    # chip.  A pinned rank sees its chip as device 0 of a one-chip
    # topology, so the chip is named by the pin, not by the device id.
    print(f"rank {rank}/{nproc}: TPU_VISIBLE_CHIPS="
          f"{os.environ.get('TPU_VISIBLE_CHIPS', 'unset')} "
          f"jax.devices() = {jax.devices()}", flush=True)
    cfg, size = build_model(args)

    # One jitted program, not hundreds of op-by-op compiles.
    params, bstats = jax.jit(lambda k: resnet.init(k, cfg))(
        jax.random.PRNGKey(0))
    params = hvd.broadcast_parameters(params, root_rank=0)

    grad_fn = jax.jit(jax.grad(
        lambda p, b, x, y: resnet.loss_fn(p, b, x, y, cfg)[0]))

    rs = np.random.RandomState(rank)
    images = jnp.asarray(rs.rand(args.batch_size, size, size, 3),
                         jnp.float32)
    labels = jnp.asarray(rs.randint(0, cfg.num_classes, (args.batch_size,)))
    compression = hvd.Compression.fp16 if args.fp16_allreduce \
        else hvd.Compression.none

    def one_batch(params):
        grads = grad_fn(params, bstats, images, labels)
        # axis=None selects the engine (multi-process) allreduce path;
        # under jit the sync ops dispatch through the bridge.
        grads = hvd.allreduce_gradients(grads, axis=None,
                                        compression=compression)
        return jax.tree.map(lambda p, g: p - 0.01 * g, params, grads)

    if args.bridge:
        # Whole-step jit: XLA fuses grad + update, and the reduction
        # enters the engine via one ordered host callback (fusion,
        # cache, timeline on the compiled path).
        one_batch = jax.jit(one_batch)

    log(rank, f"Model: {args.model}  Batch size: {args.batch_size} "
              f"x {nproc} process(es), "
              f"{'bridge (jitted step)' if args.bridge else 'eager'} mode")
    for _ in range(args.num_warmup_batches):
        params = one_batch(params)
    jax.block_until_ready(params)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params = one_batch(params)
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        rate = args.batch_size * args.num_batches_per_iter / dt
        log(rank, f"Iter #{i}: {rate * nproc:.1f} img/sec total")
        img_secs.append(rate)
    report(img_secs, nproc, rank)
    hvd.shutdown()


def report(img_secs, n_devices, rank):
    # Output format parity: tensorflow2_synthetic_benchmark.py:119-130.
    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    if rank == 0:
        print(f"Img/sec per device: {img_sec_mean:.1f} "
              f"+-{img_sec_conf:.1f}")
        print(f"Total img/sec on {n_devices} device(s): "
              f"{n_devices * img_sec_mean:.1f} "
              f"+-{n_devices * img_sec_conf:.1f}")


def main():
    args = parse_args()
    if int(os.environ.get("HVD_SIZE", "1")) > 1:
        run_eager(args)
    else:
        run_ingraph(args)


if __name__ == "__main__":
    main()
