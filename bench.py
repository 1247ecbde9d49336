"""Benchmark: ResNet-50 synthetic images/sec — the reference's headline
metric (``examples/tensorflow2_synthetic_benchmark.py``: ResNet-50, batch
32, images/sec per device; we report the median over timed iterations
after warmup where the reference uses the mean).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...extras}.

Beyond the reference's images/sec, the line carries:

* ``flops_per_sec`` / ``mfu`` — achieved model FLOP/s from XLA's own cost
  analysis of the compiled train step (not a handount), and the fraction
  of the chip's peak bf16 throughput (``device_peaks.py``) that represents.
* ``allreduce_images_per_sec`` — the same step trained through
  ``DistributedOptimizer``/``grouped_allreduce`` over EVERY local chip,
  batch 32 per chip, so the framework's fused collective path is on the
  timed profile (the reference's benchmark always runs through
  ``hvd.DistributedOptimizer``,
  examples/tensorflow2_synthetic_benchmark.py:119-130).
* ``fp16_allreduce_images_per_sec`` — the ``--fp16-allreduce`` twin
  (Compression.fp16 on the gradient collectives).
* ``transformer_tokens_per_sec`` / ``transformer_mfu`` — the flagship
  decoder LM (Pallas flash attention), the model family the reference
  doesn't have.

``vs_baseline`` compares against the reference's only published per-device
throughput: 1656.82 images/sec on 16 Pascal GPUs (docs/benchmarks.rst:28-42)
= 103.55 images/sec/device — ResNet-101 there, ResNet-50 here, so the ratio
is indicative, not apples-to-apples; BASELINE.json publishes no ResNet-50
number.

No fallback: the run needs a TPU and exits non-zero without one.  A CPU
run is a rehearsal the caller asks for by name (``JAX_PLATFORMS=cpu``,
optionally ``XLA_FLAGS=--xla_force_host_platform_device_count=8``): it
uses tiny sizes, says ``platform: cpu`` and writes every number under a
``rehearsal_`` key, never under a device metric's name.  A section that
raises is recorded under its ``*_error`` key AND makes the exit code
non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

from device_peaks import peak as device_peak


def _timed_images_per_sec(step, state, images, labels, batch, iters,
                          batches_per_iter):
    import numpy as np

    img_secs = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(batches_per_iter):
            state, loss = step(state, images, labels)
        # Host readback as the timing fence: a device→host transfer of
        # the chain's final loss cannot complete before the chain has.
        float(np.asarray(loss).ravel()[0])
        dt = time.perf_counter() - t0
        img_secs.append(batch * batches_per_iter / dt)
    return float(np.median(img_secs)), state


def _transformer_model_flops(cfg, batch, seq):
    """Analytic model FLOPs per train step (fwd + 2x bwd, no remat).

    XLA's ``cost_analysis()`` counts a ``lax.scan`` body ONCE, so for
    the layer-scanned transformer it under-reports by ~n_layers and the
    resulting "MFU" is meaningless.  Standard MFU practice (PaLM appx B)
    counts matmul FLOPs analytically: per layer 4 attention projections
    (2·T·D²·4), a gated FFN (3 matmuls, 2·T·D·F·3), and the attention
    core (2 score/context matmuls, 2·2·H·B·S²·Dh), plus the vocab
    projection — times 3 for forward + backward.
    """
    assert not cfg.n_experts, (
        "analytic FLOP count assumes a dense FFN; MoE routes ~1 "
        "expert's FLOPs per token plus router/dispatch — extend the "
        "formula before benching an MoE config")
    T = batch * seq
    per_layer = (4 * 2 * T * cfg.d_model ** 2
                 + 3 * 2 * T * cfg.d_model * cfg.d_ff
                 + 2 * 2 * cfg.n_heads * batch * seq * seq * cfg.head_dim)
    fwd = cfg.n_layers * per_layer + 2 * T * cfg.d_model * cfg.vocab_size
    return 3.0 * fwd


def _step_flops(step, state, images, labels):
    """Model FLOPs per step from XLA's cost analysis of the compiled step."""
    compiled = step.lower(state, images, labels).compile()
    return float(compiled.cost_analysis()["flops"])


def _record_error(extras, key, exc):
    """A failed section is written into the line AND fails the run (main
    exits non-zero when any ``*_error`` key exists)."""
    traceback.print_exc()
    extras[key] = f"{type(exc).__name__}: {exc}"[:200]


def _fused_small_tensor_worker(iters: int, k: int, count: int) -> float:
    """Runs on every rank of an eager gang: k tiny fp32 tensors per step
    submitted async and synchronized together — the fusion-bound workload
    the persistent-sender/fusion-buffer data plane is built for
    (docs/performance.md).  Returns tensors/sec."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    xs = [np.random.RandomState(rank + i).randn(count).astype(np.float32)
          for i in range(k)]

    def one():
        hs = [hvd.allreduce_async(xs[i], op=hvd.Sum, name=f"small.{i}")
              for i in range(k)]
        for h in hs:
            hvd.synchronize(h)

    one()
    one()  # second warm pass lands on the response cache
    hvd.barrier()
    t0 = time.perf_counter()
    for _ in range(iters):
        one()
    dt = time.perf_counter() - t0
    return iters * k / dt


def _eager_allreduce_images_worker(iters: int, counts, batch: int) -> float:
    """Runs on every rank of an 8-way same-host eager gang: one "step"
    allreduces a fused gradient batch of ``counts`` fp32 tensors (the
    data-plane work a ``batch``-image training step would ship), so
    images/sec = iters * batch / elapsed.  The driver runs it twice —
    once with the shm intra-host transport on (the default for same-host
    peers) and once with ``HVD_SHM_DISABLE=1`` — so the pair isolates
    exactly the transport swap on an identical workload."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    xs = [np.random.RandomState(rank * 7 + i).randn(c).astype(np.float32)
          for i, c in enumerate(counts)]

    def one():
        hs = [hvd.allreduce_async(xs[i], op=hvd.Sum, name=f"grad.{i}")
              for i in range(len(xs))]
        for h in hs:
            hvd.synchronize(h)

    one()
    one()  # second warm pass lands on the response cache
    hvd.barrier()
    t0 = time.perf_counter()
    for _ in range(iters):
        one()
    dt = time.perf_counter() - t0
    return iters * batch / dt


def main() -> int:
    import jax

    from horovod_tpu.utils.platform import (
        accelerator_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    devices = accelerator_devices(cpu_by_name=True)
    # CPU devices only get past accelerator_devices when asked for by
    # name: that run is a rehearsal of this script at tiny sizes.
    rehearsal = devices[0].platform == "cpu"
    facts = {"platform": devices[0].platform,
             "device_kind": devices[0].device_kind,
             "device_count": len(devices)}
    print(f"bench: {facts}" + (" REHEARSAL (tiny sizes)" if rehearsal
                               else ""), file=sys.stderr, flush=True)
    peak_flops = None if rehearsal else \
        device_peak(devices[0].device_kind).bf16_flops

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import resnet
    from horovod_tpu.ops.compression import Compression
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import optimizer as opt_mod
    from horovod_tpu.parallel import train as train_mod

    n_dev = len(devices)
    # Dispatch-amortized chain protocol, shared by the b32 "steady" and
    # b128 sections — they MUST stay identical or the cross-batch
    # comparison breaks (10- vs 50-step chains once made b128 read below
    # b32).
    if rehearsal:
        cfg = resnet.ResNetConfig(blocks=(1, 1, 1, 1), width=8,
                                  num_classes=100,
                                  compute_dtype=jnp.float32)
        size, batch, big = 32, 8, 16
        warmup_iters, iters, batches_per_iter = 1, 3, 2
        steady_iters, steady_chain = 2, 3
    else:
        cfg = resnet.resnet50_config()
        size, batch, big = 224, 32, 128
        warmup_iters, iters, batches_per_iter = 3, 10, 10
        steady_iters, steady_chain = 5, 50

    rs = np.random.RandomState(0)

    def synthetic_batch(mesh, n):
        """``n`` images and labels placed with the step's dp sharding:
        each chip receives its own shard from the host, nothing is
        staged through device 0."""
        sh = NamedSharding(mesh, P("dp"))
        x = rs.rand(n, size, size, 3).astype(np.float32)
        y = rs.randint(0, cfg.num_classes, (n,)).astype(np.int32)
        return jax.device_put(x, sh), jax.device_put(y, sh)

    mesh1 = mesh_mod.make_mesh({"dp": 1}, devices=devices[:1])
    images, labels = synthetic_batch(mesh1, batch)

    # --- headline: plain single-device step ------------------------------
    step, init = train_mod.make_resnet_train_step(
        cfg, mesh1, optax.sgd(0.01, momentum=0.9))
    state = init(jax.random.PRNGKey(0))
    for _ in range(warmup_iters):
        state, loss = step(state, images, labels)
    jax.block_until_ready(loss)
    flops = _step_flops(step, state, images, labels)
    value, state = _timed_images_per_sec(
        step, state, images, labels, batch, iters, batches_per_iter)

    extras = {}
    achieved = flops * value / batch  # steps/sec × flops/step
    extras["flops_per_sec"] = round(achieved, 1)
    extras["step_flops"] = round(flops, 1)
    if peak_flops:
        extras["mfu"] = round(achieved / peak_flops, 4)

    # --- dispatch-amortized variants: (a) a 50-step chain and (b) a
    # jit-fused lax.scan of 10 steps (one dispatch per iteration — the
    # XLA-native training-loop shape), beside the reference's 10-batch
    # protocol.
    try:
        v50, state = _timed_images_per_sec(
            step, state, images, labels, batch, steady_iters,
            steady_chain)
        extras["steady_images_per_sec"] = round(v50, 2)

        import jax.lax as lax

        def scan10(state, images, labels):
            def body(s, _):
                s, l = step(s, images, labels)
                return s, l
            state, losses = lax.scan(body, state, None, length=10)
            return state, losses[-1]

        scan_step = jax.jit(scan10, donate_argnums=(0,))
        for _ in range(2):
            state, sloss = scan_step(state, images, labels)
        float(np.asarray(sloss).ravel()[0])
        vscan, state = _timed_images_per_sec(
            scan_step, state, images, labels, batch * 10, steady_iters,
            3)
        extras["scan_fused_images_per_sec"] = round(vscan, 2)
        if peak_flops:
            extras["steady_mfu"] = round(
                flops * max(v50, vscan) / batch / peak_flops, 4)
    except Exception as e:
        _record_error(extras, "steady_error", e)

    # --- large-batch variant: batch 128 (the reference pins batch 32 for
    # comparability; the chip's MXU utilization peaks at larger batches,
    # so report the bigger number alongside, not instead).  Measured with
    # the SAME dispatch-amortized 50-step-chain protocol as
    # ``steady_images_per_sec``.
    try:
        # Free the b32 programs + state first: two resident ResNet-50
        # train programs at 224px would overlap peak memory.  The
        # scan10 closure captures ``step``, so it must go too or the
        # name-level del frees nothing.
        scan_step = scan10 = None
        del step, state
        big_images, big_labels = synthetic_batch(mesh1, big)
        bstep, binit = train_mod.make_resnet_train_step(
            cfg, mesh1, optax.sgd(0.01, momentum=0.9))
        bstate = binit(jax.random.PRNGKey(0))
        bflops = _step_flops(bstep, bstate, big_images, big_labels)
        for _ in range(warmup_iters):
            bstate, bloss = bstep(bstate, big_images, big_labels)
        jax.block_until_ready(bloss)
        bval, bstate = _timed_images_per_sec(
            bstep, bstate, big_images, big_labels, big, steady_iters,
            steady_chain)
        extras["batch128_images_per_sec"] = round(bval, 2)
        if peak_flops:
            extras["batch128_mfu"] = round(
                bflops * bval / big / peak_flops, 4)
        del bstep, bstate, big_images, big_labels
    except Exception as e:
        _record_error(extras, "batch128_error", e)

    # --- collective path: DistributedOptimizer → grouped_allreduce -------
    # Over EVERY local chip, ``batch`` images per chip (the reference's
    # per-device batch); the rate is the total over the mesh.  On one
    # chip the dp axis is 1 and the collective lowers to the identity
    # (``allreduce_ndev`` says which it was).
    mesh_all = mesh_mod.make_mesh({"dp": n_dev}, devices=devices)

    def bench_hvd_step(compression):
        dist_opt = opt_mod.DistributedOptimizer(
            optax.sgd(0.01, momentum=0.9), axis=("dp",),
            compression=compression)
        step_h, init_h = train_mod.make_resnet_train_step_hvd(
            cfg, mesh_all, dist_opt)
        state_h = init_h(jax.random.PRNGKey(0))
        images_h, labels_h = synthetic_batch(mesh_all, batch * n_dev)
        for _ in range(warmup_iters):
            state_h, loss_h = step_h(state_h, images_h, labels_h)
        jax.block_until_ready(loss_h)
        v, _ = _timed_images_per_sec(
            step_h, state_h, images_h, labels_h, batch * n_dev, iters,
            batches_per_iter)
        return v

    try:
        extras["allreduce_images_per_sec"] = round(
            bench_hvd_step(Compression.none), 2)
        extras["allreduce_ndev"] = n_dev
        extras["fp16_allreduce_images_per_sec"] = round(
            bench_hvd_step(Compression.fp16), 2)
    except Exception as e:
        _record_error(extras, "variant_error", e)

    # --- flagship transformer LM: tokens/sec + MFU ----------------------
    # The framework's flagship model family (beyond the reference, which
    # is CNN-only): decoder LM with the Pallas flash-attention kernel,
    # data-parallel over every local chip (8 sequences per chip).  bf16,
    # MXU-sized matmuls.
    try:
        from horovod_tpu.models import transformer as tfm

        if rehearsal:
            tcfg = tfm.TransformerConfig(
                vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                d_ff=128, max_seq_len=64, compute_dtype=jnp.float32,
                attn_impl="flash")
            tbatch, tseq, titers = 2 * n_dev, 64, 2
        else:
            tcfg = tfm.TransformerConfig(
                vocab_size=32768, d_model=1024, n_layers=8, n_heads=16,
                d_ff=4096, max_seq_len=1024, attn_impl="flash")
            tbatch, tseq, titers = 8 * n_dev, 1024, 5
        tstep, tinit = train_mod.make_transformer_train_step(tcfg, mesh_all)
        tstate = tinit(jax.random.PRNGKey(0))
        toks_np = rs.randint(0, tcfg.vocab_size,
                             (tbatch, tseq)).astype(np.int32)
        tsh = NamedSharding(mesh_all, P("dp"))
        toks = jax.device_put(toks_np, tsh)
        tgts = jax.device_put(np.roll(toks_np, -1, axis=1), tsh)
        # Analytic, NOT cost_analysis: XLA counts the layer scan once
        # (see _transformer_model_flops).
        tflops = _transformer_model_flops(tcfg, tbatch, tseq)
        for _ in range(warmup_iters):
            tstate, tloss = tstep(tstate, toks, tgts)
        float(np.asarray(tloss).ravel()[0])
        tok_rate, tstate = _timed_images_per_sec(
            tstep, tstate, toks, tgts, tbatch * tseq, titers,
            batches_per_iter)
        extras["transformer_tokens_per_sec"] = round(tok_rate, 1)
        extras["transformer_ndev"] = n_dev
        t_achieved = tflops * tok_rate / (tbatch * tseq)
        extras["transformer_flops_per_sec"] = round(t_achieved, 1)
        if peak_flops:
            extras["transformer_mfu"] = round(
                t_achieved / (n_dev * peak_flops), 4)
        del tstep, tstate
    except Exception as e:
        _record_error(extras, "transformer_error", e)

    # --- decode: KV-cache generation throughput -------------------------
    # The flagship LM's inference path (models/transformer.generate):
    # tokens/sec for greedy decode from a short prompt.
    try:
        from horovod_tpu.models import transformer as tfm2

        if rehearsal:
            gcfg = tfm2.TransformerConfig(
                vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                d_ff=128, max_seq_len=64, compute_dtype=jnp.float32)
            gbatch, gnew = 2, 16
        else:
            gcfg = tfm2.TransformerConfig(
                vocab_size=32768, d_model=1024, n_layers=8, n_heads=16,
                d_ff=4096, max_seq_len=512)
            gbatch, gnew = 8, 128
        gparams = jax.jit(lambda k: tfm2.init(k, gcfg))(
            jax.random.PRNGKey(0))
        gprompt = jnp.asarray(
            rs.randint(0, gcfg.vocab_size, (gbatch, 16)), jnp.int32)
        gen = jax.jit(lambda p, t: tfm2.generate(
            p, t, gcfg, max_new_tokens=gnew))
        out = gen(gparams, gprompt)
        float(np.asarray(out[0, -1]))  # warmup + fence
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = gen(gparams, gprompt)
            float(np.asarray(out[0, -1]))
            rates.append(gbatch * gnew / (time.perf_counter() - t0))
        # Median of 3; note the window includes the (short) prefill, so
        # this slightly understates pure per-token decode rate.
        extras["decode_tokens_per_sec"] = round(float(np.median(rates)), 1)
    except Exception as e:
        _record_error(extras, "decode_error", e)

    # --- decode per-token latency: the serving step -----------------------
    # Percentiles of a single batched decode_step (serving/decode.py) —
    # the latency a served token actually pays, where the throughput
    # number above amortizes prefill over the whole generation.
    try:
        from horovod_tpu.serving.decode import DecodeEngine

        deng = DecodeEngine(gparams, gcfg, max_batch=gbatch,
                            cache_len=gcfg.max_seq_len)
        for slot in range(gbatch):
            deng.prefill(slot, [1 + slot, 7, 11, 13])
        for _ in range(3):
            deng.step()  # warmup (np.asarray inside fences the device)
        lats = []
        for _ in range(40):
            t0 = time.perf_counter()
            deng.step()
            lats.append((time.perf_counter() - t0) * 1e3)
        from horovod_tpu.telemetry.registry import quantile as _quantile

        for q in (50, 90, 99):
            extras[f"decode_token_latency_p{q}_ms"] = round(
                _quantile(lats, q / 100.0), 3)
    except Exception as e:
        _record_error(extras, "decode_latency_error", e)

    # --- serving: closed-loop clients vs the in-process loop --------------
    # The full serving stack — FrontDoor HTTP, bounded-queue scheduler,
    # continuous-batching ServingLoop — single-rank in this process,
    # measured the way an SLO is: concurrent closed-loop clients, wall
    # time per request (docs/serving.md).
    try:
        import http.client
        import threading as _th

        from horovod_tpu.serving import ServingLoop

        ready = _th.Event()
        box = {}

        def _on_ready(port):
            box["port"] = port
            ready.set()

        sloop = ServingLoop(gparams, gcfg, port=0, max_batch=4,
                            max_queue=64, cache_len=gcfg.max_seq_len,
                            host="127.0.0.1", on_ready=_on_ready)
        sthread = _th.Thread(target=sloop.run, daemon=True)
        sthread.start()
        if not ready.wait(120):
            raise TimeoutError("serving loop never came up")
        n_clients, reqs_each, snew = 3, 5, 16
        lat_ms, ttft_ms = [], []
        lk = _th.Lock()

        def _client(ci):
            for j in range(reqs_each):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", box["port"], timeout=120)
                t0 = time.perf_counter()
                conn.request("POST", "/generate", json.dumps(
                    {"prompt": [1 + 7 * ci + j, 5, 9],
                     "max_new_tokens": snew}))
                body = json.loads(conn.getresponse().read())
                dt_ms = (time.perf_counter() - t0) * 1e3
                conn.close()
                with lk:
                    lat_ms.append(dt_ms)
                    if body.get("ttft_ms") is not None:
                        ttft_ms.append(body["ttft_ms"])

        cts = [_th.Thread(target=_client, args=(ci,))
               for ci in range(n_clients)]
        t0 = time.perf_counter()
        for t in cts:
            t.start()
        for t in cts:
            t.join()
        wall = time.perf_counter() - t0
        sloop.stop()
        sthread.join(30)
        extras["serve_tokens_per_sec"] = round(
            n_clients * reqs_each * snew / wall, 1)
        from horovod_tpu.telemetry.registry import quantile as _quantile

        extras["serve_ttft_p50_ms"] = round(
            _quantile(ttft_ms, 0.50), 2)
        extras["serve_p99_ms"] = round(
            _quantile(lat_ms, 0.99), 2)
    except Exception as e:
        _record_error(extras, "serve_bench_error", e)

    # --- eager data plane: fused-small-tensor rate ----------------------
    # A real 2-rank Python-engine gang over the host TCP mesh (run-func
    # mode — same launch path as examples/engine_benchmark.py), timing
    # 64 tiny tensors per step through the persistent-sender /
    # fusion-buffer path (docs/performance.md).  In-graph metrics above
    # never touch that plane.
    try:
        from horovod_tpu.runner.run import run as hvd_run

        per_rank = hvd_run(
            _fused_small_tensor_worker, (20, 64, 1024), np=2,
            env={"HVD_TPU_CORE": "py", "JAX_PLATFORMS": "cpu"})
        extras["allreduce_fused_small_tensors_per_sec"] = round(
            per_rank[0], 1)
    except Exception as e:
        _record_error(extras, "fused_small_error", e)

    # --- eager 8-way transport shoot-out: shm rings vs loopback TCP -----
    # Same workload, same gang shape, only the intra-host transport
    # differs: an 8-rank same-host gang pairs over seqlock'd /dev/shm
    # rings by default; HVD_SHM_DISABLE=1 pins the seed's loopback-TCP
    # path.  4x 1 MiB fp32 tensors per step is a ResNet-scale fused
    # gradient batch, large enough that transport bandwidth (not Python
    # dispatch) dominates.
    try:
        from horovod_tpu.runner.run import run as hvd_run

        counts, tr_iters, tr_batch = [1 << 18] * 4, 10, 32
        tr_env = {"HVD_TPU_CORE": "py", "JAX_PLATFORMS": "cpu"}
        shm_rates = hvd_run(
            _eager_allreduce_images_worker, (tr_iters, counts, tr_batch),
            np=8, env=tr_env)
        extras["allreduce_shm_images_per_sec"] = round(shm_rates[0], 2)
        tcp_rates = hvd_run(
            _eager_allreduce_images_worker, (tr_iters, counts, tr_batch),
            np=8, env={**tr_env, "HVD_SHM_DISABLE": "1"})
        extras["allreduce_tcp_images_per_sec"] = round(tcp_rates[0], 2)
    except Exception as e:
        _record_error(extras, "transport_bench_error", e)

    # --- gang-wide tracing: phase-attributed eager allreduce ------------
    # The same fused-gradient workload once more with HVD_TRACE=1: every
    # rank streams spans, tools/hvd_trace.py reduces them to mean
    # ms-per-collective per phase, and the block rides the snapshot so
    # tools/check_bench_regression.py can name the phase that moved when
    # the throughput gate trips (docs/timeline.md "Gang-wide tracing").
    try:
        import tempfile

        from horovod_tpu.runner.run import run as hvd_run

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import hvd_trace

        tr_counts, tr_iters, tr_batch = [1 << 18] * 4, 10, 32
        with tempfile.TemporaryDirectory(prefix="hvd-bench-trace-") as td:
            hvd_run(_eager_allreduce_images_worker,
                    (tr_iters, tr_counts, tr_batch), np=8,
                    env={"HVD_TPU_CORE": "py", "JAX_PLATFORMS": "cpu",
                         "HVD_TRACE": "1", "HVD_TRACE_DIR": td})
            rep = hvd_trace.analyze_dir(td)
        if rep is not None:
            extras["phase_breakdown"] = rep["phase_breakdown_ms"]
            extras["trace_num_collectives"] = rep["num_collectives"]
    except Exception as e:
        _record_error(extras, "trace_bench_error", e)

    # --- gang aggregation cost: one fold over an 8-rank gang ------------
    # The coordinator-side GangAggregator fold (telemetry/aggregate.py)
    # runs every HVD_AGG_INTERVAL on rank 0 next to training, so its
    # cost is itself a gated number: 8 synthetic per-rank snapshots with
    # realistic histogram/counter density, folded repeatedly; headline
    # ``gang_agg_fold_p50_us`` is the median fold wall time
    # (one-sided gate in tools/check_bench_regression.py).
    try:
        from horovod_tpu.telemetry import aggregate as _agg_mod
        from horovod_tpu.telemetry import registry as _reg_mod

        agg_snaps = {}
        for r in range(8):
            reg = _reg_mod.Registry()
            for i in range(200):
                reg.observe("hvd_collective_latency_seconds",
                            0.001 * (1 + (i + r) % 7),
                            labels=("allreduce", "float32"))
                reg.observe("hvd_ring_hop_seconds",
                            0.0005 * (1 + (i * (r + 1)) % 5),
                            labels=("recv",))
            reg.inc_counter("hvd_collectives_total", 200,
                            labels=("allreduce", "float32"))
            reg.inc_counter("hvd_transport_bytes_total", 1 << 24,
                            labels=("shm",))
            reg.set_gauge("hvd_queue_depth", r)
            agg_snaps[r] = {"rank": r, **reg.snapshot()}
        fold_us = []
        for _ in range(50):
            t0 = time.perf_counter()
            _agg_mod.fold(agg_snaps)
            fold_us.append((time.perf_counter() - t0) * 1e6)
        extras["gang_agg_fold_p50_us"] = round(
            _reg_mod.quantile(fold_us, 0.5), 1)
    except Exception as e:
        _record_error(extras, "agg_bench_error", e)

    # --- control-plane scale: coordination-cycle latency vs ranks -------
    # 8/64/256 in-process ranks over socketpairs (horovod_tpu/ctrl_sim),
    # flat star vs the hierarchical per-host sub-coordinator tree
    # (docs/fault_tolerance.md).  Headline ``coordination_cycle_p50_us``
    # is the tree's p50 at 256 ranks — the proof point the regression
    # gate watches; the per-size/per-mode keys carry the full curve.
    try:
        from horovod_tpu import ctrl_sim

        curve = ctrl_sim.run_curve()
        extras.update(curve)
    except Exception as e:
        _record_error(extras, "ctrl_sim_error", e)

    failed = sorted(k for k in extras if k.endswith("_error"))
    baseline = 1656.82 / 16.0  # reference's per-device number
    if rehearsal:
        # A CPU timing never appears under a device metric's name.
        line = {"metric": "rehearsal_tiny_resnet_images_per_sec",
                "value": round(value, 2), "unit": "images/sec", **facts,
                **{f"rehearsal_{k}": v for k, v in extras.items()}}
    else:
        line = {"metric": "resnet50_synthetic_images_per_sec_per_chip",
                "value": round(value, 2), "unit": "images/sec",
                "vs_baseline": round(value / baseline, 3), **facts,
                **extras}
    print(json.dumps(line))
    if failed:
        print(f"bench: FAILED sections: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
