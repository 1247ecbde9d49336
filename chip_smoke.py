"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process that owns every local chip (one on the driver's machine, four
on the four-chip host: same script) drives the main path once through the
entry points a user would call, at the full width of models the repo
supports, with random weights made from a seed:

* ``resnet_hvd``  trainer, Horovod contract: ResNet-50 at 224², 32 images
  per chip, ``DistributedOptimizer`` -> ``make_resnet_train_step_hvd``.
* ``lm_flash``    trainer, kernel: the d_model 1024 x 8 layer LM with the
  Pallas flash-attention kernel through ``make_transformer_train_step``,
  plus the kernel against dense attention on the chip.
* ``serve``       server: ``ServingLoop`` behind its real ``FrontDoor``,
  three ``POST /generate`` requests over HTTP, one of them checked token
  for token against ``transformer.generate``.

Any exception, non-finite value or failed check in any leg makes the exit
code non-zero.  Without a TPU it exits non-zero and prints no result: there
is no CPU mode.  It starts no child process.  The last line of standard
output of a passing run is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Per leg it prints the compile seconds and the run seconds apart.  They are
set-up facts (is the compile cache warm? does the path finish?), not rates.
"""

from __future__ import annotations

import json
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, NamedTuple, Tuple

# |flash - dense| <= FLASH_TOL * max|dense|, forward and each of dq/dk/dv.
# bf16 keeps 8 significant bits (one rounding is 2^-9 = 0.2 % of a value);
# the kernel rounds p and ds to bf16 before their matmuls and its outputs
# once more, against a float32 reference at full matmul precision.
FLASH_TOL = 2e-2


class Sizes(NamedTuple):
    """What the legs run at.  ``FULL`` is what the chip runs; a CPU
    rehearsal (tests/test_chip_smoke.py) passes tiny ones."""
    resnet: Callable[[], Any]       # () -> ResNetConfig
    image: int
    images_per_chip: int
    lm: Dict[str, Any]              # TransformerConfig fields, train leg
    lm_seqs_per_chip: int
    lm_seq: int
    attn_shape: Tuple[int, int, int, int]   # B, S, H, D of the kernel check
    serve: Dict[str, Any]           # TransformerConfig fields, server leg
    serve_requests: Tuple[Tuple[int, int], ...]  # (prompt len, new tokens)


def _full_sizes() -> Sizes:
    from horovod_tpu.models import resnet

    lm = dict(vocab_size=32768, d_model=1024, n_layers=8, n_heads=16,
              d_ff=4096)
    return Sizes(
        resnet=resnet.resnet50_config, image=224, images_per_chip=32,
        lm=dict(lm, max_seq_len=1024, attn_impl="flash"),
        lm_seqs_per_chip=8, lm_seq=1024, attn_shape=(8, 1024, 16, 64),
        serve=dict(lm, max_seq_len=512),
        serve_requests=((3, 16), (9, 12), (17, 8)))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _losses_ok(losses: List[float], what: str) -> None:
    import math

    _check(all(math.isfinite(x) for x in losses),
           f"{what}: non-finite loss in {losses}")
    _check(losses[-1] < losses[0],
           f"{what}: loss did not fall over {len(losses)} steps: {losses}")


def _run_steps(compiled, state, batch, n_steps: int):
    """``n_steps`` of a compiled train step on one fixed batch; returns
    (state, losses, seconds) with the device fenced inside the window."""
    import jax

    losses = []
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, loss = compiled(state, *batch)
        losses.append(loss)
    losses = [float(x) for x in jax.device_get(losses)]
    return state, losses, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# leg 1: trainer, Horovod contract
# ---------------------------------------------------------------------------


def leg_resnet_hvd(devices, sizes: Sizes) -> Dict[str, Any]:
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import optimizer as opt_mod
    from horovod_tpu.parallel import train as train_mod

    n = len(devices)
    cfg = sizes.resnet()
    mesh = mesh_mod.make_mesh({"dp": n}, devices=devices)
    dist = opt_mod.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), axis=("dp",))
    step, init = train_mod.make_resnet_train_step_hvd(cfg, mesh, dist)

    # The batch goes from the host to each chip's own shard (the step's dp
    # sharding); it is never committed to device 0 first.
    rs = np.random.RandomState(0)
    batch_sharding = NamedSharding(mesh, P("dp"))
    per = sizes.images_per_chip
    images_np = rs.rand(per * n, sizes.image, sizes.image,
                        3).astype(np.float32)
    labels_np = rs.randint(0, cfg.num_classes, (per * n,)).astype(np.int32)
    images = jax.device_put(images_np, batch_sharding)
    labels = jax.device_put(labels_np, batch_sharding)
    shards = sorted(images.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    _check({s.device for s in shards} == set(devices),
           "batch shards do not cover every device")
    for i, s in enumerate(shards):
        _check(np.array_equal(np.asarray(s.data),
                              images_np[i * per:(i + 1) * per]),
               f"device {s.device} does not hold batch rows "
               f"{i * per}..{(i + 1) * per}")
    _check(all(not np.array_equal(np.asarray(a.data), np.asarray(b.data))
               for a, b in zip(shards, shards[1:])),
           "two devices hold the same batch shard")

    t0 = time.perf_counter()
    state = init(jax.random.PRNGKey(0))
    compiled = step.lower(state, images, labels).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    if n > 1:
        _check("all-reduce" in text,
               "no all-reduce in the compiled data-parallel step")

    params0 = jax.device_get(state.params)  # the step donates its state
    state, losses, run_s = _run_steps(compiled, state, (images, labels), 6)
    _losses_ok(losses, "resnet_hvd")
    params1 = jax.device_get(state.params)
    changed = [bool(np.any(a != b)) for a, b in zip(
        jax.tree.leaves(params0), jax.tree.leaves(params1))]
    _check(all(changed),
           f"{changed.count(False)} of {len(changed)} parameter leaves "
           "did not change")
    want = set(mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        have = {s.device for s in leaf.addressable_shards}
        _check(have == want,
               f"state leaf {jax.tree_util.keystr(path)} lives on "
               f"{sorted(d.id for d in have)}, not on every mesh device")
        _check(bool(np.all(np.isfinite(np.asarray(
            leaf.addressable_shards[0].data, dtype=np.float32)))),
            f"non-finite values in state leaf {jax.tree_util.keystr(path)}")
    return {"compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "steps": len(losses), "loss_first": losses[0],
            "loss_last": losses[-1], "dp": n,
            "all_reduce_in_step": "all-reduce" in text}


# ---------------------------------------------------------------------------
# leg 2: trainer, kernel
# ---------------------------------------------------------------------------


def _dense_attention(q, k, v):
    """Causal attention in plain ``jax.numpy``, float32 at full matmul
    precision: the reference the kernel is held to."""
    import math

    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    S, D = q.shape[1], q.shape[3]
    logits = jnp.einsum("bshk,bthk->bhst", q, k,
                        precision=hi) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    return jnp.einsum("bhst,bthk->bshk", jax.nn.softmax(logits, axis=-1),
                      v, precision=hi)


def _flash_vs_dense(shape) -> Dict[str, float]:
    """``flash_attention`` forward and backward against dense attention on
    this process's default device, bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.pallas_attention import flash_attention

    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(
        jnp.bfloat16) for key in (kq, kk, kv))
    w = jax.random.normal(kw, shape, jnp.float32)  # output cotangent

    def both(attn):
        def f(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out_f), grads_f = both(
        lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    (_, out_d), grads_d = both(_dense_attention)(q, k, v)
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"),
                          (out_f,) + tuple(grads_f),
                          (out_d,) + tuple(grads_d)):
        a = np.asarray(a, dtype=np.float32)
        b = np.asarray(b, dtype=np.float32)
        _check(bool(np.all(np.isfinite(a))), f"flash {name} is not finite")
        errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        _check(errs[name] <= FLASH_TOL,
               f"flash {name} differs from dense attention by "
               f"{errs[name]:.4f} of max|dense| (tolerance {FLASH_TOL})")
    return errs


def leg_lm_flash(devices, sizes: Sizes) -> Dict[str, Any]:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import train as train_mod

    n = len(devices)
    cfg = tfm.TransformerConfig(**sizes.lm)
    mesh = mesh_mod.make_mesh({"dp": n}, devices=devices)
    step, init = train_mod.make_transformer_train_step(cfg, mesh)
    rs = np.random.RandomState(1)
    toks_np = rs.randint(0, cfg.vocab_size,
                         (sizes.lm_seqs_per_chip * n,
                          sizes.lm_seq)).astype(np.int32)
    sharding = NamedSharding(mesh, P("dp"))
    toks = jax.device_put(toks_np, sharding)
    tgts = jax.device_put(np.roll(toks_np, -1, axis=1), sharding)

    t0 = time.perf_counter()
    state = init(jax.random.PRNGKey(0))
    compiled = step.lower(state, toks, tgts).compile()
    compile_s = time.perf_counter() - t0
    # Mosaic custom calls in the compiled step: the kernel was compiled,
    # not interpreted into plain HLO and not replaced by dense attention.
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    if devices[0].platform == "tpu":
        _check(mosaic_calls > 0,
               "no tpu_custom_call in the compiled flash LM step: the "
               "Pallas kernel was interpreted or replaced")

    state, losses, run_s = _run_steps(compiled, state, (toks, tgts), 5)
    _losses_ok(losses, "lm_flash")
    del state, compiled

    t0 = time.perf_counter()
    errs = _flash_vs_dense(sizes.attn_shape)
    kernel_check_s = time.perf_counter() - t0
    return {"compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "steps": len(losses), "loss_first": losses[0],
            "loss_last": losses[-1], "mosaic_calls": mosaic_calls,
            "flash_vs_dense_rel_err": {k: round(v, 5)
                                       for k, v in errs.items()},
            "flash_tol": FLASH_TOL,
            "kernel_check_s": round(kernel_check_s, 2)}


# ---------------------------------------------------------------------------
# leg 3: server
# ---------------------------------------------------------------------------


def _post_generate(port: int, prompt: List[int], max_new: int):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/generate", json.dumps(
            {"prompt": prompt, "max_new_tokens": max_new}))
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def leg_serve(devices, sizes: Sizes) -> Dict[str, Any]:
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving import ServingLoop

    cfg = tfm.TransformerConfig(**sizes.serve)
    cache_len = cfg.max_seq_len
    params = jax.jit(lambda k: tfm.init(k, cfg))(jax.random.PRNGKey(0))
    rs = np.random.RandomState(2)
    requests = [([int(t) for t in rs.randint(1, cfg.vocab_size, (plen,))],
                 new) for plen, new in sizes.serve_requests]

    ready = threading.Event()
    box: Dict[str, Any] = {}

    def on_ready(port):
        box["port"] = port
        ready.set()

    loop = ServingLoop(params, cfg, port=0, max_batch=4, max_queue=64,
                       cache_len=cache_len, host="127.0.0.1",
                       on_ready=on_ready)

    def serve():
        try:
            loop.run()
        except BaseException as e:  # surfaced by the main thread below
            box["error"] = e
            ready.set()
            raise

    thread = threading.Thread(target=serve, name="chip-smoke-serve",
                              daemon=True)
    thread.start()
    try:
        _check(ready.wait(300), "serving loop never came up")
        if "error" in box:
            raise box["error"]

        def one_pass():
            t0 = time.perf_counter()
            out = []
            for prompt, new in requests:
                status, body = _post_generate(box["port"], prompt, new)
                _check(status == 200, f"POST /generate -> {status}: {body}")
                _check(len(body["tokens"]) == new,
                       f"asked for {new} tokens, got {len(body['tokens'])}")
                out.append([int(t) for t in body["tokens"]])
            return out, time.perf_counter() - t0

        # The first pass compiles (one prefill per prompt length, the
        # decode step); the second pass runs the same shapes warm.
        cold, cold_s = one_pass()
        warm, warm_s = one_pass()
        _check(cold == warm, "greedy decode answered the same prompts "
                             f"differently: {cold} vs {warm}")
    finally:
        loop.stop()
        thread.join(120)
        hvd.shutdown()
    _check(not thread.is_alive(), "serving loop did not stop")
    if "error" in box:
        raise box["error"]

    # The repo's oracle: transformer.generate on the same prompt, alone,
    # with the serving cache length.
    prompt, new = requests[0]
    oracle = tfm.generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                          max_new_tokens=new, cache_len=cache_len)
    oracle = [int(t) for t in np.asarray(oracle)[0, len(prompt):]]
    _check(cold[0] == oracle,
           f"served tokens {cold[0]} != transformer.generate {oracle}")
    return {"compile_s": round(max(cold_s - warm_s, 0.0), 2),
            "run_s": round(warm_s, 2), "requests": len(requests),
            "prompt_lens": [len(p) for p, _ in requests],
            "tokens": [len(t) for t in cold], "oracle_match": True}


# ---------------------------------------------------------------------------


LEGS: List[Tuple[str, Callable]] = [
    ("resnet_hvd", leg_resnet_hvd),
    ("lm_flash", leg_lm_flash),
    ("serve", leg_serve),
]


def run(devices, legs, sizes: Sizes) -> int:
    """Run ``legs`` on ``devices``; print the result line and return 0 only
    if every leg passed."""
    failed = []
    for name, leg in legs:
        print(f"chip_smoke: leg {name} ...", flush=True)
        try:
            info = leg(devices, sizes)
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: leg {name} FAIL", flush=True)
            failed.append(name)
        else:
            print(f"chip_smoke: leg {name} PASS {json.dumps(info)}",
                  flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def main() -> int:
    from device_peaks import peak
    from horovod_tpu.utils.platform import (
        accelerator_devices,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    devices = accelerator_devices()  # exits non-zero without a TPU
    peak(devices[0].device_kind)     # raises for a chip not in the table
    print(f"chip_smoke: platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind!r} count={len(devices)} "
          f"compile_cache={cache_dir}", flush=True)
    return run(devices, LEGS, _full_sizes())


if __name__ == "__main__":
    sys.exit(main())
