"""Decoder-LM pretraining on a multi-axis device mesh — the TPU-native
flagship workflow.

No reference counterpart exists (Horovod v0.19 is data-parallel only;
SURVEY.md §2.8): this example shows the in-graph regime the framework
adds — one `jit`-compiled train step whose parallelism comes entirely
from a named mesh:

    dp  data parallel (gradients psum over dp)
    tp  Megatron tensor parallel (QKV/FFN column-, projections row-sharded)
    sp  sequence parallel (ring attention over ppermute when sp > 1)

plus rank-0-gated orbax checkpointing with resume
(`horovod_tpu.utils.checkpoint.resume_or_init`), so a preempted run —
or one relaunched by `hvdrun --max-restarts` — continues where it left
off.  Run on a virtual 8-device mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax_transformer_lm.py --dp 2 --tp 2 --sp 2

On a TPU slice, drop the env vars and size the axes to the hardware.
Uses a synthetic Zipf corpus (this environment has no egress).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=256)
    p.add_argument("--vocab-size", type=int, default=512)
    p.add_argument("--batch-size", type=int, default=8,
                   help="global batch (sharded over dp)")
    p.add_argument("--seq-len", type=int, default=64,
                   help="sequence length (sharded over sp)")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=25)
    p.add_argument("--fp32", action="store_true",
                   help="compute in fp32 (default bf16 on TPU meshes)")
    p.add_argument("--zero1", action="store_true",
                   help="shard optimizer state over dp (ZeRO-1)")
    p.add_argument("--jax-distributed", action="store_true",
                   help="join all hvdrun processes' devices into one "
                        "global mesh (hvd.init_jax_distributed)")
    args = p.parse_args()

    if args.jax_distributed:
        import horovod_tpu as hvd

        hvd.init()
        hvd.init_jax_distributed()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import train as train_mod
    from horovod_tpu.utils import checkpoint as ckpt
    from horovod_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    axes = {k: v for k, v in
            (("dp", args.dp), ("tp", args.tp), ("sp", args.sp)) if v > 1}
    n_mesh = int(np.prod(list(axes.values()))) if axes else 1
    if n_mesh > len(jax.devices()):
        raise SystemExit(f"mesh needs {n_mesh} devices, "
                         f"have {len(jax.devices())}")
    mesh = mesh_mod.make_mesh(axes or {"dp": 1},
                              devices=jax.devices()[:n_mesh])
    if args.batch_size % max(args.dp, 1):
        raise SystemExit("--batch-size must divide over --dp")
    if args.seq_len % max(args.sp, 1):
        raise SystemExit("--seq-len must divide over --sp")

    cfg = tfm.TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads, d_ff=args.d_ff,
        max_seq_len=args.seq_len,
        compute_dtype=jnp.float32 if args.fp32 else jnp.bfloat16,
        # ring attention rotates K/V blocks around the sp ring; dense
        # GSPMD attention otherwise
        attn_impl="ring" if args.sp > 1 else "dense")

    step, init = train_mod.make_transformer_train_step(
        cfg, mesh, zero1=args.zero1)

    def fresh():
        return init(jax.random.PRNGKey(0))

    ckpt_path = (os.path.join(args.checkpoint_dir, "state")
                 if args.checkpoint_dir else None)
    state = (ckpt.resume_or_init(ckpt_path, fresh) if ckpt_path
             else fresh())
    start_step = int(jax.device_get(state.step))
    if start_step:
        print(f"resumed from step {start_step}")

    # Synthetic Zipf token stream with local correlation.
    rs = np.random.RandomState(0)
    zipf = 1.0 / np.arange(1, args.vocab_size + 1)
    corpus = rs.choice(args.vocab_size, 200_000, p=zipf / zipf.sum())

    def batch(i):
        idx = (np.arange(args.batch_size)[:, None] * 977 +
               np.arange(args.seq_len + 1)[None, :] + i * 31) % (
                   len(corpus) - 1)
        toks = corpus[idx]
        return (jnp.asarray(toks[:, :-1], jnp.int32),
                jnp.asarray(toks[:, 1:], jnp.int32))

    t0 = time.time()
    last_saved = start_step
    for i in range(start_step, args.steps):
        tokens, targets = batch(i)
        state, loss = step(state, tokens, targets)
        if (i + 1) % 10 == 0 or i + 1 == args.steps:
            print(f"step {i + 1}: loss {float(loss):.4f}")
        if ckpt_path and (i + 1) % args.checkpoint_every == 0:
            ckpt.save(ckpt_path, state)
            last_saved = i + 1
    dt = time.time() - t0
    done = args.steps - start_step
    if done > 0:
        toks = done * args.batch_size * args.seq_len
        print(f"done: mesh={axes or {'dp': 1}} ({n_mesh} devices), "
              f"{toks / dt:.0f} tokens/sec")
    if ckpt_path:
        if last_saved != args.steps:
            ckpt.save(ckpt_path, state)
        print(f"checkpoint at step {int(jax.device_get(state.step))}")


if __name__ == "__main__":
    main()
