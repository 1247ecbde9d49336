"""Continuous-batching LM serving on the gang — `horovod_tpu.serving`.

Every rank runs this same script (docs/serving.md): rank 0 opens the
HTTP front door and drives admissions; all ranks step the identical
jit-ed decode in lockstep off the broadcast batch deltas.  The model is
a tiny randomly-initialized decoder (deterministic seed, so every rank
holds identical params without a broadcast) — the point is the serving
machinery, not the prose.

Serve on a 2-rank gang and query it::

    hvdrun -np 2 --serve-port 8100 -- python examples/serve_lm.py
    curl -s localhost:8100/generate \
        -d '{"prompt": [3, 14, 15], "max_new_tokens": 24}'
    curl -s localhost:8100/stats

Or single-process with a built-in closed-loop client::

    python examples/serve_lm.py --selftest 8

Greedy decode is deterministic, so resubmitting a prompt always returns
the same tokens — including after a gang re-form replays it
(``attempts`` > 1 in the response).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import threading


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=128)
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--cache-len", type=int, default=128,
                   help="serving KV cache length (caps prompt+new)")
    p.add_argument("--port", type=int, default=None,
                   help="front-door port (default HVD_SERVE_PORT, "
                        "0 = ephemeral)")
    p.add_argument("--max-batch", type=int, default=None,
                   help="decode slots (default HVD_SERVE_MAX_BATCH)")
    p.add_argument("--selftest", type=int, default=0, metavar="N",
                   help="run N closed-loop requests from this process, "
                        "print them, and exit (instead of serving "
                        "forever)")
    args = p.parse_args()

    os.environ.setdefault("HVD_TPU_CORE", "py")  # serving requirement

    import jax
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.serving import ServingLoop
    from horovod_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    cfg = tfm.TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads, d_ff=args.d_ff,
        max_seq_len=args.cache_len, compute_dtype=jax.numpy.float32,
        remat=False)
    params = tfm.init(jax.random.PRNGKey(0), cfg)

    ready = threading.Event()
    port_box = {}

    def on_ready(port):
        port_box["port"] = port
        print(f"serving on http://127.0.0.1:{port}/generate", flush=True)
        ready.set()

    loop = ServingLoop(params, cfg, port=args.port,
                       max_batch=args.max_batch,
                       cache_len=args.cache_len, on_ready=on_ready)

    if args.selftest and hvd.rank() == 0:
        def client():
            import http.client

            ready.wait()
            conns = []
            for i in range(args.selftest):
                c = http.client.HTTPConnection("127.0.0.1",
                                               port_box["port"])
                c.request("POST", "/generate", json.dumps(
                    {"prompt": [3 + i, 14, 15], "max_new_tokens": 12}))
                conns.append((i, c))
            for i, c in conns:
                body = json.loads(c.getresponse().read())
                print(f"request {i}: {body['tokens']}", flush=True)
                c.close()
            loop.stop()

        threading.Thread(target=client, daemon=True).start()

    loop.run()
    hvd.shutdown()


if __name__ == "__main__":
    main()
