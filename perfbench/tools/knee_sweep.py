"""Find the knee of a serving cell once, on the chip: the highest offered
rate at which the queue at the window's end is no longer than at its start
and nothing is refused.  One process, one server; each rate is a pre-roll
and a window of the cell's own traffic at that rate, drained before the
next.  Prints one JSON line a rate; the table goes into PERF.md and the
knee and 0.8 of it into the traffic file.

    python3 perfbench/tools/knee_sweep.py --workload olmo-1b_serve_chat \
        --rates 3,4,5,6,7 --seconds 30
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import harness, loadgen  # noqa: E402


def main() -> None:
    import argparse

    import jax

    from perfbench.jobs import lm_serve
    from perfbench.jobs.lm_train import model_sizes
    from perfbench.reference import lm as ref

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    run = harness.Run(workload=a.workload, seed=a.seed, seconds=a.seconds,
                      trace=False, rehearsal=a.rehearsal,
                      t_start=time.perf_counter())
    run.open_devices()
    traffic, serve = run.cell.traffic, run.cell.params("serve")
    if a.rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
        serve = {**serve, **serve.get("rehearsal", {})}
    sizes = model_sizes(run, "serve")
    weights = jax.jit(lambda k: ref.make_weights(k, sizes))(run.rng_key(0))
    server = lm_serve.Server(run, weights, sizes, serve, registry=True)
    client = loadgen.Client(server.port,
                            timeout_s=serve["request_timeout_s"] + 30)
    try:
        lm_serve.warm_up(client, traffic, serve, run.numpy_rng(2),
                         sizes["vocab_size"])
        run.settle()
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            got = lm_serve.drive(
                run, client, server, {**traffic, "rate_rps": rate},
                a.seconds, run.numpy_rng(10 + i), sizes["vocab_size"],
                trace=False, poll=True)
            s = loadgen.summarize(got["outcomes"])
            last = max(o.t_done for o in got["outcomes"])
            first = min(o.t_due for o in got["outcomes"]
                        if o.request.sampled)
            print(json.dumps({
                "rate_rps": rate, "attempted": s["attempted"],
                "failed": s["failed"],
                "queued_start": got["facts"]["queued_start"],
                "queued_end": got["facts"]["queued_end"],
                "in_flight_mean": got["facts"]["in_flight_mean"],
                "server_step_ms_mean": got["facts"]["server_step_ms_mean"],
                "p50_ms_per_token": loadgen.percentile(s["per_token_ms"], 50),
                "p90_ms_per_token": loadgen.percentile(s["per_token_ms"], 90),
                "ttft_p90_ms": loadgen.percentile(s["ttft_ms"], 90),
                "late_p99_ms": loadgen.percentile(s["late_ms"], 99),
                "drain_s": last - first - a.seconds,
                "compiles_in_window": run.facts["compiles_in_window"],
            }), flush=True)
            while True:
                st = client.get_stats()
                if not st["active"] and not st["queued"]:
                    break
                time.sleep(0.2)
    finally:
        client.close()
        server.stop()


if __name__ == "__main__":
    main()
