"""``knee_sweep.py`` for a cell of the ``conv_moe_lm`` family: the same sweep
(one process, one server; each rate a pre-roll and a window of the cell's
own traffic at that rate, drained before the next; one JSON line a rate),
stood up by the family's own job.

    python3 perfbench/tools/conv_moe_knee_sweep.py \
        --workload lfm2-8b-a1b_serve_assistants \
        --rates 8,9,10,11,12,13 --seconds 30

The knee is the highest rate whose queue ends no longer than it began
(``queued_end <= queued_start``).
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import harness, loadgen  # noqa: E402


def main() -> None:
    import argparse

    from perfbench.jobs import conv_moe_lm_serve, lm_serve

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20260928)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    run = harness.Run(workload=a.workload, seed=a.seed, seconds=a.seconds,
                      trace=False, rehearsal=a.rehearsal,
                      t_start=time.perf_counter())
    run.open_devices()
    traffic, _, sizes, _, server, client = conv_moe_lm_serve.stand_up(
        run, registry=True)
    vocab = sizes["vocab_size"]
    try:
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            got = lm_serve.drive(
                run, client, server, {**traffic, "rate_rps": rate},
                a.seconds, run.numpy_rng(10 + i), vocab, trace=False,
                poll=True)
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
