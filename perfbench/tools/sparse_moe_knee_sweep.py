"""``knee_sweep.py`` for a cell of the ``sparse_moe_lm`` family: the same
sweep (one process, one server; each rate a pre-roll and a window of the
cell's own sampled traffic at that rate, drained before the next; one JSON
line a rate) WITH THE RESIDENT SESSIONS DECODING: before each rate the
cell's residents are admitted anew, with as many tokens to return as
outlast the rate's window (``--resident-tokens``), and the next rate
waits for them to finish.

    python3 perfbench/tools/sparse_moe_knee_sweep.py \
        --workload deepseek-v3.2_serve_resident --rates 1.5,2,2.5,3,3.5 \
        --seconds 30 --resident-tokens 3500
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import harness, loadgen  # noqa: E402


def main() -> None:
    import argparse

    from perfbench.jobs import lm_serve, sparse_moe_lm_serve as job

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--resident-tokens", type=int, required=True)
    ap.add_argument("--seed", type=int, default=20261001)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    run = harness.Run(workload=a.workload, seed=a.seed, seconds=a.seconds,
                      trace=False, rehearsal=a.rehearsal,
                      t_start=time.perf_counter())
    run.open_devices()
    traffic, _, sizes, _, server, client = job.stand_up(run, registry=True)
    vocab = sizes["vocab_size"]
    spec = {**traffic["residents"], "new_tokens": a.resident_tokens}
    try:
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            t_admit = time.perf_counter()
            rng = run.numpy_rng(40 + i)
            residents = job.admit_residents(client, job.resident_requests(
                spec, rng, vocab), traffic, rng, vocab)
            admitted_s = time.perf_counter() - t_admit
            got = lm_serve.drive(
                run, client, server, {**traffic, "rate_rps": rate},
                a.seconds, run.numpy_rng(10 + i), vocab, trace=False,
                poll=True)
            window_end = (run.t_start + run.end_to_end["setup_s"]
                          + a.seconds)
            s = loadgen.summarize(got["outcomes"])
            sessions = [f.result() for f in residents]
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
                "residents_admitted_in_s": admitted_s,
                "residents_decoding_at_end": sum(
                    o.status == 200 and o.t_done > window_end
                    for o in sessions),
                "resident_tokens_by_window_end": max(
                    job.tokens_by(o, window_end) for o in sessions),
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
