"""Read the numbers a cell's limits are set from, on the chip.

    python3 perfbench/tools/limits.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2] [--seconds 15]

For every seed it prints one JSON line with the numbers ``correct``
compares (the program against the float32 reference) and, for the control
seeds, the same numbers with the reference's int8 path in the program's
place.  Training cells read all seeds in one process and need no window;
a serving cell is one process a seed (call once per seed) and drives a
short window at the cell's own load.  Not run by the benchmark's own runs.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import compare, harness  # noqa: E402


def training(cell: str, seeds, control_seeds, rehearsal: bool) -> None:
    import importlib

    from perfbench.jobs import _train

    for seed in seeds:
        run = harness.Run(workload=cell, seed=seed, seconds=0.0, trace=False,
                          rehearsal=rehearsal, t_start=time.perf_counter())
        run.open_devices()
        job = importlib.import_module(
            f"perfbench.jobs.{run.cell.config['family']}_train")
        s = job.build(run)
        state, prog, _ = _train.first_steps(s)
        del state
        ref = s.reference()
        out = {"cell": cell, "seed": seed,
               "sound": compare.training_checks(prog, ref,
                                                s.limits).as_dict()}
        def top(a):
            g = compare.leaf_gaps(a["first_grad_norm"],
                                  ref["first_grad_norm"])
            return sorted(g.items(), key=lambda kv: -kv[1])[:6]

        out["sound_top_grad_leaves"] = top(prog)
        out["leaves"] = {
            "ref_grad_norm": ref["first_grad_norm"],
            "sound_grad_gap": compare.leaf_gaps(prog["first_grad_norm"],
                                                ref["first_grad_norm"]),
            "sound_delta_gap": compare.leaf_gaps(prog["delta_norm"],
                                                 ref["delta_norm"])}
        if seed in control_seeds:
            ctl = s.reference(quant=True)
            out["control"] = compare.training_checks(
                ctl, ref, s.limits).as_dict()
            out["control_top_grad_leaves"] = top(ctl)
            out["leaves"]["control_grad_gap"] = compare.leaf_gaps(
                ctl["first_grad_norm"], ref["first_grad_norm"])
            out["leaves"]["control_delta_gap"] = compare.leaf_gaps(
                ctl["delta_norm"], ref["delta_norm"])
        print(json.dumps(out), flush=True)
        del s, prog, ref


def serving(cell: str, seeds, control_seeds, seconds: float,
            rehearsal: bool) -> None:
    from functools import partial

    from perfbench.jobs import lm_serve

    if len(seeds) != 1:
        raise SystemExit("a serving cell takes one seed a process")
    argv = ["--workload", cell, "--seed", str(seeds[0]), "--seconds",
            str(seconds), "--trace", "0"] + (["--rehearsal"] if rehearsal
                                             else [])
    harness.main(argv, job=partial(lm_serve.run,
                                   control=seeds[0] in control_seeds))


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    seeds = [int(x) for x in a.seeds.split(",") if x]
    control = {int(x) for x in a.control_seeds.split(",") if x}
    cell = harness.Cell(a.workload)
    if cell.traffic["kind"] == "train":
        training(a.workload, seeds, control, a.rehearsal)
    else:
        serving(a.workload, seeds, control, a.seconds, a.rehearsal)


if __name__ == "__main__":
    main()
