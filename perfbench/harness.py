"""What every cell's run shares: finding the cell's files by the names in
``BENCHMARK.json``, the device check, the compile counter, the profiler
window, the per-layer readers and the result line.

A run is ``Run(args)`` handed to the job module that the cell's traffic
file and configuration file name between them::

    perfbench/jobs/<config["family"]>_<traffic["kind"]>.py   run(run) -> None

The job fills ``run.end_to_end`` (name -> value), ``run.checks``
(``compare.Checks``), ``run.attempted`` / ``run.failed`` and, in a traced
run, ``run.facts`` (whatever the per-layer readers read).  Nothing here
knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import importlib
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name: str, bench: Optional[Dict] = None):
        bench = bench or load_json(ROOT / "BENCHMARK.json")
        self.bench = bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(ROOT / cfg_entry["file"])
        self.traffic = load_json(
            HERE / "traffic" / f"{self.entry['traffic']}.json")

    def reports(self, metric: Dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def metrics(self, group: str) -> List[Dict]:
        return [m for m in self.bench[group] if self.reports(m)]

    def params(self, section: str) -> Dict:
        """The configuration's ``section`` (``train`` / ``serve``) with the
        traffic file's ``overrides`` laid over it."""
        out = dict(self.config.get(section, {}))
        out.update(self.traffic.get("overrides", {}))
        return out


class CompileCounter:
    """Counts programs compiled or loaded from the cache, by JAX's own
    monitoring event, between ``start()`` and ``stop()``."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        self._on = False
        mon.register_event_duration_secs_listener(self._listen)

    def _listen(self, name: str, _secs: float, **_kw) -> None:
        if self._on and name == COMPILE_EVENT:
            self.count += 1

    def start(self) -> None:
        self.count, self._on = 0, True

    def stop(self) -> int:
        self._on = False
        return self.count


class Run:
    def __init__(self, *, workload: str, seed: int, seconds: float,
                 trace: bool, rehearsal: bool, t_start: float):
        self.cell = Cell(workload)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearsal = bool(rehearsal)
        self.t_start = t_start
        self.end_to_end: Dict[str, float] = {}
        self.facts: Dict[str, Any] = {}
        self.checks = None
        self.attempted = 0
        self.failed = 0
        self.devices: List = []
        self.compiles: Optional[CompileCounter] = None
        self.trace_dir = ROOT / ".perfbench_trace" / workload
        self.memory_peak_bytes: Optional[int] = None

    # -- set-up -----------------------------------------------------------

    def open_devices(self) -> None:
        """The cell's chips, or a non-zero exit.  A CPU run is a rehearsal
        asked for by name (``--rehearsal`` with ``JAX_PLATFORMS=cpu``)."""
        import jax

        from horovod_tpu.utils.platform import enable_compile_cache

        enable_compile_cache()
        devices = jax.devices()
        platform = devices[0].platform
        if self.rehearsal:
            if platform != "cpu":
                raise SystemExit("--rehearsal is for JAX_PLATFORMS=cpu")
        elif platform != "tpu":
            raise SystemExit(
                f"no accelerator: JAX found platform={platform!r} "
                f"count={len(devices)}; this benchmark needs a TPU "
                "(a CPU run is --rehearsal with JAX_PLATFORMS=cpu)")
        else:
            from perfbench.peaks import peak

            peak(devices[0].device_kind)    # unknown chip: an error
        if len(devices) < self.cell.chips:
            raise SystemExit(
                f"cell {self.cell.name} needs {self.cell.chips} chip(s); "
                f"JAX found {len(devices)}")
        self.devices = devices[:self.cell.chips]
        self.compiles = CompileCounter()

    def rng_key(self, stream: int = 0):
        """A JAX key made from ``--seed`` (any size) and a stream number."""
        import jax
        import numpy as np

        words = np.random.SeedSequence([self.seed, stream]).generate_state(2)
        return jax.random.wrap_key_data(
            np.asarray(words, dtype=np.uint32), impl="threefry2x32")

    def numpy_rng(self, stream: int = 0):
        import numpy as np

        return np.random.default_rng(
            np.random.SeedSequence([self.seed, stream]))

    def size(self, key: str, section: str) -> Any:
        """A size of the cell: the rehearsal block's where this is one."""
        params = self.cell.params(section)
        if self.rehearsal and key in params.get("rehearsal", {}):
            return params["rehearsal"][key]
        return params[key]

    # -- the window -------------------------------------------------------

    def settle(self) -> None:
        """Call once warm-up is over.  Tracing and compiling leave millions
        of live Python objects behind; a full collection over them holds
        the interpreter lock for a second or two, and came in the middle
        of one window in six (PERF.md, Findings).  Collect once now and
        take what is left out of later collections."""
        import gc

        gc.collect()
        gc.freeze()

    def setup_done(self) -> float:
        """Call at the first measured instant."""
        now = time.perf_counter()
        self.end_to_end["setup_s"] = now - self.t_start
        self.compiles.start()
        print(f"window open after {now - self.t_start:.3f} s of set-up",
              file=sys.stderr, flush=True)
        return now

    def window_done(self) -> None:
        self.facts["compiles_in_window"] = self.compiles.stop()
        # The allocator counts buffers (``bytes_in_use``) and the loaded
        # programs' scratch (``bytes_reserved``) apart; a chip holds both.
        stats = [d.memory_stats() or {} for d in self.devices]
        print(f"programs compiled or loaded in the window: "
              f"{self.facts['compiles_in_window']}; memory at its end:",
              json.dumps(
            [{k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_reserved")} for s in stats]),
            file=sys.stderr, flush=True)
        self.memory_peak_bytes = max(
            max(int(s.get("peak_bytes_in_use", 0)),
                int(s.get("bytes_in_use", 0))
                + int(s.get("bytes_reserved", 0))) for s in stats)

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def load_trace(self) -> None:
        from perfbench import trace as tr

        t = tr.load(tr.find_xplane(str(self.trace_dir)))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.facts["trace"] = t
        self.facts["trace_window"] = tr.traced_window(t)

    # -- the result -------------------------------------------------------

    def read_per_layer(self) -> Dict[str, Dict]:
        out = {}
        for m in self.cell.metrics("per_layer"):
            spec = load_json(HERE / "metrics" / f"{m['name']}.json")
            reader = importlib.import_module(
                f"perfbench.readers.{spec['reader']}")
            value = reader.read(self, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def result(self) -> Dict:
        d0 = self.devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": self.memory_peak_bytes}
        out: Dict[str, Any] = {
            "correct": bool(self.checks is not None and self.checks.correct
                            and self.failed == 0),
            "attempted": self.attempted, "failed": self.failed}
        if self.trace:
            from perfbench import trace as tr

            metrics = self.read_per_layer()
            t, w = self.facts.get("trace"), self.facts.get("trace_window")
            if t is not None:
                b = tr.busy(t, w)
                chips = sorted(b["busy_s"])[:len(self.devices)]
                device["busy_s"] = (sum(b["busy_s"][c] for c in chips)
                                    / max(len(chips), 1))
                device["window_s"] = b["window_s"]
                chip0 = chips[0] if chips else 0
                out["breakdown"] = {
                    "device_ops": tr.top_ops(t, chip0, 10, w),
                    "idle_gaps": tr.idle_gaps(t, chip0, 5, w)}
        else:
            units = {m["name"]: m["unit"]
                     for m in self.cell.metrics("end_to_end")}
            metrics = {k: {"value": float(v), "unit": units[k]}
                       for k, v in self.end_to_end.items() if k in units}
        if self.rehearsal:
            metrics = {f"rehearsal_{k}": v for k, v in metrics.items()}
        out["metrics"] = metrics
        out["device"] = device
        out["checks"] = self.checks.as_dict() if self.checks else {}
        return out


def main(argv: Optional[List[str]] = None,
         job: Optional[Callable[[Run], None]] = None,
         t_start: Optional[float] = None) -> int:
    """Run one cell once.  ``job`` replaces the cell's own job module (the
    tests drive the rest of a run over a broken one)."""
    import argparse

    if t_start is None:
        t_start = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU (JAX_PLATFORMS=cpu); "
                         "metrics are prefixed rehearsal_")
    a = ap.parse_args(argv)
    run = Run(workload=a.workload, seed=a.seed, seconds=a.seconds,
              trace=bool(a.trace), rehearsal=a.rehearsal, t_start=t_start)
    run.open_devices()
    if job is None:
        job = importlib.import_module(
            f"perfbench.jobs.{run.cell.config['family']}_"
            f"{run.cell.traffic['kind']}").run
    job(run)
    for line in run.checks.report() if run.checks else []:
        print(line, flush=True)
    print(json.dumps(run.result()), flush=True)
    return 0
