"""Shm-transport leak scenarios, run as a standalone subprocess by
tests/test_dataplane.py (rc 0 = clean; any resource-tracker chatter in
the combined output fails the driving test).

Scenarios (``sys.argv[1]``):

* ``shutdown_reform`` — a 2-rank same-host gang pairs over shm (the
  worker asserts the transports really are shm, so the scenario can
  never pass vacuously), allreduces, and verifies no named ``/dev/shm``
  segment exists even while traffic flows (the pairing protocol unlinks
  at attach time).  Then ``hvd.shutdown()`` must leave no ``hvd-send-*``
  threads and no segments — and the gang re-forms under a fresh
  rendezvous scope (the elastic re-form mechanics) and repeats, proving
  re-pairing starts clean.
* ``sigkill`` — a 3-rank gang warms up over shm, then rank 2 dies via
  the chaos harness's ``kill`` kind (``os._exit(137)``, the SIGKILL a
  supervisor sees).  The launcher surfaces the failure; ``/dev/shm``
  must stay clean because every segment name was already unlinked at
  pairing time.

Markers: ``KINDS <rank> <kinds>`` per rank per epoch.
"""

import glob
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEG_GLOB = "/dev/shm/hvd-shm-*"


def _segs(patience=5.0):
    """Named segments still in ``/dev/shm`` after ``patience`` seconds.
    A gang of another test file, run beside this one by pytest-xdist, has
    its own for the instant of its pairing; a leak stays."""
    deadline = time.monotonic() + patience
    while True:
        found = glob.glob(SEG_GLOB)
        if not found or time.monotonic() >= deadline:
            return found
        time.sleep(0.05)


def _senders():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("hvd-send-")]


def _assert_clean(where):
    assert not _segs(), f"{where}: shm segments leaked: {_segs()}"
    deadline = time.monotonic() + 10.0
    while _senders() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not _senders(), \
        f"{where}: sender threads leaked: {_senders()}"


def _one_epoch(epoch):
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ops import eager

    hvd.init()
    from horovod_tpu import basics

    eng = basics._runtime
    kinds = sorted(t.kind for t in eng._transports.values())
    print(f"KINDS {hvd.rank()} {kinds}", flush=True)
    assert kinds and set(kinds) == {"shm"}, \
        f"same-host gang did not pair over shm: {kinds}"
    # Traffic flows with every segment name already unlinked.
    h = eager.allreduce_async(
        np.arange(64, dtype=np.float32) * (hvd.rank() + 1), op=hvd.Sum,
        name=f"probe.e{epoch}")
    out = np.asarray(eager.synchronize(h))
    n = hvd.size()
    expect = np.arange(64, dtype=np.float32) * (n * (n + 1) / 2)
    assert np.array_equal(out, expect), (out[:4], expect[:4])
    assert not _segs(), f"named segment survived pairing: {_segs()}"
    hvd.shutdown()
    _assert_clean(f"epoch {epoch} post-shutdown")


def _gang_shutdown_reform():
    for epoch in range(2):
        # Fresh rendezvous scope per incarnation, exactly like the
        # elastic re-form path: fresh addr/hostid/shm pairing keys.
        os.environ["HVD_RDV_SCOPE"] = f"shmtest-{epoch}"
        _one_epoch(epoch)
    return "ok"


def _gang_sigkill():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import fault_injection as fi
    from horovod_tpu.ops import eager

    hvd.init()
    from horovod_tpu import basics

    eng = basics._runtime
    kinds = sorted(t.kind for t in eng._transports.values())
    print(f"KINDS {hvd.rank()} {kinds}", flush=True)
    assert set(kinds) == {"shm"}, kinds
    h = eager.allreduce_async(np.ones(32, np.float32), op=hvd.Sum,
                              name="warm")
    eager.synchronize(h)
    if hvd.rank() == 2:
        fi.configure({"faults": [{"site": "train.step", "kind": "kill"}]})
        fi.fire("train.step")  # os._exit(137): no teardown runs
    hvd.shutdown()
    return "survived"


def main():
    scenario = sys.argv[1]
    # The launched ranks must import the checkout too.
    os.environ["PYTHONPATH"] = (
        REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    from horovod_tpu.runner.run import run as hvd_run

    env = {"HVD_TPU_CORE": "py", "JAX_PLATFORMS": "cpu"}
    before = _segs()
    assert not before, f"pre-existing segments, aborting: {before}"
    if scenario == "shutdown_reform":
        results = hvd_run(_gang_shutdown_reform, np=2, env=env)
        assert results == ["ok", "ok"], results
    elif scenario == "sigkill":
        try:
            hvd_run(_gang_sigkill, np=3, env=env)
        except Exception as e:
            print(f"EXPECTED_FAILURE {type(e).__name__}", flush=True)
        else:
            raise AssertionError("rank 2's kill did not surface")
    else:
        raise SystemExit(f"unknown scenario {scenario!r}")
    # The launcher has reaped every worker; nothing may remain.
    assert not _segs(), f"segments survived {scenario}: {_segs()}"
    print("CLEAN", flush=True)


if __name__ == "__main__":
    main()
