"""Elastic training end-to-end: a SIGKILLed rank triggers an in-process
gang re-form at a smaller world (rollback to the last commit, replay,
continue — no relaunch), and a discovery-announced joiner grows the gang
mid-run.  Plus fast unit tests for the state / driver / KV pieces.

Multi-process scenarios reuse the harness idiom of tests/test_chaos.py:
per-rank subprocess environments on the loopback mesh, stdout markers
parsed by the driving test, exit codes as part of the contract.
"""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from horovod_tpu.common import fault_injection as fi
from horovod_tpu.runner.http_server import RendezvousServer

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "elastic_worker.py")

HEARTBEAT_ENV = {"HVD_HEARTBEAT_TIMEOUT": "2.0",
                 "HVD_HEARTBEAT_INTERVAL": "0.25"}


# ---------------------------------------------------------------------------
# state commit / rollback (in-process, no engine)
# ---------------------------------------------------------------------------


def test_object_state_commit_restore_roundtrip():
    from horovod_tpu import elastic

    s = elastic.ObjectState(w=np.arange(4, dtype=np.float32), step=0)
    s.w[0] = 99.0
    s.step = 5
    s.restore()  # back to the construction-time snapshot
    assert s.step == 0 and float(s.w[0]) == 0.0
    s.step = 3
    s.w = s.w + 1.0
    s.commit()  # no elastic ctx attached: commit is a plain snapshot
    s.step = 7
    s.w[:] = 0.0
    s.restore()
    assert s.step == 3 and float(s.w[0]) == 1.0


def test_state_reset_rewinds_commit_serial():
    from horovod_tpu import elastic

    s = elastic.ObjectState(x=1)
    s._commit_serial = 14
    s._update_pending = True
    called = []
    s.register_reset_callbacks([lambda: called.append(True)])
    s.on_reset()
    # Commit-check collectives are named by the serial; a joiner admitted
    # at the re-form starts at 0, so survivors must rewind theirs too or
    # the next commit's allreduce names diverge across ranks.
    assert s._commit_serial == 0
    assert not s._update_pending
    assert called == [True]


# ---------------------------------------------------------------------------
# host discovery + driver (in-process)
# ---------------------------------------------------------------------------


def test_host_discovery_script_parsing(tmp_path):
    from horovod_tpu.elastic.driver import HostDiscoveryScript

    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\n"
                      "echo '# provisioning note'\n"
                      "echo hostA:4\n"
                      "echo '  hostB  '\n"
                      "echo ''\n"
                      "echo hostC:1\n")
    script.chmod(0o755)
    d = HostDiscoveryScript(str(script), default_slots=2)
    assert d.find_available_hosts_and_slots() == {
        "hostA": 4, "hostB": 2, "hostC": 1}


def test_elastic_driver_epoch_and_blacklist():
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import HostBlacklist

    class StubDiscovery:
        def __init__(self):
            self.hosts = {"a": 1}

        def find_available_hosts_and_slots(self):
            return dict(self.hosts)

    events = []
    disco = StubDiscovery()
    bl = HostBlacklist(threshold=1, cooldown_s=300.0)
    d = ElasticDriver(
        disco, 1, 4, blacklist=bl, interval_s=0.02,
        on_hosts_updated=lambda e, a, r: events.append((e, a, r)))
    d.start()
    try:
        # start() polls synchronously: the first host set is an epoch bump
        assert d.epoch == 1 and d.hosts() == {"a": 1}
        assert events == [(1, ["a"], [])]
        disco.hosts["b"] = 2
        deadline = time.monotonic() + 5.0
        while d.epoch < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert d.epoch == 2 and d.slots() == 3
        assert events[-1] == (2, ["b"], [])
        bl.record_failure("b")  # blacklisted hosts drop out of discovery
        while d.epoch < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert d.hosts() == {"a": 1}
        assert events[-1] == (3, [], ["b"])
    finally:
        d.stop()


def test_driver_wait_for_available_slots():
    from horovod_tpu.elastic.driver import ElasticDriver, FixedHostDiscovery

    d = ElasticDriver(FixedHostDiscovery({"a": 2}), 1, 4, interval_s=0.02)
    d.start()
    try:
        assert d.wait_for_available_slots(2) == {"a": 2}
        with pytest.raises(TimeoutError):
            d.wait_for_available_slots(5, timeout=0.15)
    finally:
        d.stop()


def test_kv_list_prefix(monkeypatch):
    monkeypatch.delenv("HVD_SECRET_KEY", raising=False)
    from horovod_tpu.runner.http_client import KVClient

    server = RendezvousServer("127.0.0.1")
    port = server.start()
    try:
        kv = KVClient("127.0.0.1", port)
        kv.put("elastic/pending/uid-a", "1")
        kv.put("elastic/pending/uid-b", "1")
        kv.put("elastic/world/1", "x")
        assert kv.list("elastic/pending/") == [
            "elastic/pending/uid-a", "elastic/pending/uid-b"]
        assert kv.list("nope/") == []
    finally:
        server.stop()


@pytest.mark.parametrize("pending, max_np, seen", [
    (["uid-joiner"], 3, True),      # announced while the gang was starting
    ([], 3, False),                 # an update with nobody waiting: handled
    (["uid-joiner"], 2, False),     # no room: deferred, not re-armed
    (["uid-1"], 3, False),          # a member's stale key is not a joiner
], ids=["joiner_waiting", "nobody_waiting", "no_room", "member_key"])
def test_consume_updates_leaves_a_waiting_joiner_to_the_next_commit(
        monkeypatch, pending, max_np, seen):
    """A joiner puts its pending key, then bumps the update count.  A
    rank that is still starting (or re-forming) when that happens takes
    the count as handled; it must not take the joiner's bump with it, or
    nobody ever admits the joiner (the hang of
    test_elastic_joiner_grows_gang on a loaded machine)."""
    monkeypatch.delenv("HVD_SECRET_KEY", raising=False)
    from horovod_tpu.elastic.run import _ElasticContext

    server = RendezvousServer("127.0.0.1")
    monkeypatch.setenv("HVD_RENDEZVOUS_ADDR", "127.0.0.1")
    monkeypatch.setenv("HVD_RENDEZVOUS_PORT", str(server.start()))
    monkeypatch.setenv("HVD_ELASTIC_UID", "uid-0")
    monkeypatch.setenv("HVD_ELASTIC_MAX_NP", str(max_np))
    try:
        ctx = _ElasticContext()
        ctx.roster = ["uid-0", "uid-1"]
        for uid in pending:
            ctx.kv.put(ctx.key(f"elastic/pending/{uid}"), "1")
        ctx.publish_update()
        ctx.consume_updates()
        assert ctx.has_pending_update() is seen
        ctx.publish_update()        # a later update is seen either way
        assert ctx.has_pending_update()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# multi-process elastic scenarios
# ---------------------------------------------------------------------------


def run_elastic(np_, *, min_np, max_np, base_env=None, rank_env=None,
                joiner_delay=None, timeout=180.0):
    """Spawn an np_-rank elastic gang of elastic_worker.py (PyEngine on
    the loopback mesh), optionally a late joiner after ``joiner_delay``
    seconds, and return per-process (exit_code, stdout, stderr) — the
    joiner's tuple last."""
    server = RendezvousServer("127.0.0.1")
    port = server.start()

    def env_for(rank, extra=None):
        env = dict(os.environ)
        env.pop(fi.ENV_VAR, None)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update({
            "HVD_RANK": str(rank),
            "HVD_SIZE": str(np_),
            "HVD_LOCAL_RANK": str(rank),
            "HVD_LOCAL_SIZE": str(np_),
            "HVD_CROSS_RANK": "0",
            "HVD_CROSS_SIZE": "1",
            "HVD_RENDEZVOUS_ADDR": "127.0.0.1",
            "HVD_RENDEZVOUS_PORT": str(port),
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_CORE": "py",
            "HVD_ELASTIC_EPOCH": "0",
            "HVD_ELASTIC_MIN_NP": str(min_np),
            "HVD_ELASTIC_MAX_NP": str(max_np),
            "HVD_ELASTIC_UID": f"uid-{rank}",
            "HVD_ELASTIC_CHECK_INTERVAL_S": "0.05",
        })
        env.update(HEARTBEAT_ENV)
        if base_env:
            env.update(base_env)
        if extra:
            env.update(extra)
        return env

    procs = []
    try:
        for rank in range(np_):
            procs.append(subprocess.Popen(
                [sys.executable, WORKER],
                env=env_for(rank, (rank_env or {}).get(rank)),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        if joiner_delay is not None:
            time.sleep(joiner_delay)
            # The coordinate env is a placeholder: the joiner blocks for
            # an epoch assignment and first initializes there.
            procs.append(subprocess.Popen(
                [sys.executable, WORKER],
                env=env_for(np_, {"HVD_ELASTIC_JOINER": "1",
                                  "HVD_ELASTIC_UID": "uid-joiner"}),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        deadline = time.monotonic() + timeout
        outs = []
        for p in procs:
            remaining = max(1.0, deadline - time.monotonic())
            try:
                out, err = p.communicate(timeout=remaining)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise AssertionError("elastic scenario: worker timed out")
            outs.append((p.returncode, out.decode(), err.decode()))
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()


def _steps(out):
    return [(int(m.group(1)), float(m.group(2)))
            for m in re.finditer(r"STEP (\d+) ([\d.]+)", out)]


def test_elastic_rank_failure_reforms_smaller_world(tmp_path):
    """Rank 2 of 3 dies SIGKILL-style after step 3, between commits
    (commit every 3 steps, so steps 3-4 are uncommitted work).  The
    survivors' in-flight step 4 completes over the survivor group, the
    next submission raises, and they roll back to the step-3 commit,
    re-form a 2-rank gang under epoch 1 **in the same processes**,
    replay the uncommitted steps, and finish all 8 steps — the final
    weight proves continuation, the timeline records the reset/re-form
    cycle."""
    np_, victim, total = 3, 2, 8
    plan = json.dumps({"faults": [
        {"site": "train.step", "kind": "kill", "after": 3}]})
    tl_path = tmp_path / "elastic_timeline.json"
    outs = run_elastic(
        np_, min_np=2, max_np=3,
        base_env={"ELASTIC_TOTAL_STEPS": str(total),
                  "ELASTIC_COMMIT_EVERY": "3"},
        rank_env={victim: {fi.ENV_VAR: plan},
                  0: {"HVD_TIMELINE": str(tl_path)}})

    v_code, v_out, v_err = outs[victim]
    assert v_code == 137, (v_code, v_out, v_err)
    assert _steps(v_out)[-1][0] == 3  # completed steps 0-3, then died

    for rank in (0, 1):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        assert "RESET size 2" in out, out
        assert "FINAL_EPOCH 1" in out, out
        assert "DONE" in out, out
        steps = _steps(out)
        kept = dict(steps)  # last occurrence per step index survives
        assert sorted(kept) == list(range(total))
        # Step 3 ran at 3.0 over the full gang, was rolled back (its
        # commit never happened), and replayed at 2.0 over the re-formed
        # 2-rank world: the rollback+replay proof.
        occ3 = [v for i, v in steps if i == 3]
        assert occ3 == [3.0, 2.0], steps
        # Committed steps are never replayed.
        assert [v for i, v in steps if i == 0] == [3.0], steps
        # w accumulated exactly the kept executions: the run continued
        # from the commit, not from scratch and not through a relaunch.
        final_w = float(re.search(r"FINAL_W ([\d.]+)", out).group(1))
        assert final_w == sum(kept.values()), (final_w, steps)

    tl = tl_path.read_text()
    assert "ELASTIC_RESET" in tl
    assert "ELASTIC_REFORM" in tl
    assert "ELASTIC_EPOCH_1" in tl


def test_elastic_joiner_grows_gang():
    """A 2-rank gang (max_np=3) is joined mid-run by a late worker: the
    joiner announces itself through the KV store, the incumbents agree to
    interrupt at a commit, the re-formed 3-rank gang syncs state to the
    joiner, and everyone trains on — allreduce sums rise from 2.0 to 3.0
    with zero process relaunches."""
    np_ = 2
    outs = run_elastic(
        np_, min_np=1, max_np=3,
        base_env={"ELASTIC_TOTAL_STEPS": "400",
                  "ELASTIC_COMMIT_EVERY": "1",
                  "ELASTIC_STEP_SLEEP": "0.05",
                  "ELASTIC_STOP_AT_SIZE": "3",
                  "ELASTIC_STEPS_AFTER_GROW": "3"},
        joiner_delay=1.0)

    assert len(outs) == np_ + 1
    for i, (code, out, err) in enumerate(outs):
        assert code == 0, (i, out, err)
        assert "DONE" in out, (i, out, err)

    for rank in range(np_):
        code, out, err = outs[rank]
        assert "RESET size 3" in out, out
        steps = _steps(out)
        assert any(v == 2.0 for _, v in steps), steps  # before the join
        assert steps[-1][1] == 3.0, steps              # after the join
    j_code, j_out, j_err = outs[-1]
    j_steps = _steps(j_out)
    assert j_steps, j_out
    assert all(v == 3.0 for _, v in j_steps), j_steps
    assert "RESET size" not in j_out  # a joiner is fresh, not reset

    # All three agreed on the final state (synced from the survivor
    # leader, then identical steps): same FINAL_W everywhere.
    finals = {re.search(r"FINAL_W ([\d.]+)", o).group(1)
              for _, o, _ in outs}
    assert len(finals) == 1, finals


def test_elastic_discovery_script_triggers_reform(tmp_path):
    """Launcher-less mode: rank 0 runs the in-process discovery driver
    (HVD_HOST_DISCOVERY_SCRIPT).  When the script starts reporting an
    extra host, the gang agrees to interrupt at a commit and re-forms
    under epoch 1 — exactly once: the restarted driver's baseline poll
    must not re-trigger."""
    marker = tmp_path / "hostC.up"
    polled = tmp_path / "driver.polled"
    script = tmp_path / "discover.sh"
    # The marker is read BEFORE the poll stamp is written: once the test
    # sees the stamp, a marker it writes can only be picked up by a
    # *later* poll — the driver's baseline snapshot deterministically
    # excludes hostC no matter how slow worker startup was.
    script.write_text("#!/bin/sh\n"
                      f"if [ -f {marker} ]; then c=1; else c=0; fi\n"
                      "echo hostA\n"
                      "echo hostB\n"
                      f"touch {polled}\n"
                      "if [ $c = 1 ]; then echo hostC; fi\n")
    script.chmod(0o755)

    server = RendezvousServer("127.0.0.1")
    port = server.start()
    procs = []
    try:
        for rank in range(2):
            env = dict(os.environ)
            env.pop(fi.ENV_VAR, None)
            env["PYTHONPATH"] = (REPO + os.pathsep
                                 + env.get("PYTHONPATH", ""))
            env.update({
                "HVD_RANK": str(rank), "HVD_SIZE": "2",
                "HVD_LOCAL_RANK": str(rank), "HVD_LOCAL_SIZE": "2",
                "HVD_CROSS_RANK": "0", "HVD_CROSS_SIZE": "1",
                "HVD_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVD_RENDEZVOUS_PORT": str(port),
                "JAX_PLATFORMS": "cpu",
                "HVD_TPU_CORE": "py",
                "HVD_ELASTIC_EPOCH": "0",
                "HVD_ELASTIC_MIN_NP": "1",
                "HVD_ELASTIC_MAX_NP": "4",
                "HVD_ELASTIC_UID": f"uid-{rank}",
                "HVD_ELASTIC_CHECK_INTERVAL_S": "0.05",
                "HVD_HOST_DISCOVERY_SCRIPT": str(script),
                "HVD_ELASTIC_DISCOVERY_INTERVAL_S": "0.1",
                "ELASTIC_TOTAL_STEPS": "80",
                "ELASTIC_COMMIT_EVERY": "1",
                "ELASTIC_STEP_SLEEP": "0.05",
            })
            env.update(HEARTBEAT_ENV)
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        deadline = time.time() + 60
        while not polled.exists() and time.time() < deadline:
            time.sleep(0.05)
        assert polled.exists(), "discovery driver never polled"
        marker.write_text("up\n")
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=150)
            outs.append((p.returncode, out.decode(), err.decode()))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()

    for rank, (code, out, err) in enumerate(outs):
        assert code == 0, (rank, out, err)
        assert "DONE" in out, (rank, out, err)
        # One re-form (same two members, new epoch), not a reform storm.
        assert out.count("RESET size 2") == 1, out
        assert "FINAL_EPOCH 1" in out, out
        assert all(v == 2.0 for _, v in _steps(out)), out


# ---------------------------------------------------------------------------
# hvdrun elasticity flags: parse-time validation
# ---------------------------------------------------------------------------


def _run_cli(*flags):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.run", *flags,
         sys.executable, "-c", "pass"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO)


def test_cli_elastic_flag_validation(tmp_path):
    """Bad elasticity flags fail at parse time (exit 2, actionable
    message), before any rendezvous or ssh side effect."""
    res = _run_cli("-np", "2", "--min-np", "3")
    assert res.returncode == 2 and "--min-np (3) cannot exceed" \
        in res.stderr, res.stderr
    res = _run_cli("-np", "2", "--max-np", "1")
    assert res.returncode == 2 and "--max-np (1) cannot be below" \
        in res.stderr, res.stderr
    res = _run_cli("-np", "2", "--min-np", "0")
    assert res.returncode == 2 and "--min-np must be >= 1" in res.stderr
    res = _run_cli("-np", "2", "--host-discovery-script",
                   str(tmp_path / "nope.sh"))
    assert res.returncode == 2 and "not an executable file" in res.stderr
    res = _run_cli("-np", "2", "--min-np", "1", "--launcher", "jsrun")
    assert res.returncode == 2 and "not supported with --launcher" \
        in res.stderr, res.stderr


def _cache_view(out):
    m = re.search(r"CACHE (\{.*\})", out)
    assert m, out
    return json.loads(m.group(1))


def test_elastic_response_cache_survivors_agree_after_reform():
    """Response-cache consistency across a failure re-form (satellite of
    the hierarchical-control-plane PR): the re-formed engine starts the
    cache cold on EVERY survivor — positions are renegotiated, and the
    post-re-form hit-bit exchange addresses the same responses on both.
    The probe warms four names twice after training; identical views +
    nonzero hits prove the cache protocol re-converged rather than one
    rank replaying positions from the dead incarnation."""
    plan = json.dumps({"faults": [
        {"site": "train.step", "kind": "kill", "after": 3}]})
    outs = run_elastic(
        3, min_np=2, max_np=3,
        base_env={"ELASTIC_TOTAL_STEPS": "8",
                  "ELASTIC_COMMIT_EVERY": "3",
                  "ELASTIC_CACHE_PROBE": "1"},
        rank_env={2: {fi.ENV_VAR: plan}})

    assert outs[2][0] == 137, outs[2]
    views = []
    for rank in (0, 1):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        assert "RESET size 2" in out, out
        views.append(_cache_view(out))
    assert views[0] == views[1], views
    assert views[0]["len"] >= 4, views
    assert all(pos >= 0 for _, pos in views[0]["positions"]), views
    assert views[0]["hits"] >= 4, views   # the second pass hit


def test_elastic_response_cache_joiner_starts_cold_in_sync():
    """The joiner half: a late worker admitted into a grown gang holds
    no cache from before its epoch, yet after the probe its positions
    match the incumbents' exactly — a cold start re-converges instead
    of desyncing the hit bits."""
    outs = run_elastic(
        2, min_np=1, max_np=3,
        base_env={"ELASTIC_TOTAL_STEPS": "400",
                  "ELASTIC_COMMIT_EVERY": "1",
                  "ELASTIC_STEP_SLEEP": "0.05",
                  "ELASTIC_STOP_AT_SIZE": "3",
                  "ELASTIC_STEPS_AFTER_GROW": "3",
                  "ELASTIC_CACHE_PROBE": "1"},
        joiner_delay=1.0)

    views = []
    for i, (code, out, err) in enumerate(outs):
        assert code == 0, (i, out, err)
        views.append(_cache_view(out))
    assert views[-1] == views[0], views       # joiner == incumbent
    assert all(v == views[0] for v in views), views
    assert views[0]["hits"] >= 4, views
