"""Surviving rank 0 (docs/fault_tolerance.md): the two acceptance
gangs for leader fail-over.

* rank 0 is SIGKILLed mid-serving with four requests in flight — the
  lowest surviving rank is promoted, its front door flips from
  forwarder to leader, the followers' shadow slot table replays every
  in-flight request oracle-exact (``attempts > 1``), and rank 1's
  timeline records ``LEADER_FAILOVER`` naming the dead rank.
* the primary rendezvous KV server (a subprocess of the new
  ``python -m horovod_tpu.runner.http_server`` CLI, write-through
  mirrored to a standby) is SIGKILLed mid-elastic-reform — the
  survivors' KV clients rotate to the standby inside the PR-1 retry
  budget and the re-form completes against the mirrored state.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from horovod_tpu.common import fault_injection as fi
from horovod_tpu.runner.http_server import RendezvousServer

from test_serving import (  # noqa: F401  (same-dir test helpers)
    CACHE_LEN, REPO, WORKER, _gang_env, _http, _oracle_tokens,
    _read_port)

HERE = os.path.dirname(os.path.abspath(__file__))
ELASTIC_WORKER = os.path.join(HERE, "elastic_worker.py")


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    fi.clear()
    yield
    fi.clear()


# ---------------------------------------------------------------------------
# rank 0 SIGKILL mid-serving
# ---------------------------------------------------------------------------


def _repost_until_served(port, req_id, prompt, max_new, out, deadline):
    """Closed-loop client half 2: after the old leader died mid-request,
    keep re-POSTing the same id to a survivor's (stable) front door —
    503/forward failures during the re-election window are expected —
    until the promoted leader answers 200."""
    while time.monotonic() < deadline:
        try:
            code, body = _http(port, "POST", "/generate",
                               {"id": req_id, "prompt": prompt,
                                "max_new_tokens": max_new},
                               timeout=150.0)
        except Exception:
            time.sleep(0.25)
            continue
        if code == 200:
            out[req_id] = json.loads(body)
            return
        time.sleep(0.25)
    out[req_id] = None


@pytest.mark.timeout(420)
def test_rank0_sigkill_mid_serving_promotes_survivor(tmp_path):
    """SIGKILL the serving leader with all four decode slots occupied.
    Ranks 1+2 re-form; rank 1 (lowest survivor) is promoted, requeues
    the shadow's in-flight requests, and its follower front door —
    bound since startup — starts answering directly.  Every request
    completes bit-identical to the oracle with ``attempts > 1``."""
    np_ = 3
    reqs = [(f"cli{i}", [3 + i, 14, 15], 24) for i in range(4)]
    tl_path = tmp_path / "failover_timeline.json"
    port_files = {r: str(tmp_path / f"serve_port{r}") for r in range(2)}
    server = RendezvousServer("127.0.0.1")
    rport = server.start()
    procs = []
    results = {}
    try:
        for rank in range(np_):
            env = _gang_env(rank, np_, rport, min_np=2)
            env.update({
                "SERVE_MAX_BATCH": "4",   # all four in flight at once
                "HVD_SHM_DISABLE": "1",   # SIGKILL can't unlink shm
                "HVD_COLLECTIVE_TIMEOUT": "5.0",
                "HVD_COLLECTIVE_PROBE_TIMEOUT": "0.5",
                "HVD_KV_RETRY_BASE_S": "0.02",
            })
            if rank in port_files:
                env["SERVE_PORT_FILE"] = port_files[rank]
            if rank == 0:
                env["SERVE_EXPECT"] = "0"   # dies before stopping
                # The victim's turns take 0.1 s each, so "mid-decode" is
                # a window of seconds: at full speed the 24 tokens are
                # out ~0.1 s after the fourth admission, and on a loaded
                # machine a request could complete between the poll that
                # saw four slots busy and the SIGKILL (attempts == 1).
                env[fi.ENV_VAR] = json.dumps({"faults": [
                    {"site": "serve.step", "kind": "delay",
                     "delay_s": 0.1}]})
            else:
                env["SERVE_EXPECT"] = str(len(reqs))
            if rank == 1:
                env["HVD_TIMELINE"] = str(tl_path)
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))

        port0 = _read_port(port_files[0], procs)
        port1 = _read_port(port_files[1], procs)

        # Phase 1: occupy every slot.  These clients die with the
        # leader; the requests live on in the followers' shadows.
        phase1 = {}

        def client(i, prompt, max_new):
            try:
                phase1[i] = _http(
                    port0, "POST", "/generate",
                    {"id": reqs[i][0], "prompt": prompt,
                     "max_new_tokens": max_new}, timeout=150.0)
            except Exception as e:
                phase1[i] = e

        threads = [threading.Thread(target=client, args=(i, p, m),
                                    daemon=True)
                   for i, (_, p, m) in enumerate(reqs)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                code, body = _http(port0, "GET", "/stats", timeout=5.0)
            except Exception:
                code, body = 0, b"{}"
            if code == 200 and json.loads(body).get("active") == 4:
                break
            time.sleep(0.05)
        else:
            raise AssertionError("four slots never filled")

        # A few of the victim's 0.1 s turns on: the frame that admitted
        # the fourth request has reached the followers' shadows (the
        # scheduler shows a slot busy before that frame is sent), and
        # twenty turns of every request are still to come.
        time.sleep(0.3)
        procs[0].kill()  # SIGKILL, mid-decode

        # Phase 2: the clients re-POST the same ids to rank 1's door.
        reposters = [
            threading.Thread(
                target=_repost_until_served,
                args=(port1, rid, p, m, results,
                      time.monotonic() + 240.0),
                daemon=True)
            for rid, p, m in reqs]
        for t in reposters:
            t.start()
        for t in reposters:
            t.join(timeout=260)

        outs = {}
        for rank in (1, 2):
            out, err = procs[rank].communicate(timeout=120)
            outs[rank] = (procs[rank].returncode, out.decode(),
                          err.decode())
        v_out, v_err = procs[0].communicate(timeout=30)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()

    assert procs[0].returncode == -9, v_err.decode()[-500:]
    for rank in (1, 2):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        assert "DONE" in out, (rank, out, err)
        final = int(re.search(r"GEN_FINAL (\d+)", out).group(1))
        assert final >= 1, out  # a re-form actually happened

    # Every in-flight request completed on the promoted leader,
    # oracle-exact, and its admission shows the replay.
    for rid, prompt, max_new in reqs:
        got = results.get(rid)
        assert got is not None, (rid, results)
        assert got["tokens"] == _oracle_tokens(prompt, max_new), rid
        assert got["attempts"] > 1, (rid, got)

    # LEADER_FAILOVER on the promoted rank's timeline names rank 0.
    tl = tl_path.read_text()
    assert "LEADER_FAILOVER" in tl, tl[-2000:]
    recs = [json.loads(line.rstrip().rstrip(","))
            for line in tl.splitlines() if "LEADER_FAILOVER" in line]
    assert any(0 in ((r.get("args") or {}).get("failed") or [])
               for r in recs), recs


# ---------------------------------------------------------------------------
# primary KV SIGKILL mid-elastic-reform
# ---------------------------------------------------------------------------


def _start_primary_kv(tmp_path, standby_port):
    """The primary rendezvous server as a killable subprocess (the new
    http_server CLI), write-through mirrored to the in-process standby."""
    port_file = str(tmp_path / "kv_port")
    env = dict(os.environ)
    env.pop("HVD_SECRET_KEY", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.http_server",
         "--host", "127.0.0.1", "--port", "0",
         "--port-file", port_file,
         "--mirror", f"127.0.0.1:{standby_port}"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if os.path.exists(port_file):
            return proc, int(open(port_file).read())
        if proc.poll() is not None:
            out, err = proc.communicate()
            raise AssertionError(
                f"primary KV died at start: {out.decode()} {err.decode()}")
        time.sleep(0.05)
    proc.kill()
    raise AssertionError("primary KV never wrote its port file")


@pytest.mark.timeout(420)
def test_kv_primary_sigkill_mid_reform_uses_standby(tmp_path):
    """Rank 2 of 3 dies after step 3 (the eviction/re-form trigger);
    the moment it is gone the primary KV server is SIGKILLed too.  The
    survivors' rendezvous traffic rotates to the mirrored standby
    inside the normal retry budget and the epoch-1 re-form completes —
    same rollback/replay outcome as with a healthy KV."""
    standby = RendezvousServer("127.0.0.1")
    sport = standby.start()
    primary, pport = _start_primary_kv(tmp_path, sport)
    np_, victim, total = 3, 2, 8
    plan = json.dumps({"faults": [
        {"site": "train.step", "kind": "kill", "after": 3}]})
    procs = []
    try:
        for rank in range(np_):
            env = dict(os.environ)
            env.pop(fi.ENV_VAR, None)
            env.pop("HVD_SECRET_KEY", None)
            env["PYTHONPATH"] = (REPO + os.pathsep
                                 + env.get("PYTHONPATH", ""))
            env.update({
                "HVD_RANK": str(rank), "HVD_SIZE": str(np_),
                "HVD_LOCAL_RANK": str(rank),
                "HVD_LOCAL_SIZE": str(np_),
                "HVD_CROSS_RANK": "0", "HVD_CROSS_SIZE": "1",
                "HVD_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVD_RENDEZVOUS_PORT": str(pport),
                "HVD_KV_ADDRS":
                    f"127.0.0.1:{pport},127.0.0.1:{sport}",
                "HVD_KV_RETRY_BASE_S": "0.02",
                "JAX_PLATFORMS": "cpu",
                "HVD_TPU_CORE": "py",
                "HVD_ELASTIC_EPOCH": "0",
                "HVD_ELASTIC_MIN_NP": "2",
                "HVD_ELASTIC_MAX_NP": str(np_),
                "HVD_ELASTIC_UID": f"uid-{rank}",
                "HVD_ELASTIC_CHECK_INTERVAL_S": "0.05",
                "HVD_HEARTBEAT_TIMEOUT": "2.0",
                "HVD_HEARTBEAT_INTERVAL": "0.25",
                "ELASTIC_TOTAL_STEPS": str(total),
                "ELASTIC_COMMIT_EVERY": "3",
            })
            if rank == victim:
                env[fi.ENV_VAR] = plan
            procs.append(subprocess.Popen(
                [sys.executable, ELASTIC_WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))

        # The victim's death is the re-form trigger: the instant it
        # exits, kill the primary KV so the entire re-form conversation
        # has to happen against the standby.
        deadline = time.monotonic() + 180.0
        while procs[victim].poll() is None and \
                time.monotonic() < deadline:
            time.sleep(0.02)
        assert procs[victim].poll() == 137, "victim never died"
        primary.kill()

        outs = []
        for p in procs:
            out, err = p.communicate(timeout=180)
            outs.append((p.returncode, out.decode(), err.decode()))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        primary.kill()
        primary.wait(timeout=10)
        standby.stop()

    for rank in (0, 1):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        assert "RESET size 2" in out, (rank, out)
        assert "FINAL_EPOCH 1" in out, (rank, out)
        assert "DONE" in out, (rank, out)
        # All 8 steps completed despite losing a rank AND the primary
        # KV: the replayed step ran over the 2-rank world.
        steps = [(int(m.group(1)), float(m.group(2)))
                 for m in re.finditer(r"STEP (\d+) ([\d.]+)", out)]
        kept = dict(steps)
        assert sorted(kept) == list(range(total)), steps
