"""Hierarchical failure-isolated control plane
(docs/fault_tolerance.md "Hierarchical control plane, fencing, and
quorum"):

* tree planning units — per-host sub-coordinators from the block
  topology, fan-out caps, the single-host byte-identical-to-seed pin,
  and the HVD_CTRL_TREE kill-switch;
* the ctrl_sim scale harness (the 256-rank proof);
* sub-coordinator SIGKILL on a 3-host/9-rank gang — children re-parent
  to the root, only the dead rank is evicted, SUBCOORD_REPARENT lands
  on the timeline and in the blackbox ring;
* chaos at the new ``ctrl.subcoord.send`` / ``ctrl.reparent`` sites;
* epoch fencing end-to-end (typed FencedError on the zombie) and the
  elastic quorum gate (PARTITION_MINORITY self-termination).

Multi-process scenarios ride the tests/test_chaos.py harness (per-rank
loopback-mesh subprocesses, stdout markers, exit codes as contract).
"""

import json
import re

import pytest

from test_chaos import HEARTBEAT_ENV, _steps, run_chaos

from horovod_tpu import ctrl_sim
from horovod_tpu.common import fault_injection as fi
from horovod_tpu.elastic.run import quorum_lost
from horovod_tpu.runtime_py import PyEngine
from horovod_tpu.telemetry import registry as tmx


# ---------------------------------------------------------------------------
# tree planning (in-process, no engine)
# ---------------------------------------------------------------------------


class _Topo:
    """Just enough engine surface for PyEngine._plan_tree."""

    def __init__(self, rank, size, local_size, fanout=0, block=True):
        self.rank = rank
        self.size = size
        self.local_size = local_size
        self.cross_size = max(1, size // local_size)
        self.ctrl_fanout = fanout
        self._block = block

    def hierarchical_topology_ok(self):
        return self._block


def _plan(rank, size, local_size, **kw):
    return PyEngine._plan_tree(_Topo(rank, size, local_size, **kw))


def test_plan_tree_three_hosts():
    # 9 ranks on 3 hosts of 3: hosts 1 and 2 get sub-coordinators 3 and
    # 6; the root's own host stays direct (a sub-coordinator between
    # processes on the root's host would add a hop for nothing).
    parent, children, route = _plan(0, 9, 3)
    assert parent is None and children == []
    assert route == {4: 3, 5: 3, 7: 6, 8: 6}
    assert _plan(3, 9, 3) == (None, [4, 5], {4: 3, 5: 3, 7: 6, 8: 6})
    assert _plan(4, 9, 3)[0] == 3
    assert _plan(8, 9, 3)[0] == 6
    assert _plan(1, 9, 3) == (None, [], {4: 3, 5: 3, 7: 6, 8: 6})


def test_plan_tree_fanout_cap():
    # HVD_CTRL_FANOUT=1: each sub-coordinator folds at most one child;
    # overflow ranks (5, 8) fall back to the direct star.
    parent, children, route = _plan(3, 9, 3, fanout=1)
    assert children == [4]
    assert route == {4: 3, 7: 6}
    assert _plan(5, 9, 3, fanout=1)[0] is None


def test_plan_tree_single_host_is_seed_star():
    # The pin from the issue: single-host gangs run the seed star
    # byte-identical — no parents, no children, no routes, anywhere.
    for rank in range(4):
        assert _plan(rank, 4, 4) == (None, [], {})
    assert _plan(1, 2, 1) == (None, [], {})      # local_size 1: flat too


def test_plan_tree_requires_block_layout():
    assert _plan(4, 9, 3, block=False) == (None, [], {})


def test_plan_tree_kill_switch(monkeypatch):
    monkeypatch.setenv("HVD_CTRL_TREE", "0")
    assert _plan(4, 9, 3) == (None, [], {})
    monkeypatch.setenv("HVD_CTRL_TREE", "1")
    assert _plan(4, 9, 3)[0] == 3


# ---------------------------------------------------------------------------
# quorum predicate (elastic/run.py)
# ---------------------------------------------------------------------------


def test_quorum_strict_majority():
    assert not quorum_lost(3, {2})          # 2/3 alive: re-form
    assert quorum_lost(3, {1, 2})           # 1/3 alive: minority
    assert quorum_lost(5, {0, 1, 2})        # 2/5 alive: minority
    assert not quorum_lost(5, {3, 4})       # 3/5 alive: re-form


def test_quorum_even_split_rank0_breaks_the_tie():
    # An exact half re-forms only on the side still holding old rank 0
    # — the seed behavior (2-rank gang, rank 1 dies, survivor re-forms
    # to 1) is preserved, and two live halves can never both win.
    assert not quorum_lost(2, {1})
    assert quorum_lost(2, {0})
    assert not quorum_lost(4, {2, 3})
    assert quorum_lost(4, {0, 1})


# ---------------------------------------------------------------------------
# ctrl_sim: the in-process scale harness
# ---------------------------------------------------------------------------


def test_ctrl_sim_star_and_tree_cycles():
    star = ctrl_sim.simulate(8, mode="star", cycles=6, warmup=2)
    assert len(star) == 6 and all(s > 0 for s in star)
    tree = ctrl_sim.simulate(16, mode="tree", cycles=6, warmup=2,
                             local_size=4)
    assert len(tree) == 6 and all(s > 0 for s in tree)
    with pytest.raises(ValueError):
        ctrl_sim.simulate(8, mode="ring")
    with pytest.raises(ValueError):
        ctrl_sim.simulate(1)


def test_ctrl_sim_curve_exports_headline_and_observes_metric():
    tmx.configure(True)
    try:
        curve = ctrl_sim.run_curve(sizes=(8, 16), cycles=4, local_size=4)
        assert curve["coordination_cycle_p50_us"] == \
            curve["ctrl_cycle_tree_p50_us_16"]
        for mode in ("star", "tree"):
            for size in (8, 16):
                assert curve[f"ctrl_cycle_{mode}_p50_us_{size}"] > 0
        hists = tmx.snapshot()["histograms"]
        series = [k for k in hists
                  if k.startswith("hvd_ctrl_cycle_seconds")]
        assert any('ranks="16"' in k for k in series), series
        assert sum(hists[k]["count"] for k in series) >= 8
    finally:
        tmx.configure(False)


@pytest.mark.slow
def test_ctrl_sim_256_rank_tree_beats_star():
    """The acceptance proof at full scale: 256 in-process ranks, the
    hierarchical tree's p50 under the flat star's, reproducible as a
    test.  (Median of three runs per mode to shrug off scheduler
    noise on shared CI hosts.)"""
    import statistics

    def p50(mode):
        runs = [statistics.median(
            ctrl_sim.simulate(256, mode=mode, cycles=20, warmup=5))
            for _ in range(3)]
        return statistics.median(runs)

    star, tree = p50("star"), p50("tree")
    assert tree < star, (tree, star)


# ---------------------------------------------------------------------------
# sub-coordinator death: failure isolation end-to-end
# ---------------------------------------------------------------------------


def _tree_line(out):
    m = re.search(r"TREE rank=(\d+) parent=(\S+) orphaned=(\S+) "
                  r"reparented=(\[.*?\]) bb_reparent=(\S+)", out)
    assert m, out
    return {"rank": int(m.group(1)), "parent": m.group(2),
            "orphaned": m.group(3) == "True",
            "reparented": json.loads(m.group(4)),
            "bb_reparent": m.group(5) == "True"}


def test_subcoord_sigkill_children_reparent_only_victim_evicted(tmp_path):
    """3 hosts x 3 ranks; the host-1 sub-coordinator (rank 3) dies
    SIGKILL-style after step 2.  Its children (4, 5) re-parent to the
    root and ride on: the in-flight step completes over the survivors,
    the eventual RanksFailedError names ONLY the dead rank — no
    COLLECTIVE_ABORT, no gang-wide teardown — and SUBCOORD_REPARENT is
    on the root's timeline with subcoord.reparent in the blackbox
    rings on both ends."""
    np_, victim = 9, 3
    tl = tmp_path / "root-timeline.json"
    plan = json.dumps({"faults": [
        {"site": "train.step", "kind": "kill", "after": 2}]})
    outs = run_chaos(
        "tree_subcoord_steps", np_, local_size=3,
        base_env=HEARTBEAT_ENV,
        rank_env={victim: {fi.ENV_VAR: plan},
                  0: {"HVD_TIMELINE": str(tl)}},
        timeout=180)

    v_code, v_out, v_err = outs[victim]
    assert v_code == 137, (v_code, v_out, v_err)
    assert dict(_steps(v_out))[2] == 9.0

    for rank in range(np_):
        if rank == victim:
            continue
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        # Failure isolation: the error names the dead sub-coordinator
        # and NOBODY else — without re-parenting, 4 and 5 would be
        # dragged down with their parent.
        assert f"RANKS_FAILED [{victim}]" in out, (rank, out)
        assert "COLLECTIVE_ABORT" not in out + err, (rank, out, err)
        assert "ELASTIC_REFORM" not in out + err, (rank, out, err)
        steps = dict(_steps(out))
        assert steps[2] == 9.0                       # full gang pre-kill
        # The in-flight fused step completed over the survivor group.
        assert any(v == 8.0 for s, v in steps.items() if s >= 3), steps

    for child in (4, 5):
        t = _tree_line(outs[child][1])
        assert t["orphaned"], outs[child][1]
        assert t["bb_reparent"], outs[child][1]
    root = _tree_line(outs[0][1])
    assert root["reparented"] == [4, 5], outs[0][1]
    assert root["bb_reparent"], outs[0][1]
    # Ranks still routed through the LIVE sub-coordinator never moved.
    for steady in (7, 8):
        t = _tree_line(outs[steady][1])
        assert not t["orphaned"] and t["parent"] == "6", outs[steady][1]
    assert "SUBCOORD_REPARENT" in tl.read_text()


def test_chaos_subcoord_send_fault_isolated_to_that_host():
    """Chaos at ``ctrl.subcoord.send``: the sub-coordinator's TREE_UP
    send fails (injected wire error).  The sub-coordinator aborts as a
    lost-coordinator, its children re-parent, and the survivors get a
    RanksFailedError naming only the victim — the same isolation
    contract as a SIGKILL, reached through the send path.  The fault is
    cycle-armed, so under load it can land while step-0 frames are
    still in flight inside the dying parent; the bounded collective is
    the documented net for that completion race (the verdict may then
    also name a child that never got its replay out, so the failed set
    is asserted as a victim-containing subset of the victim's host)."""
    np_, victim = 6, 3
    plan = json.dumps({"faults": [
        {"site": "ctrl.subcoord.send", "kind": "error",
         "times": 1, "after": 2}]})
    outs = run_chaos(
        "tree_subcoord_steps", np_, local_size=3,
        base_env=dict(HEARTBEAT_ENV, HVD_COLLECTIVE_TIMEOUT="8"),
        rank_env={victim: {fi.ENV_VAR: plan}},
        timeout=180)

    v_code, v_out, v_err = outs[victim]
    assert v_code == 17, (v_code, v_out, v_err)

    # The other host and the root are never dragged down.
    for rank in (0, 1, 2):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        m = re.search(r"RANKS_FAILED (\[[^\]]*\])", out)
        assert m, (rank, out)
        failed = set(json.loads(m.group(1)))
        assert victim in failed and failed <= {3, 4, 5}, (rank, out)
    # The victim's children re-parent and ride on (exit 0, orphaned);
    # if the completion race resolved through the bounded-collective
    # verdict instead, a child whose replay lost may exit as a lost
    # coordinator (17) — never anything in between.
    for child in (4, 5):
        code, out, err = outs[child]
        assert code in (0, 17), (child, code, out, err)
        if code == 0:
            assert _tree_line(out)["orphaned"], out


def test_chaos_reparent_fault_child_falls_back_to_abort():
    """Chaos at ``ctrl.reparent``: the orphan's adoption announcement
    itself fails.  With no path left to the root the child must abort
    as a lost-coordinator (exit 17), not hang — and the rest of the
    gang rides on, evicting the dead pair.  (Which eviction round
    catches the silent orphan — the heartbeat sweep after the orphan
    grace expires, or the bounded-collective verdict — is a timing
    race, so the survivors' failed set is asserted as a subset.)"""
    np_, subcoord, orphan = 6, 3, 4
    kill = json.dumps({"faults": [
        {"site": "train.step", "kind": "kill", "after": 2}]})
    wedge = json.dumps({"faults": [
        {"site": "ctrl.reparent", "kind": "error"}]})
    outs = run_chaos(
        "tree_subcoord_steps", np_, local_size=3,
        base_env=dict(HEARTBEAT_ENV, HVD_COLLECTIVE_TIMEOUT="8"),
        rank_env={subcoord: {fi.ENV_VAR: kill},
                  orphan: {fi.ENV_VAR: wedge}},
        timeout=180)

    assert outs[subcoord][0] == 137, outs[subcoord]
    o_code, o_out, o_err = outs[orphan]
    assert o_code == 17, (o_code, o_out, o_err)
    # Rank 5's reparent went through; survivors evict from {3, 4} only
    # and keep running — nobody else gets dragged down.
    for rank in (0, 1, 2, 5):
        code, out, err = outs[rank]
        assert code == 0, (rank, out, err)
        m = re.search(r"RANKS_FAILED (\[[^\]]*\])", out)
        assert m, (rank, out, err)
        failed = set(json.loads(m.group(1)))
        assert failed and failed <= {subcoord, orphan}, (rank, out)
    assert _tree_line(outs[5][1])["orphaned"], outs[5][1]


# ---------------------------------------------------------------------------
# epoch fencing: the control-plane half (KV half in test_kv_failover)
# ---------------------------------------------------------------------------


def test_stale_epoch_rank_draws_typed_fence():
    """A rank that boots believing a stale elastic epoch (the zombie
    shape: evicted, paused, resumed) sends one negotiation frame, draws
    TAG_FENCE, and its submitted collective raises the *typed*
    FencedError.  The up-to-date coordinator just evicts it on
    heartbeat silence — epoch.fence in its blackbox, no gang abort."""
    outs = run_chaos(
        "fence_stale_epoch", 2,
        base_env=HEARTBEAT_ENV,
        rank_env={0: {"HVD_ELASTIC_EPOCH": "3"},
                  1: {"HVD_ELASTIC_EPOCH": "1"}},
        timeout=120)

    z_code, z_out, z_err = outs[1]
    assert z_code == 0, (z_code, z_out, z_err)
    assert "FENCED rank=1 stale=1 current=3" in z_out, (z_out, z_err)

    c_code, c_out, c_err = outs[0]
    assert c_code == 0, (c_code, c_out, c_err)
    # The coordinator either completed the in-flight step over the
    # survivor group (itself) after evicting the zombie, or hit the
    # typed eviction error — both isolate the gang; in both the fence
    # must be on its blackbox ring.
    m = re.search(r"(SURVIVED rank=0 sum=1\.0|RANKS_FAILED \[1\]) "
                  r"fences=(\d+)", c_out)
    assert m, (c_out, c_err)
    assert int(m.group(2)) >= 1       # epoch.fence hit the blackbox
    assert "FENCED" not in c_out, c_out


# ---------------------------------------------------------------------------
# quorum: minority partitions self-terminate
# ---------------------------------------------------------------------------


def _run_elastic_quorum(np_, kill_ranks, min_np=1, quorum="1"):
    from test_elastic import run_elastic

    plan = json.dumps({"faults": [
        {"site": "train.step", "kind": "kill", "after": 2}]})
    return run_elastic(
        np_, min_np=min_np, max_np=np_,
        base_env={"ELASTIC_TOTAL_STEPS": "8", "HVD_QUORUM": quorum},
        rank_env={r: {fi.ENV_VAR: plan} for r in kill_ranks})


def test_elastic_minority_self_terminates_partition_minority():
    """2 of 3 members die at the same step: the lone survivor holds no
    strict majority of the last-committed roster and must refuse to
    re-form (PARTITION_MINORITY), even though min_np would allow a
    1-rank gang — a real partition would have the other side re-forming
    the same scope."""
    outs = _run_elastic_quorum(3, kill_ranks=(1, 2))
    for r in (1, 2):
        assert outs[r][0] == 137, outs[r]
    code, out, err = outs[0]
    assert code != 0, (code, out, err)
    assert "PARTITION_MINORITY" in out + err, (out, err)
    assert "RESET size" not in out, out      # no re-form happened
    assert "DONE" not in out, out


def test_elastic_majority_reforms_and_finishes():
    """The flip side on the same harness: 1 of 3 dies, the 2/3 majority
    passes the quorum gate, re-forms, and trains to completion."""
    outs = _run_elastic_quorum(3, kill_ranks=(2,), min_np=2)
    assert outs[2][0] == 137, outs[2]
    for r in (0, 1):
        code, out, err = outs[r]
        assert code == 0, (r, out, err)
        assert "PARTITION_MINORITY" not in out + err, (out, err)
        assert "RESET size 2" in out, out
        assert "DONE" in out, out


def test_quorum_kill_switch_restores_seed_behavior():
    """HVD_QUORUM=0: the pre-quorum contract — min_np is the only
    floor, so the lone survivor of a 3->1 collapse re-forms and
    finishes alone."""
    outs = _run_elastic_quorum(3, kill_ranks=(1, 2), quorum="0")
    code, out, err = outs[0]
    assert code == 0, (code, out, err)
    assert "PARTITION_MINORITY" not in out + err
    assert "RESET size 1" in out, out
    assert "DONE" in out, out
