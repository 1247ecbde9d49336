"""The reduction from a profiler trace to numbers: the interval arithmetic
on hand-made events, and the whole of it on small traces recorded on the
chip (``data/``: a scan of matmuls and one attention kernel call, four
fenced calls; on four chips the same with an all-reduce between matmuls)."""

from pathlib import Path

import pytest

from perfbench import trace as tr
from perfbench.trace import Event

DATA = Path(__file__).resolve().parent / "data"


def test_union_total_and_subtract():
    u = tr.union([(0, 10), (5, 20), (30, 40), (40, 45), (7, 8)])
    assert u == [(0, 20), (30, 45)]
    assert tr.total(u) == 35
    assert tr.subtract([(0, 100)], u) == [(20, 30), (45, 100)]
    assert tr.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]


def test_self_time_takes_a_loop_body_out_of_the_loop():
    evs = [Event("%while = () while()", 0, 100),
           Event("%a = f32[] fusion()", 10, 40),
           Event("%b = f32[] fusion()", 50, 90),
           Event("%c = f32[] copy()", 100, 120)]
    assert tr.self_times(evs) == [(evs[0].name, 30.0), (evs[1].name, 30.0),
                                  (evs[2].name, 40.0), (evs[3].name, 20.0)]


def test_names():
    name = ("%copy.74 = bf16[8,32,2048,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[8,32,2048,16,128]{4,3,2,1,0} %p)")
    assert tr.opcode(name) == "copy"
    assert tr.short_name(name) == "copy.74_copy_bf16_8_32_2048_16_128_"
    assert tr.is_collective("%all-reduce.1 = f32[4]{0} all-reduce(f32[4] %x)")
    assert tr.is_collective(
        "%ar = (f32[4]) all-reduce-start(f32[4]{0} %x), replica_groups={}")
    assert not tr.is_collective(name)


def _toy(ops, async_ops=(), host=None):
    return tr.Trace({0: list(ops)}, {0: list(async_ops)}, {0: []},
                    host or {})


def test_busy_idle_exposed_collective_and_gaps_on_hand_made_events():
    ar = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %g)"
    ops = [Event("%f = f32[] fusion()", 0, 40),
           Event(ar, 30, 70),                      # 10 hidden, 30 exposed
           Event("%g = f32[] fusion()", 90, 100)]
    host = {"main": [Event("bench:trace_window", 0, 100),
                     Event("bench:fence", 68, 92),
                     Event("TransferFromDevice", 72, 88)]}
    t = _toy(ops, host=host)
    w = tr.traced_window(t)
    assert w == (0, 100)
    b = tr.busy(t, w)
    assert b["busy_s"][0] == pytest.approx(80e-9)
    assert b["idle_share"][0] == pytest.approx(0.2)
    c = tr.collectives(t, 0, w)
    assert c["seconds"] == pytest.approx(40e-9)
    assert c["exposed_seconds"] == pytest.approx(30e-9)
    assert tr.idle_gaps(t, 0, 5, w) == [
        ["bench:fence_TransferFromDevice", pytest.approx(20e-9)]]
    secs, n = tr.op_seconds(t, 0, tr.is_collective, w)
    assert (secs, n) == (pytest.approx(40e-9), 1)


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "small_1chip.xplane.pb.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    return tr.load(str(path))


def test_recorded_trace_busy_idle_and_kernel_time(recorded):
    t = recorded
    assert sorted(t.ops) == [0] and len(t.modules[0]) == 4
    w = tr.traced_window(t)
    assert w is None        # recorded before the window annotation existed
    b = tr.busy(t)
    span = b["window_s"]
    assert 0 < b["busy_s"][0] < span
    assert b["idle_share"][0] == pytest.approx(1 - b["busy_s"][0] / span)
    # busy is the four programs' time: each 'XLA Modules' event is busy
    # from its first to its last op, to within the gaps between ops
    modules = sum(e.end - e.start for e in t.modules[0]) * 1e-9
    assert b["busy_s"][0] == pytest.approx(modules, rel=0.02)
    # the attention kernel: one Mosaic custom call a program
    ks, calls = tr.op_seconds(t, 0, tr.is_mosaic_call)
    assert calls == 4
    by_hand = sum(e.end - e.start for e in t.ops[0]
                  if "tpu_custom_call" in e.name) * 1e-9
    assert ks == pytest.approx(by_hand)
    assert ks == pytest.approx(4 * 46.75e-6, rel=0.02)
    # the scan's while loop is not the top op: its body's fusions are
    top = tr.top_ops(t, 0, 3)
    assert top[0][0].startswith("fusion.13_fusion_bf16_1024_1024_")
    assert top[0][1] == pytest.approx(4 * 6 * 14.92e-6, rel=0.02)
    assert all("while" not in name for name, _ in top)
    # self times add up to busy time (nothing nested is counted twice)
    assert sum(d for _, d in tr.self_times(t.ops[0])) * 1e-9 == \
        pytest.approx(b["busy_s"][0], rel=0.01)
    gaps = tr.idle_gaps(t, 0, 5)
    assert [g[1] > 5e-3 for g in gaps] == [True] * 3 + [False] * 2  # sleeps


def test_recorded_four_chip_trace_has_an_exposed_all_reduce():
    path = DATA / "small_4chip.xplane.pb.gz"
    if not path.exists():
        pytest.skip("no recorded four-chip trace")
    t = tr.load(str(path))
    assert sorted(t.ops) == [0, 1, 2, 3]
    for chip in range(4):
        c = tr.collectives(t, chip)
        assert c["count"] >= 4
        assert 0 < c["exposed_seconds"] <= c["seconds"]
