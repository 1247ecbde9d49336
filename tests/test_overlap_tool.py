"""Parser tests for tools/measure_overlap.py — the real capture needs a
chip, so the schedule-walk is pinned here against hand-written
scheduled-HLO shapes (async pairs, variadic sync all-reduce, consumer
lines that must NOT count as collectives)."""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from measure_overlap import _ring_bytes, shape_bytes, measure  # noqa: E402
import device_peaks  # noqa: E402  (measure_overlap put the repo root on sys.path)

V5E = device_peaks.peak("TPU v5 lite")


def test_shape_bytes():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("(f32[8]{0}, bf16[4]{0})") == 32 + 8
    assert shape_bytes("%name, metadata={}") == 0


def test_ring_bytes_start_tuple_halved():
    # -start result is an (operand, result) alias tuple: payload twice.
    rhs = " (f32[100]{0}, f32[100]{0}) all-reduce-start(%fusion.1)"
    assert _ring_bytes(rhs, "all-reduce-start") == 400
    # operand shapes win when printed
    rhs2 = " (f32[100]{0}, f32[100]{0}) all-reduce-start(f32[100]{0} %x)"
    assert _ring_bytes(rhs2, "all-reduce-start") == 400


def test_measure_async_overlap_fifo():
    """One 400-byte AR fully hidden by a big fusion; a second AR done
    immediately after start (exposed). Compute credited once, FIFO."""
    hlo = """
HloModule m
ENTRY %main () -> f32[] {
  %p = f32[100]{0} parameter(0)
  %ar1 = (f32[100]{0}, f32[100]{0}) all-reduce-start(%p)
  %big = f32[100000]{0} fusion(%p), kind=kLoop
  %d1 = f32[100]{0} all-reduce-done(%ar1)
  %ar2 = (f32[100]{0}, f32[100]{0}) all-reduce-start(%d1)
  %d2 = f32[100]{0} all-reduce-done(%ar2)
  %use = f32[100]{0} add(f32[100]{0} %d1, f32[100]{0} %d2)
}
"""
    r = measure(hlo, 8, V5E)
    assert r["async_collective_pairs"] == 2
    assert r["sync_collectives"] == 0
    # ar1 fully hidden by %big (its cost >> ar cost); ar2 has nothing
    # between start and done -> exposed.
    assert r["hidden_s_est"] > 0
    assert abs(r["overlap_fraction"] - 0.5) < 1e-9, r


def test_measure_consumers_not_counted_as_collectives():
    hlo = """
ENTRY %main () -> f32[] {
  %p = f32[154092]{0} parameter(0)
  %ar = (f32[154092]{0}, f32[8]{0}) all-reduce(%p, %q), to_apply=%add
  %g0 = f32[154092]{0} get-tuple-element(%ar), index=0
  %g1 = f32[8]{0} get-tuple-element(%ar), index=1
  %f = f32[154092]{0} fusion(f32[154092]{0} %g0), kind=kLoop
}
"""
    r = measure(hlo, 8, V5E)
    assert r["sync_collectives"] == 1
    assert r["async_collective_pairs"] == 0
    # variadic payload counted once (result tuple, not halved)
    expected = 2 * 7 / 8 * (154092 * 4 + 8 * 4) / (V5E.ici_bytes_per_s / V5E.ici_links)
    assert abs(r["total_collective_s_est"] - expected) < 1e-12


def test_measure_double_credit_impossible():
    """Two in-flight ARs + one compute instruction between them: the
    instruction's time is split across the two, never duplicated."""
    hlo = """
ENTRY %main () -> f32[] {
  %p = f32[1000]{0} parameter(0)
  %a1 = (f32[1000]{0}, f32[1000]{0}) all-reduce-start(%p)
  %a2 = (f32[1000]{0}, f32[1000]{0}) all-reduce-start(%p)
  %c = f32[10]{0} fusion(%p), kind=kLoop
  %d1 = f32[1000]{0} all-reduce-done(%a1)
  %d2 = f32[1000]{0} all-reduce-done(%a2)
}
"""
    r = measure(hlo, 8, V5E)
    # compute time is tiny (40 bytes); hidden must equal it exactly
    # (credited once), not twice.
    assert abs(r["hidden_s_est"] - 40 / V5E.hbm_bytes_per_s) < 1e-15, r


def test_measure_entry_bounded_and_non_entry_counted():
    """Instructions in computations after ENTRY must not enter the
    schedule walk; collectives in any non-entry computation are counted
    as a diagnostic (scan/while bodies hide gradient syncs there)."""
    hlo = """
HloModule m
%body (p: f32[10]) -> f32[10] {
  %p = f32[10]{0} parameter(0)
  %arb = f32[10]{0} all-reduce(%p), to_apply=%add
}
ENTRY %main () -> f32[] {
  %q = f32[10]{0} parameter(0)
  %w = f32[10]{0} while(f32[10]{0} %q), body=%body
}
%trailing (x: f32[10]) -> f32[10] {
  %x = f32[10]{0} parameter(0)
  %art = f32[10]{0} all-reduce(%x), to_apply=%add
}
"""
    r = measure(hlo, 8, V5E)
    # neither the body's nor the trailing computation's all-reduce may
    # be walked as entry traffic...
    assert r["sync_collectives"] == 0
    assert r["total_collective_s_est"] == 0.0
    # ...but both are visible in the diagnostic count.
    assert r["non_entry_collectives"] == 2
