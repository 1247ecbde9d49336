"""Telemetry lint as a test: every metric name emitted in the package
must be declared in ``telemetry.registry.KNOWN_METRICS``, and every
registered metric must appear in the docs/metrics.md table
(tools/check_metric_docs.py — the same three-way contract as
tests/test_fault_sites.py)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import check_metric_docs  # noqa: E402


def test_registry_is_nontrivial():
    known = check_metric_docs.registry()
    assert "hvd_cycles_total" in known
    assert "hvd_collectives_total" in known
    assert "hvd_straggler_skew_seconds" in known
    for name, spec in known.items():
        assert spec["kind"] in ("counter", "gauge", "histogram"), name
        assert spec["help"], name


def test_scan_finds_real_call_sites():
    used = check_metric_docs.used_literals()
    # Engine, collective, robustness, and straggler layers all show up.
    assert "hvd_cycles_total" in used
    assert "hvd_collectives_total" in used
    assert "hvd_kv_retries_total" in used
    assert "hvd_nonfinite_skips_total" in used
    assert "hvd_straggler_skew_seconds" in used


def test_every_used_metric_is_declared():
    undecl = check_metric_docs.undeclared_metrics()
    assert not undecl, (
        f"undeclared metrics: {undecl} — add them to KNOWN_METRICS "
        "(see tools/check_metric_docs.py)")


def test_every_registered_metric_is_documented():
    undoc = check_metric_docs.undocumented_metrics()
    assert not undoc, (
        f"undocumented metrics: {undoc} — add them to the table in "
        "docs/metrics.md")


def test_every_declared_share_is_of_two_counters_and_documented(tmp_path):
    assert check_metric_docs.bad_shares() == []
    assert check_metric_docs.bad_shares(tmp_path / "n.md") == [
        "attn_read_share", "attn_selected_share", "state_live_share"]


def test_every_alert_rule_is_documented():
    rules = check_metric_docs.alert_rules()
    assert "throughput_collapse" in rules
    assert "straggler_skew" in rules
    undoc = check_metric_docs.undocumented_alert_rules()
    assert not undoc, (
        f"undocumented alert rules: {undoc} — add them to the rule "
        "table in docs/metrics.md (Gang-wide aggregation & alerts)")


def test_missing_doc_file_reports_every_alert_rule(tmp_path):
    undoc = check_metric_docs.undocumented_alert_rules(tmp_path / "n.md")
    assert undoc == sorted(check_metric_docs.alert_rules())


def test_undeclared_scan_on_synthetic_tree(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "from horovod_tpu.telemetry import registry as _tmx\n"
        "_tmx.inc_counter('no_such_metric_total')\n"
        "_tmx.observe('hvd_cycle_duration_seconds', 0.1)\n"
        "_tmx.inc_counter(f'hvd_{kind}_total')\n"  # computed: invisible
    )
    undecl = check_metric_docs.undeclared_metrics(pkg)
    assert list(undecl) == ["no_such_metric_total"]


def test_missing_doc_file_reports_everything(tmp_path):
    undoc = check_metric_docs.undocumented_metrics(tmp_path / "nope.md")
    assert undoc == sorted(check_metric_docs.registry())
