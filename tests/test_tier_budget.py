"""The fast tier polices its own wall-time budget (ISSUE PR-2 satellite).

The driver hard-kills the tier-1 run (6 xdist workers, a file whole on one
worker) at 1470 s; a PR that adds one more compiling test too many makes
the WHOLE tier read as broken. `benchmarks/tier_budget_audit.py` banks
measured per-test durations; the audit test here projects the cost of the
live fast-tier collection against that bank and fails while the offending PR is still
open — rebalance markers (or shrink configs) and re-bank instead of
silently timing out later.
"""

import importlib.util
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_audit():
    spec = importlib.util.spec_from_file_location(
        "tier_budget_audit",
        os.path.join(_REPO, "benchmarks", "tier_budget_audit.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


audit = _load_audit()


class TestParsing:
    def test_parse_durations_sums_phases(self):
        log = """
============================= slowest durations ==============================
12.00s call     tests/test_a.py::test_x
0.50s setup    tests/test_a.py::test_x
0.25s teardown tests/test_a.py::test_x
3.00s call     tests/test_b.py::TestC::test_y
(0.00 durations hidden.  Use -vv to show these durations.)
"""
        out = audit.parse_durations(log)
        assert out == {
            "tests/test_a.py::test_x": 12.75,
            "tests/test_b.py::TestC::test_y": 3.0,
        }

    def test_parse_ignores_non_duration_noise(self):
        out = audit.parse_durations("...\nPASSED\n1.5x not a row\n")
        assert out == {}

    def test_project_wall_charges_unknown_tests(self):
        banked = {"t::a": 10.0, "t::b": 5.0}
        rep = audit.project_wall(["t::a", "t::b", "t::new"], banked, default_s=2.0)
        assert rep["projected_s"] == 17.0
        assert rep["banked_s"] == 15.0
        assert rep["n_known"] == 2
        assert rep["n_unknown"] == 1
        assert rep["unknown_ids"] == ["t::new"]

    def test_project_wall_gives_a_file_whole_to_the_least_loaded_worker(self):
        # --dist loadfile: a.py (10 s) on one worker, b.py then c.py on the other
        banked = {"a.py::x": 6.0, "a.py::y": 4.0, "b.py::x": 3.0, "c.py::x": 5.0}
        rep = audit.project_wall(list(banked) + ["c.py::new"], banked, default_s=2.0, workers=2)
        assert rep["projected_s"] == 10.0
        assert rep["serial_s"] == 20.0
        assert audit.project_wall(list(banked), banked, workers=1)["projected_s"] == 18.0

    def test_audit_report_verdicts(self):
        record = {"durations": {"t::a": 800.0}, "measured": "2026-01-01"}
        over = audit.audit_report(["t::a", "t::new"], record, budget_s=801.0)
        assert over["over_budget"] and over["margin_s"] < 0
        under = audit.audit_report(["t::a"], record, budget_s=870.0)
        assert not under["over_budget"]
        assert under["margin_s"] == 70.0


class TestLiveBudget:
    def test_fast_tier_projection_within_budget(self, request):
        """Project the CURRENT collection's fast-tier subset against the
        banked durations. Runs at zero extra cost (no subprocess, no
        timing): the session already collected the items. Under the full
        tier-1 invocation this projects the exact tier; under a partial
        run it projects that run's fast subset — a subset of the tier, so
        a pass is never a false negative for the real budget."""
        if not os.path.exists(audit.RECORD_PATH):
            pytest.skip("no banked tier_durations.json yet — run "
                        "`tier_budget_audit.py bank` on a measured log")
        bank = audit.load_bank()
        fast_ids = [
            item.nodeid
            for item in request.session.items
            if item.get_closest_marker("slow") is None
        ]
        report = audit.audit_report(fast_ids, bank)
        assert not report["over_budget"], (
            f"fast tier projected at {report['projected_s']}s exceeds the "
            f"{report['budget_s']}s tier-1 budget "
            f"({report['n_unknown']} unbanked tests charged "
            f"{audit.DEFAULT_UNKNOWN_S}s each; unknown sample: "
            f"{report['unknown_ids']}). Mark new heavy tests slow, shrink "
            "their configs, or re-bank with benchmarks/tier_budget_audit.py "
            "after a deliberate rebalance."
        )
