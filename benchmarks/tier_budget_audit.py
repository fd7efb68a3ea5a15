"""Fast-tier wall-time budget accounting.

The driver runs tier 1 as ``pytest -m 'not slow' -n 6 --dist loadfile``
under a hard ``timeout 1470`` (the ``commands`` of ``/root/TESTS_LAST_RUN.json``;
ROADMAP.md's serial line under 870 s is the older form of the same run, D9).
Every PR that adds fast-tier tests eats into that headroom, and the failure
mode is brutal: the suite times out as a unit and the WHOLE tier reads as
broken. This module makes the budget a number the suite itself enforces
(see ``tests/test_tier_budget.py``) instead of a constant nobody re-checks:

1. **Bank** a measured run:  ``pytest -m 'not slow' --durations=0 -vv``
   prints per-phase (setup/call/teardown) durations; pipe the log here
   to write ``benchmarks/records/tier_durations.json``::

       python -m pytest tests/ -q -m 'not slow' --durations=0 \\
           --durations-min=0 | tee /tmp/t1.log
       python benchmarks/tier_budget_audit.py bank /tmp/t1.log

   (``merge`` in place of ``bank`` keeps the entries the log does not hold:
   for a serial log of the test files one PR touched.)

2. **Audit** a collection against the bank: project wall time as the
   driver's run would spend it. ``--dist loadfile`` gives a file whole to
   one worker, the next file to the worker that is free first, so the
   projection hands the files out in collection order to the least loaded
   of ``WORKERS`` and reads the fullest one. A test with no banked number
   is charged ``DEFAULT_UNKNOWN_S`` (new tests are assumed cheap until
   measured — the point is catching the pattern of many new compiles, not
   hiding them)::

       python benchmarks/tier_budget_audit.py audit   # exit 1 over budget

The parsing/projection functions are pure (stdlib only, no pytest, no
jax) so the fast tier can unit-test them and run the projection in-
process against its own collected items at zero subprocess cost.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RECORD_PATH = os.path.join(_REPO, "benchmarks", "records", "tier_durations.json")
SCHEMA = "tier_durations/v1"

# The driver's tier-1 timeout and worker count (its command in
# /root/TESTS_LAST_RUN.json). Projection must land UNDER the timeout with
# margin: the banked numbers are serial durations from one host state, and
# six workers contend for that host's cores (the driver's run of PR 27's
# tree took 739 s where this projection read 478), so the audit fails at the
# budget, and the margin field in reports tells you how close you are.
BUDGET_S = 1470.0
WORKERS = 6

# Charged for a collected test with no banked duration. Most unit tests
# cost milliseconds; anything that compiles a train step costs minutes
# and MUST be measured into the bank (or marked slow) — 2 s splits the
# difference loudly enough that ~30 new unbanked tests ring the alarm.
DEFAULT_UNKNOWN_S = 2.0

# `--durations` line:  "  12.34s call     tests/test_x.py::test_y"
_DURATION_RE = re.compile(
    r"^\s*(?P<sec>\d+(?:\.\d+)?)s\s+(?P<phase>call|setup|teardown)\s+"
    r"(?P<id>\S+)\s*$"
)


def parse_durations(text: str):
    """{test_id: total_seconds} summed over setup+call+teardown from a
    pytest ``--durations=0`` log. Lines that are not duration rows are
    ignored, so the whole run log can be piped in unfiltered."""
    out = {}
    for line in text.splitlines():
        m = _DURATION_RE.match(line)
        if not m:
            continue
        out[m.group("id")] = out.get(m.group("id"), 0.0) + float(m.group("sec"))
    return out


def project_wall(collected_ids, banked_durations, default_s: float = DEFAULT_UNKNOWN_S,
                 workers: int = WORKERS):
    """Projected wall seconds for ``collected_ids`` plus accounting detail.

    Each file (the id up to its first ``::``) goes whole, in collection
    order, to the least loaded of ``workers``; the wall is the fullest
    worker's load. Returns a dict: projected_s, serial_s (all the tests one
    after another), banked_s (portion with measurements), n_known,
    n_unknown, unknown_ids (capped at 20 for readability)."""
    banked_s = 0.0
    unknown = []
    per_file = {}  # insertion order = collection order
    for tid in collected_ids:
        sec = banked_durations.get(tid)
        if sec is None:
            unknown.append(tid)
            sec = default_s
        else:
            banked_s += sec
        name = tid.split("::", 1)[0]
        per_file[name] = per_file.get(name, 0.0) + sec
    loads = [0.0] * max(workers, 1)
    for sec in per_file.values():
        loads[loads.index(min(loads))] += sec
    return {
        "projected_s": round(max(loads), 1),
        "serial_s": round(sum(loads), 1),
        "banked_s": round(banked_s, 1),
        "n_known": len(collected_ids) - len(unknown),
        "n_unknown": len(unknown),
        "unknown_ids": unknown[:20],
    }


def audit_report(collected_ids, banked_record, budget_s: float = BUDGET_S,
                 default_s: float = DEFAULT_UNKNOWN_S):
    """Projection + verdict against the budget. ``banked_record`` is the
    loaded tier_durations.json dict."""
    report = project_wall(
        collected_ids, banked_record.get("durations", {}), default_s
    )
    report["budget_s"] = budget_s
    report["margin_s"] = round(budget_s - report["projected_s"], 1)
    report["over_budget"] = report["projected_s"] > budget_s
    report["banked_at"] = banked_record.get("measured")
    return report


def load_bank(path: str = RECORD_PATH):
    with open(path) as f:
        return json.load(f)


def bank(log_path: str, record_path: str = RECORD_PATH, merge: bool = False) -> dict:
    """Parse a durations log and write the bank record; with ``merge`` the
    entries the log does not hold stay as they are banked."""
    with open(log_path) as f:
        durations = parse_durations(f.read())
    if merge and durations:
        durations = {**load_bank(record_path)["durations"], **durations}
    if not durations:
        raise SystemExit(
            f"tier_budget_audit: no duration rows found in {log_path} — "
            "run pytest with --durations=0 (and --durations-min=0 on "
            "pytest>=6.2 so sub-5ms rows are kept)"
        )
    record = {
        "schema": SCHEMA,
        "measured": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "n_tests": len(durations),
        "total_s": round(sum(durations.values()), 1),
        "durations": {k: round(v, 3) for k, v in sorted(durations.items())},
    }
    os.makedirs(os.path.dirname(record_path), exist_ok=True)
    tmp = f"{record_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    os.replace(tmp, record_path)
    return record


def _collect_fast_tier_ids():
    """Collected fast-tier test ids via a pytest --collect-only subprocess
    (CLI audit path; the in-suite test uses its own live collection)."""
    import subprocess

    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            "tests/",
            "-q",
            "-m",
            "not slow",
            "--collect-only",
            "-p",
            "no:cacheprovider",
        ],
        capture_output=True,
        text=True,
        cwd=_REPO,
    )
    ids = [
        line.strip()
        for line in r.stdout.splitlines()
        if "::" in line and not line.startswith(("=", "<"))
    ]
    if not ids:
        raise SystemExit(
            "tier_budget_audit: collection produced no test ids "
            f"(rc={r.returncode}):\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
        )
    return ids


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] not in ("bank", "merge", "audit"):
        print(__doc__, file=sys.stderr)
        return 2
    if argv[0] in ("bank", "merge"):
        if len(argv) < 2:
            print("usage: tier_budget_audit.py bank|merge <pytest-log>", file=sys.stderr)
            return 2
        record = bank(argv[1], merge=argv[0] == "merge")
        print(
            f"banked {record['n_tests']} tests, {record['total_s']}s total "
            f"-> {RECORD_PATH}"
        )
        return 0
    # audit
    report = audit_report(_collect_fast_tier_ids(), load_bank())
    print(json.dumps(report, indent=1))
    if report["over_budget"]:
        print(
            f"tier_budget_audit: FAIL projected {report['projected_s']}s > "
            f"budget {report['budget_s']}s — mark tests slow or shrink "
            "configs, then re-bank",
            file=sys.stderr,
        )
        return 1
    print(
        f"tier_budget_audit: OK {report['projected_s']}s projected, "
        f"{report['margin_s']}s margin",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
