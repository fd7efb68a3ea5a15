"""The harness's memory rule at size, on one TPU chip and by hand:

    python3 tests/perf_yardstick/toy/run_wide.py --seed <n> [--seconds 5]

Drives `bigram_wide.fed` (BENCHMARK.wide.json: the toy's program with two
tables of 65,536 x 4,096, 537 M parameters, 8.6 GB at 16 B each) through
`harness.run_cell` as `perf/run.py` drives a cell of record, and prints the
result line. A harness that keeps a parameter-sized copy beside the program
or the reference runs out of memory here; one that keeps the rule at the top
of perf/harness.py comes out `correct` with `memory_peak_bytes` under 11e9.
No test runs it and it reports no number anywhere.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

TOY = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(TOY)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from perf import harness

    program = harness.load_file(os.path.join(TOY, "program.py"))
    result, code = harness.run_cell(
        ROOT, os.path.join(TOY, "BENCHMARK.wide.json"), "bigram_wide.fed", args.seed, args.seconds, False,
        t_start=_T_START, program=(program.get_config, program.Trainer),
    )
    if result is not None:
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
