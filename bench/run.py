#!/usr/bin/env python3
"""Chip benchmark of the RAMA multicut solve.

    python3 bench/run.py --workload grid256_pd --seed 7 --seconds 45 \\
        --trace 0

Runs one cell of ``BENCHMARK.json`` on the machine it is started on: it
needs as many TPU chips as the cell asks for, and exits non-zero with no
result line on anything else. Set-up makes the cell's instances from
``--seed`` on the host and warms the one executable up; the window then
solves the instances in turn, each from host arrays to its answer on the
host, until ``--seconds`` have passed. Every answer is checked against
the plain host reference (``bench/check.py``). The last line of stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the end-to-end metrics, or with ``--trace 1`` the per-layer ones read
from a device trace of the first pass over the instances), ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number
compared, with its limit. Progress and the checks go to stderr.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime logs to a fixed directory under /tmp unless told
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
