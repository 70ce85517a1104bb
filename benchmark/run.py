#!/usr/bin/env python3
"""Run one benchmark cell: ``python3 benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the checkout's root.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, with ``breakdown``),
``device`` and, last, ``checks``: each number compared with its limit.
Without an accelerator it exits 2 and prints no result.
``--list`` prints every cell with the files the harness found for it.
See ``benchmark/harness.py``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)
    if args.list:
        for c in harness.list_cells():
            print(json.dumps(c))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
