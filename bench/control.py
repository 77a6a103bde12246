"""Read the control of a cell's correctness check: the numpy reference put
in the program's place, computed in the precision below the one the
configuration states (its ``control`` entry), and judged by the same
comparison as the program's answers.  Its readings must fail the limits.

    python3 bench/control.py --workload ssb_sf1.q4.1 --seeds 1 2 3

For each seed it generates the cell's tables at the configured size, makes
the answers to as many requests as a run makes (``--requests``; by default
the warm-up plus ``--window``), and prints each compared number beside its
limit.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, Optional, Tuple

ROOT = Path(__file__).resolve().parent.parent


def readings(cell, seed: int, requests: Optional[int] = None,
             window: int = 3) -> Tuple[int, Dict[str, float]]:
    """The number of requests answered and the control's worst reading of
    each compared number, for one seed.  ``requests`` defaults to the
    warm-up (every distinct input) plus ``window``."""
    from bench import check, loops, registry
    cfg = cell.config
    data = registry.generator(cfg["generator"])(cfg, seed)
    feed = loops.feed(cfg, cell.traffic, cell.flow, data)
    n = feed.inputs() + window if requests is None else requests
    ctrl = cfg["control"]
    answers = feed.reference(n, ctrl["precision"], ctrl["state"])
    expected = feed.reference(n)
    return n, check.worst(feed.compare(sorted(answers.items()), expected), 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=None,
                    help="requests to answer (default: warm-up + window)")
    ap.add_argument("--window", type=int, default=3,
                    help="window requests added to the warm-up")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check, registry

    cell = registry.cell(args.workload)
    for seed in args.seeds:
        n, numbers = readings(cell, seed, args.requests, args.window)
        checks = check.verdict(numbers, cell.config["limits"])
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "requests": n, "control_fails": not check.passed(
                              checks), "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
