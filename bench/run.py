#!/usr/bin/env python3
"""The benchmark's command: one run of one cell, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's tables from the seed, serves them over loopback HTTP
from this process (which holds the chip), warms up, drives the window
from a load-generating child process, compares what was served with the
plain reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device`` and, traced, ``breakdown``; ``checks`` (each
number compared beside its limit) comes last.  Exit 3: no TPU, or fewer
chips than the cell needs; exit 2: the cell's files or the program are
missing.  No result is printed then.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def _finite(x):
    """JSON has no infinity: a number that is not finite is written as the
    largest double (a failed request's latency)."""
    if isinstance(x, float) and not math.isfinite(x):
        return sys.float_info.max
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(CHECKOUT / "bench"))
    import harness
    try:
        out = harness.run_cell(CHECKOUT, args.workload, args.seed,
                               args.seconds, bool(args.trace),
                               t_start=T_START)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    except (harness.SpecError, ImportError) as exc:
        print(f"bench: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(_finite(out), allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
