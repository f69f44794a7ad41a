#!/usr/bin/env python3
"""Find the highest open-loop rate a cell's server sustains.

    python3 bench/sweep.py --workload paper18.select --seed <n> \\
        --seconds 20 --rates 10 20 40 80

One process builds the tables once and drives one window per rate, each
from a fresh load generator.  For each rate it prints the completion
rate, the median and 95th-percentile latency, and the medians of the
window's first and last thirds: a backlog that grows shows as a last
third far slower than the first.  The cell's ``rate_per_s`` is then set
by hand to about four fifths of the highest rate without a growing
backlog.  Needs the chip, like ``run.py``.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def summarize(w: dict, rate: float) -> dict:
    recs = [r for r in w["records"] if r["desc"]["op"] != "ingest"]
    ok = [r for r in recs if r.get("status") == 200]
    lat = sorted(r["done"] - r["due"] for r in ok)
    third = w["seconds"] / 3.0

    def med(lo, hi):
        xs = [r["done"] - r["due"] for r in ok
              if lo <= r["due"] - w["t0"] < hi]
        return statistics.median(xs) if xs else None

    return {"rate": rate, "sent": len(recs), "failed": len(recs) - len(ok)
            + len(w["lost"]),
            "completed_per_s": sum(r["done"] <= w["t_close"] for r in ok)
            / w["seconds"],
            "p50_s": lat[len(lat) // 2] if lat else None,
            "p95_s": lat[int(0.95 * (len(lat) - 1))] if lat else None,
            "first_third_p50_s": med(0, third),
            "last_third_p50_s": med(2 * third, 3 * third),
            "window_compiles": w["compiles"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(CHECKOUT / "bench"))
    import harness
    try:
        cell = harness.Cell(CHECKOUT, args.workload, args.seed)
    except harness.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 3
    try:
        for rate in args.rates:
            w = cell.window(args.seconds, False, t_start=T_START, rate=rate)
            print(json.dumps(summarize(w, rate)), flush=True)
    finally:
        cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
