#!/usr/bin/env python3
"""The control of the correctness check: the reference with one step a
later change might take.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] \\
        [--seconds 30] [--per-client 40]

For each seed it draws the requests a run of the cell compares (the same
sample, from the same seed), answers them with the plain reference once as
it is and once as the cell file's ``compare.control`` says: ``{"prec":
"bf16"}`` rounds every stored value, intermediate and result to bfloat16
(the nearest precision below the float32 the program stores); ``{"key_bytes":
k}`` matches keys by their first ``k`` bytes, as a fixed-width packed key
would, breaking the configuration's exact-read guarantee.  It prints the
numbers the run's check reads — ``wrong_entries`` and ``max_rel_gap`` —
for the control's answers against the reference's.  A
limit is sound only if the control fails it.  A closed loop's clients
are taken ``--per-client`` requests deep (about what a window completes);
an ingest read is answered at the state with the fewest batches it may
see.  Needs no chip: the reference runs on the host.
"""
import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def readings(checkout: Path, workload: str, seed: int, seconds: float,
             per_client: int) -> dict:
    import numpy as np

    import harness
    import traffic
    from reference import Table, answer, compare_answer, ingest_candidates
    spec = harness.load_cell(checkout, workload)
    cfg = spec["config"]
    gen = harness.load_module(spec["root"] / "data" /
                              f"{cfg['generator']}.py", "control_gen")
    data = gen.generate(cfg, seed)
    streams = harness.number_streams(traffic.build_streams(
        spec["mix"], data["ctx"], seed, seconds,
        rate=spec["cell"].get("rate_per_s")))
    keep = harness.sample_ids(streams, spec["cell"], seed)
    todo = []
    for st in streams:
        by_id = dict(zip(st["ids"], st["descs"]))
        ids = st["ids"] if st["loop"] == "open" else \
            [i for c in st["clients"] for i in c[:per_client]]
        todo += [(i, by_id[i]) for i in ids if i in keep]
    tables, lows = {}, {}
    ing = spec["cell"].get("ingest")
    ctl = spec["cell"]["compare"]["control"]
    for name in harness.tables_used(spec["mix"]):
        t = data["tables"][name]
        tables[name] = Table(t["rows"], t["cols"], t["vals"], t["aggregate"])
        lows[name] = Table(t["rows"], t["cols"], t["vals"], t["aggregate"],
                           **ctl)
    batches = []
    if ing:
        w = next(c for st in spec["mix"]["streams"] for c in st["classes"]
                 if c["op"] == "ingest")
        wspec = {"batch": w["batch"], "vals": w["vals"],
                 "key_hi": data["ctx"]["key_universe"] + w["key_extra"]}
        batches = [traffic.ingest_batch(seed, i, wspec) for i in range(64)]
    wrong, gap, n = 0, 0.0, 0
    for rid, q in todo:
        if q["op"] == "ingest":
            continue
        if ing and q["table"] == ing["table"]:
            j = int(np.random.default_rng([seed, rid]).integers(65))
            (_, want), = ingest_candidates(tables[q["table"]], batches, q,
                                           j, j)
            (_, low), = ingest_candidates(lows[q["table"]], batches, q, j,
                                          j, prec=ctl.get("prec", "f64"))
        else:
            want, low = answer(tables, q), answer(lows, q)
        w_, g_ = compare_answer(_as_served(low), want)
        wrong += w_
        gap = max(gap, g_)
        n += 1
    return {"seed": seed, "wrong_entries": wrong, "max_rel_gap": gap,
            "answers_compared": n}


def _as_served(ans) -> dict:
    """A reference answer in the shape the server sends."""
    kind = ans[0]
    if kind == "scalar":
        return {"kind": "scalar", "val": ans[1]}
    if kind == "vector":
        return {"kind": "vector", "n": len(ans[1]),
                "vals": [float(x) for x in ans[1]]}
    keys = list(ans[1])
    return {"kind": "triples", "nnz": len(keys),
            "rows": [k[0] for k in keys], "cols": [k[1] for k in keys],
            "vals": [ans[1][k] for k in keys], "truncated": False}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--per-client", type=int, default=40)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT / "bench"))
    for seed in args.seeds:
        print(json.dumps(readings(CHECKOUT, args.workload, seed,
                                  args.seconds, args.per_client)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
