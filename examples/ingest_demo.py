"""Dynamic ingest demo: streaming mutation with merge-on-read queries.

Boots the query server with one device-layer **ingest** table, then walks
the LSM lifecycle end to end through the HTTP client:

1. stream triple batches into ``POST /ingest`` (each batch folded once
   into the table's device delta);
2. query DURING ingest — reads see base ⊕ delta (merge-on-read): a
   selection reads base and delta apart, other queries a merged snapshot
   that repeated reads between mutations reuse;
3. wait for the background compactor to fold the delta into a new base
   (``/stats`` shows ``delta_depth`` returning to 0 and ``compactions``
   ticking up), and check reads are unchanged by compaction;
4. verify the final state against a one-shot oracle built from the
   concatenated triples — ingest order must not matter for ⊕ = sum.

    PYTHONPATH=src python examples/ingest_demo.py

Doubles as the CI ingest smoke: exits nonzero if any step misbehaves.
"""
import time

from repro.serve import D4MClient, TableRef, TableRegistry, start_server


def main() -> int:
    registry = TableRegistry.from_specs([
        {"name": "edges", "generator": "random", "n": 64, "nnz": 512,
         "seed": 0, "layer": "device", "ingest": True,
         "compact_threshold": 4096},
    ])
    server = start_server(registry, workers=2)
    print(f"serving {registry.names()} on {server.url}")

    try:
        client = D4MClient(server.url)
        assert client.health()["status"] == "ok"
        total_q = TableRef("edges").sum(axis=None)

        base_total = client.query(total_q)["result"]["val"]
        print(f"resident base: total weight {base_total:.1f}")

        # -- 1+2. stream batches, query between them ----------------------
        n_batches, bsz = 5, 32
        for b in range(n_batches):
            rows = [f"new{b}k{i:02d}" for i in range(bsz)]
            cols = [f"c{i % 4}" for i in range(bsz)]
            out = client.ingest("edges", rows, cols, [1.0] * bsz)["result"]
            live = client.query(total_q)["result"]["val"]
            print(f"batch {b}: accepted={out['accepted']} "
                  f"delta_depth={out['delta_depth']} "
                  f"live total={live:.1f}")
        want = base_total + n_batches * bsz
        assert abs(live - want) < 1e-3, (live, want)

        # -- 3. background compaction folds the delta away ----------------
        deadline = time.time() + 30
        while time.time() < deadline:
            info = client.stats()["ingest"]["edges"]
            if info["delta_depth"] == 0 and info["compactions"] >= 1:
                break
            time.sleep(0.1)
        assert info["delta_depth"] == 0, "compactor never folded the delta"
        print(f"compacted: version={info['version']} "
              f"compactions={info['compactions']} "
              f"merge_hit_rate={info['merge_hit_rate']:.2f}")

        post = client.query(total_q)["result"]["val"]
        assert abs(post - want) < 1e-3, (post, want)
        print(f"post-compaction total {post:.1f} == live total (reads "
              f"unchanged by compaction)")

        # -- 4. oracle: ingest ≡ one-shot construction --------------------
        from repro.core import AssocTensor
        from repro.serve.registry import generate_triples
        r0, c0, v0 = generate_triples({"generator": "random", "n": 64,
                                       "nnz": 512, "seed": 0})
        rows = list(r0) + [f"new{b}k{i:02d}" for b in range(n_batches)
                           for i in range(bsz)]
        cols = list(c0) + [f"c{i % 4}" for b in range(n_batches)
                           for i in range(bsz)]
        vals = list(v0) + [1.0] * (n_batches * bsz)
        oracle = AssocTensor.from_triples(rows, cols, vals,
                                          aggregate="sum")
        ot = float(oracle.to_assoc().sum(axis=None))
        assert abs(ot - post) < 1e-2, (ot, post)
        print(f"oracle total {ot:.1f} matches — streamed ingest ≡ "
              f"one-shot construction")
        st = client.stats()
        assert st["server"].get("errors", 0.0) == 0.0, st["server"]
        assert st["ingest"]["edges"]["compact_errors"] == 0, st["ingest"]
        print("OK")
        return 0
    finally:
        server.close()


if __name__ == "__main__":
    raise SystemExit(main())
