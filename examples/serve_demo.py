"""D4M-as-a-service demo: resident tables, wire queries, live metrics.

Boots the query server in-process on a loopback port, registers a small
device-layer table set, and runs three queries through the HTTP client —
one of them twice, to show the cross-request plan cache engaging (the
``/stats`` ``plan.plan_hits`` counter is the proof that a repeated wire
query re-uses its optimized plan instead of re-planning).

    PYTHONPATH=src python examples/serve_demo.py

Doubles as the CI client smoke: it exits nonzero if any endpoint
misbehaves or the repeated query fails to hit the plan cache.
"""
from repro.core import Keys, StartsWith
from repro.serve import D4MClient, TableRef, start_server, TableRegistry


def main() -> int:
    # -- 1. resident tables: loaded once, pinned for the server's life ----
    registry = TableRegistry.from_specs([
        {"name": "edges", "generator": "random", "n": 64, "nnz": 512,
         "seed": 0, "layer": "device"},
        {"name": "feat", "generator": "random", "n": 64, "nnz": 512,
         "seed": 1, "layer": "device"},
    ])
    server = start_server(registry, workers=2)
    print(f"serving {registry.names()} on {server.url}")

    try:
        client = D4MClient(server.url)
        assert client.health()["status"] == "ok"
        for t in client.tables():
            print(f"  table {t['name']}: layer={t['layer']} "
                  f"shape={t['shape']} nnz={t['nnz']}")

        # -- 2. three queries over TableRef leaves (no data client-side) --
        A, B = TableRef("edges"), TableRef("feat")

        q1 = A[StartsWith("r0"), :]                     # selection → triples
        out = client.query(q1)["result"]
        print(f"q1 select: {out['nnz']} triples")

        q2 = (A[StartsWith("r0"), :] @ B).sum(axis=1)   # pipeline → vector
        out = client.query(q2)
        print(f"q2 pipeline: vector n={out['result']['n']} "
              f"(exec {out['timing']['exec_s'] * 1e3:.1f} ms)")

        q3 = (A + B)[Keys(["r01", "r02"]), :]           # ⊕ then select
        out = client.query(q3)["result"]
        print(f"q3 ewise+select: {out['nnz']} triples")

        # -- 3. repeat q2: same wire structure ⇒ plan-cache hit -----------
        before = client.stats()["plan"]
        out = client.query(q2)
        after = client.stats()["plan"]
        print(f"q2 repeated: exec {out['timing']['exec_s'] * 1e3:.1f} ms, "
              f"plan_hits {before['plan_hits']} -> {after['plan_hits']}")
        assert after["plan_hits"] > before["plan_hits"], \
            "repeated query did not hit the plan cache"
        assert after["plan_misses"] == before["plan_misses"], \
            "repeated query re-planned"

        st = client.stats()["server"]
        assert st.get("errors", 0.0) == 0.0, f"{st['errors']} error responses"
        print(f"server: {st['requests']:.0f} requests, "
              f"p50 {st['p50_s'] * 1e3:.1f} ms, "
              f"p99 {st['p99_s'] * 1e3:.1f} ms, "
              f"mean batch {st.get('batch_mean', 1.0):.2f}")
    finally:
        server.close()
    print("serve demo OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
