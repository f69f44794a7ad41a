"""Generators: the same seed gives the same tables and the same traffic."""
import json

import numpy as np
import pytest

import harness
import traffic


def _data(checkout, config, seed):
    spec = harness.load_cell(checkout, {"paper18": "paper18.select",
                                        "graph500-s14":
                                            "graph500-s14.twohop"}[config])
    gen = harness.load_module(
        spec["root"] / "data" / f"{spec['config']['generator']}.py",
        "gen_" + config.replace("-", "_"))
    return spec, gen.generate(spec["config"], seed)


@pytest.mark.parametrize("config", ["paper18", "graph500-s14"])
def test_same_seed_same_tables(checkout, config):
    big = 2 ** 31 + 12345
    _, a = _data(checkout, config, big)
    _, b = _data(checkout, config, big)
    _, c = _data(checkout, config, big + 1)
    for name, t in a["tables"].items():
        for k in ("rows", "cols", "vals"):
            assert np.array_equal(t[k], b["tables"][name][k])
        assert not np.array_equal(t["vals"], c["tables"][name]["vals"])


@pytest.mark.parametrize("workload", ["paper18.select",
                                      "graph500-s14.twohop",
                                      "paper18.ingest", "paper18.kinds"])
def test_same_seed_same_traffic(checkout, workload):
    spec = harness.load_cell(checkout, workload)
    gen = harness.load_module(
        spec["root"] / "data" / f"{spec['config']['generator']}.py", "g")
    seed = 2 ** 32 + 7
    ctx = gen.generate(spec["config"], seed)["ctx"]
    rate = spec["cell"].get("rate_per_s")

    def draw(s):
        return json.dumps([traffic.build_streams(spec["mix"], ctx, s, 10.0,
                                                 rate=rate),
                           traffic.warmup_requests(spec["mix"], ctx, s,
                                                   spec["cell"]["warmup"])],
                          default=str)

    assert draw(seed) == draw(seed)
    assert draw(seed) != draw(seed + 1)


def test_open_loop_offers_fixed_work():
    t = traffic.arrival_times(12.5, 30.0, np.random.default_rng(1))
    assert len(t) == 375 and np.all(np.diff(t) >= 0)
    assert 0.0 <= t[0] and t[-1] < 30.0
    assert np.array_equal(
        t, traffic.arrival_times(12.5, 30.0, np.random.default_rng(1)))


@pytest.mark.parametrize("workload", ["paper18.select",
                                      "graph500-s14.twohop",
                                      "paper18.ingest", "paper18.kinds"])
def test_every_seed_offers_the_same_work(checkout, workload):
    """Seeds order and place the requests; the multiset of what sets
    their work (class, counts, prefixes, range bounds) is the same."""
    spec = harness.load_cell(checkout, workload)
    gen = harness.load_module(
        spec["root"] / "data" / f"{spec['config']['generator']}.py", "g")

    def work(seed):
        ctx = gen.generate(spec["config"], seed)["ctx"]
        out, seq = [], []
        for st in traffic.build_streams(spec["mix"], ctx, seed, 10.0,
                                        rate=spec["cell"].get(
                                            "rate_per_s")):
            reqs = st["requests"] if st["loop"] == "open" else \
                [q for per in st["requests"] for q in per]
            for q in reqs:
                sel = q.get("rows") or q.get("cols") or {}
                out.append((q["cls"], sel.get("p"), sel.get("lo"),
                            len(sel.get("keys", ()))))
                seq.append((q["cls"], q.get("due"), q.get("semiring"),
                            q.get("axis")))
        return sorted(out, key=str), seq

    a, b = work(3), work(2 ** 31 + 77)
    assert a[0] == b[0]          # the same sizes
    assert a[1] == b[1]          # the same classes at the same moments


def test_ingest_batches_repeat():
    spec = {"batch": 64, "key_hi": 300, "vals": [1, 100]}
    a = traffic.ingest_batch(5, 3, spec)
    b = traffic.ingest_batch(5, 3, spec)
    c = traffic.ingest_batch(5, 4, spec)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[2], c[2])
    assert a[2].min() >= 1 and a[2].max() < 100
