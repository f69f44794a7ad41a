"""The one traffic generator: a mix file of parameters → request streams.

A mix (``bench/traffic/<mix>.json``) lists streams.  Each stream is an
open loop (arrivals on a schedule, at the cell's fixed rate) or a closed
loop (``clients`` callers that each wait for their answer), and draws its
requests from weighted classes.  A request is a plain description of its
semantics (``op``, table, selectors, semiring, axis) — the harness turns
it into the program's wire format and the reference evaluates it.

Everything here is numpy and the standard library: the load-generating
child imports this module and never imports JAX.  The same seed gives the
same requests, in the same order, on every machine.
"""
from __future__ import annotations

import numpy as np

__all__ = ["build_streams", "warmup_requests", "ingest_batch",
           "arrival_times"]


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (2 ** 63), *salt])


class _Zipf:
    """Bounded Zipf over ranks 0..n-1 (YCSB's zipfian constant by
    default)."""

    def __init__(self, n: int, s: float):
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
        self.cdf = np.cumsum(w) / w.sum()

    def draw(self, rng: np.random.Generator, k: int) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, rng.random(k), side="right")
        return np.minimum(ranks, len(self.cdf) - 1)


def _prefix_pool(digits: int, size: int, rng: np.random.Generator) -> list:
    lo, hi = 10 ** (digits - 1), 10 ** digits
    return [str(x) for x in rng.choice(np.arange(lo, hi), size=size,
                                       replace=False)]


class _ClassGen:
    """The requests of one class.  Their *shapes* (key and root counts,
    Zipf ranks, prefixes, range bounds) come from the mix's fixed
    ``shape_seed``, so every run offers the same work; the run's seed
    places them: it scrambles Zipf ranks onto keys and picks the roots."""

    def __init__(self, spec: dict, ctx: dict, shape_rng, run_rng):
        self.spec = spec
        self.ctx = ctx
        sel = spec.get("sel") or {}
        self.pool = self.zipf = self.perm = self.keys = None
        if sel.get("kind") == "prefix":
            self.pool = _prefix_pool(int(sel["digits"]), int(sel["pool"]),
                                     shape_rng)
        elif sel.get("kind") == "keys":
            # YCSB's scrambled Zipfian: ranks over a fixed item count (the
            # key universe, the same for every seed), the hot ranks placed
            # on the table's own row keys by the run's seed
            self.keys = ctx["row_keys"][spec["table"]]
            self.zipf = _Zipf(int(ctx["key_universe"]),
                              float(sel.get("zipf", 0.99)))
            self.perm = run_rng.permutation(len(self.keys))

    def shape(self, rng, k=None) -> dict:
        """What sets a request's work, drawn from ``rng``."""
        s = self.spec
        sel = s.get("sel") or {}
        kind = sel.get("kind")
        if kind == "keys":
            lo, hi = sel["count"]
            k = int(rng.integers(lo, hi + 1)) if k is None else k
            return {"ranks": self.zipf.draw(rng, k).tolist()}
        if kind == "prefix":
            return {"p": self.pool[int(rng.integers(len(self.pool)))]}
        if kind == "range":
            lo, hi = sel["lo"]
            return {"lo": int(rng.integers(lo, hi + 1))}
        if kind == "roots":
            # Graph500's root count: log-uniform in [lo, hi]
            lo, hi = sel["count"]
            if k is None:
                k = int(np.exp(rng.uniform(np.log(lo), np.log(hi + 1))))
            return {"k": max(lo, min(hi, k))}
        return {}

    def _selector(self, shape, rng) -> dict:
        kind = self.spec["sel"]["kind"]
        if kind == "keys":
            pos = self.perm[np.asarray(shape["ranks"]) % len(self.keys)]
            return {"kind": "keys", "keys": [str(x) for x in self.keys[pos]]}
        if kind == "prefix":
            return {"kind": "prefix", "p": shape["p"]}
        if kind == "range":
            return {"kind": "range", "lo": str(shape["lo"]),
                    "hi": str(shape["lo"] + 1)}
        if kind == "roots":
            # Graph500's root rule: uniform over vertices of degree >= 1
            verts = self.ctx["roots"][self.spec["table"]]
            k = min(shape["k"], len(verts))
            pick = rng.choice(len(verts), size=k, replace=False)
            return {"kind": "keys", "keys": [str(verts[i]) for i in pick]}
        raise ValueError(f"unknown selector kind {kind!r}")

    def request(self, shape, rng, position: int) -> dict:
        """The request of ``shape``; ``position`` is its place in its
        client's sequence (a two-hop client alternates semirings on every
        request and axes on every second one)."""
        s = self.spec
        op = s["op"]
        q = {"cls": s["name"], "op": op, "table": s["table"]}
        if op == "select":
            sel = self._selector(shape, rng)
            q["rows"], q["cols"] = ((sel, None) if s["axis"] == "rows"
                                    else (None, sel))
        elif op == "select_sum":
            q["rows"] = self._selector(shape, rng)
            q["axis"] = int(s["reduce_axis"])
        elif op == "twohop":
            q["rows"] = self._selector(shape, rng)
            nsr = len(s["semirings"])
            q["semiring"] = s["semirings"][position % nsr]
            q["axis"] = int(s["axes"][(position // nsr) % len(s["axes"])])
        elif op == "total":
            pass
        elif op == "ingest":
            q["batch"] = int(s["batch"])
            q["key_hi"] = int(self.ctx["key_universe"]) + int(s["key_extra"])
            q["vals"] = list(s["vals"])
        else:
            raise ValueError(f"unknown op {op!r}")
        return q


def _class_counts(shares, n: int) -> np.ndarray:
    """Exactly ``n`` class indices in the mix's shares (largest
    remainders), unshuffled."""
    shares = np.asarray(shares, np.float64) / np.sum(shares)
    counts = np.floor(shares * n).astype(int)
    rest = np.argsort(-(shares * n - counts), kind="stable")
    counts[rest[:n - counts.sum()]] += 1
    return np.repeat(np.arange(len(shares)), counts)


def arrival_times(rate: float, seconds: float, shape_rng) -> np.ndarray:
    """``round(rate·seconds)`` due times in ``[0, seconds)``: uniform
    arrivals, i.e. a Poisson process given its count, from the shape
    seed."""
    n = max(1, int(round(rate * seconds)))
    return np.sort(shape_rng.uniform(0.0, seconds, size=n))


def _schedule(gens, shares, n, shape_rng, run_rng) -> list:
    """``n`` ``(class, shape)`` pairs.  The class sequence and each class's
    multiset of shapes come from the shape seed; the run's seed only
    reorders the shapes within their class.  So every seed offers the
    same work at the same moments: a heavy request (a degree vector, a
    scan) lands where it lands in every run, and only the light
    variation of sizes within a class moves."""
    cls = _class_counts(shares, n)
    cls = cls[shape_rng.permutation(n)]
    shapes = {}
    for c in range(len(gens)):
        k = int((cls == c).sum())
        drawn = [gens[c].shape(shape_rng) for _ in range(k)]
        shapes[c] = [drawn[i] for i in run_rng.permutation(k)]
    taken = {c: 0 for c in shapes}
    out = []
    for c in cls:
        c = int(c)
        out.append((c, shapes[c][taken[c]]))
        taken[c] += 1
    return out


def build_streams(mix: dict, ctx: dict, seed: int, seconds: float,
                  rate: float | None = None) -> list:
    """Requests of every stream of ``mix`` for one run.

    Returns a list of streams ``{"loop", "senders" | "clients",
    "requests"}``; an open stream's requests carry ``due`` (seconds into
    the window), a closed stream's are a list per client.  Arrival times,
    the class sequence and the multiset of shapes are the same for every
    seed (``mix["shape_seed"]``); the seed reorders shapes within a class
    and places them (keys, roots) on its own tables."""
    out = []
    for si, st in enumerate(mix["streams"]):
        shape_rng = _rng(mix["shape_seed"], si)
        run_rng = _rng(seed, 0, si)
        gens = [_ClassGen(c, ctx, shape_rng, run_rng) for c in st["classes"]]
        shares = [c.get("share", 1.0) for c in st["classes"]]
        if st["loop"] == "open":
            if rate is None:
                raise ValueError("an open-loop stream needs the cell's "
                                 "rate_per_s")
            due = arrival_times(rate, seconds, shape_rng)
            sched = _schedule(gens, shares, len(due), shape_rng, run_rng)
            reqs = []
            for pos, (t, (c, shape)) in enumerate(zip(due, sched)):
                q = gens[c].request(shape, run_rng, pos)
                q["due"] = float(t)
                reqs.append(q)
            out.append({"loop": "open", "senders": int(st["senders"]),
                        "requests": reqs})
        else:
            n = int(st.get("per_client", 256))
            per = []
            for _ in range(int(st["clients"])):
                sched = _schedule(gens, shares, n, shape_rng, run_rng)
                per.append([gens[c].request(shape, run_rng, pos)
                            for pos, (c, shape) in enumerate(sched)])
            out.append({"loop": "closed", "clients": int(st["clients"]),
                        "requests": per})
    return out


def warmup_requests(mix: dict, ctx: dict, seed: int, warm: dict) -> list:
    """The warm-up's requests, drawn apart from the window's: for every
    class ``warm["per_class"]`` requests, and one for each size listed in
    ``warm["counts"]`` (key or root counts, whose shapes differ), classes
    interleaved."""
    per_class = []
    for si, st in enumerate(mix["streams"]):
        rng = _rng(seed, 1, si)
        for c in st["classes"]:
            gen = _ClassGen(c, ctx, rng, rng)
            shapes = [gen.shape(rng) for _ in range(int(warm["per_class"]))]
            if c.get("sel", {}).get("kind") in ("keys", "roots"):
                shapes += [gen.shape(rng, k) for k in warm.get("counts", [])]
            per_class.append([gen.request(sh, rng, i)
                              for i, sh in enumerate(shapes)])
    out = []
    for i in range(max(len(r) for r in per_class)):
        out += [r[i] for r in per_class if i < len(r)]
    return out


def ingest_batch(seed: int, index: int, spec: dict):
    """Batch ``index`` of a writer's sequence: ``spec["batch"]`` triples
    with row and column keys uniform over ``[0, key_hi)`` as strings and
    values uniform integers in ``spec["vals"]`` (half-open)."""
    rng = _rng(seed, 1 << 20, index)
    b = int(spec["batch"])
    keys = rng.integers(0, int(spec["key_hi"]), size=(2, b))
    lo, hi = spec["vals"]
    vals = rng.integers(lo, hi, size=b).astype(np.float64)
    return keys[0].astype(str), keys[1].astype(str), vals
