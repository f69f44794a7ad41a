"""Static contract verification (the d4mcheck tentpole).

Two halves: (1) the registry sweep — every ``@contract``-decorated entry
point lowers its compiled program(s) on an AbstractMesh and the HLO
walker proves the declared invariants hold; (2) the checker has teeth —
deliberately broken programs (an injected psum, a densifying scatter, a
host callback, a while-of-psums) are each caught with the right
violation kind.  Everything here is static: nothing executes on devices.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.analysis import (CONTRACT_REGISTRY, Contract, analyze_program,
                            lower_hlo, verify_all, verify_entry)
from repro.analysis import contracts as contracts_mod
from repro.analysis import probes as probes_mod
from repro.analysis.contracts import RetraceAudit, Violation
from repro.analysis.hlo_contracts import parse_hlo


def _mesh():
    return AbstractMesh((8,), ("data",))


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# the sweep: every declared contract verifies against its compiled HLO
# ---------------------------------------------------------------------------

EXPECTED_ENTRIES = {
    "AssocTensor.__getitem__", "AssocTensor.__setitem__",
    "spgemm.matmul", "spgemm.matmul_reduce",
    "DistAssoc.__getitem__", "DistAssoc.__setitem__",
    "DistAssoc.add", "DistAssoc.mul", "DistAssoc.matmul",
    "DistAssoc.matmul_reduce", "DistAssoc.sqin", "DistAssoc.sqout",
    "DistAssoc.col_reduce", "DistAssoc.row_reduce", "DistAssoc.col_degree",
    "DistAssoc.matmul_dense_vec",
}


def test_registry_covers_the_public_surface():
    contracts_mod._ensure_registry()
    assert EXPECTED_ENTRIES <= set(CONTRACT_REGISTRY), \
        EXPECTED_ENTRIES - set(CONTRACT_REGISTRY)


def test_sweep_all_contracts_hold():
    results = verify_all()
    bad = {k: [str(v) for v in vs] for k, vs in results.items() if vs}
    assert not bad, bad
    # the sweep actually checked the full registry, not a subset
    assert set(results) == set(CONTRACT_REGISTRY)


def test_shard_local_entries_declare_zero_collectives():
    contracts_mod._ensure_registry()
    for name in ("DistAssoc.__getitem__", "DistAssoc.__setitem__",
                 "DistAssoc.matmul", "AssocTensor.__getitem__"):
        assert CONTRACT_REGISTRY[name].collectives == 0, name
    # the fused reduce epilogues spend exactly ONE psum-family collective
    for name in ("DistAssoc.matmul_reduce", "DistAssoc.sqin",
                 "DistAssoc.sqout", "DistAssoc.col_reduce"):
        assert CONTRACT_REGISTRY[name].collectives == 1, name


# ---------------------------------------------------------------------------
# teeth: broken programs are caught with the right violation kind
# ---------------------------------------------------------------------------

def _kinds(violations):
    return sorted({v.kind for v in violations})


def test_injected_psum_is_caught():
    f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=_mesh(),
                  in_specs=P("data"), out_specs=P(), check_vma=False)
    rep = analyze_program(lower_hlo(f, _sds((8, 16))))
    assert rep.collectives_total == 1
    viol = Contract(name="canary", collectives=0).check(rep)
    assert _kinds(viol) == ["collectives"]
    # the honest declaration passes
    assert Contract(name="ok", collectives=1).check(rep) == []


def test_while_of_psums_counts_trip_weighted():
    def body(x):
        def step(c, _):
            return c + jax.lax.psum(c, "data"), None
        out, _ = jax.lax.scan(step, x, None, length=5)
        return out
    f = shard_map(body, mesh=_mesh(), in_specs=P("data"), out_specs=P("data"),
                  check_vma=False)
    rep = analyze_program(lower_hlo(f, _sds((8, 16))))
    # a while of N psums is N collectives, not 1 — the walker multiplies
    # by the loop trip count
    assert rep.collective_counts.get("all-reduce") == pytest.approx(5.0)
    viol = Contract(name="canary", collectives=1).check(rep)
    assert _kinds(viol) == ["collectives"]


def test_densifying_scatter_is_caught():
    def densify(rows, cols, vals):
        return jnp.zeros((4096, 4096), jnp.float32).at[rows, cols].set(vals)
    rep = analyze_program(lower_hlo(
        densify, _sds((64,), jnp.int32), _sds((64,), jnp.int32),
        _sds((64,), jnp.float32)))
    assert rep.max_intermediate_elems >= 4096 * 4096
    viol = Contract(name="canary", collectives=None).check(rep)
    assert _kinds(viol) == ["densify"]
    # densify=True waives the budget
    assert Contract(name="ok", collectives=None, densify=True).check(rep) == []


def test_host_callback_is_caught():
    def f(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a), _sds((16,), jnp.float32), x)
        return y * 2
    rep = analyze_program(lower_hlo(f, _sds((16,), jnp.float32)))
    assert rep.host_transfers >= 1
    viol = Contract(name="canary", collectives=None,
                    host_transfers=0).check(rep)
    assert _kinds(viol) == ["host_transfers"]


def test_partitioner_custom_calls_are_not_host_transfers():
    # Sharding/SPMDFullToShardShape markers in shard_map lowerings must
    # not count as host round-trips
    f = shard_map(lambda x: x * 2, mesh=_mesh(), in_specs=P("data"),
                  out_specs=P("data"), check_vma=False)
    rep = analyze_program(lower_hlo(f, _sds((8, 16))))
    assert rep.host_transfers == 0
    assert rep.collectives_total == 0


# ---------------------------------------------------------------------------
# verifier plumbing: probes, retrace audits, both HLO header dialects
# ---------------------------------------------------------------------------

def test_declared_but_unprobed_contract_is_a_violation(monkeypatch):
    monkeypatch.setitem(CONTRACT_REGISTRY, "synthetic.unprobed",
                        Contract(name="synthetic.unprobed", collectives=0))
    viol = verify_entry("synthetic.unprobed")
    assert _kinds(viol) == ["probe"]


def test_retrace_audit_flags_cache_growth(monkeypatch):
    monkeypatch.setitem(
        CONTRACT_REGISTRY, "synthetic.retrace",
        Contract(name="synthetic.retrace", collectives=None,
                 host_transfers=None))
    state = {"size": 0}

    def growing_probe():
        yield RetraceAudit(
            label="grows",
            first=lambda: state.__setitem__("size", 1),
            again=lambda: state.__setitem__("size", 2),
            size=lambda: state["size"])

    monkeypatch.setitem(probes_mod.PROBES, "synthetic.retrace",
                        growing_probe)
    viol = verify_entry("synthetic.retrace")
    assert _kinds(viol) == ["recompile"]

    def stable_probe():
        yield RetraceAudit(
            label="stable",
            first=lambda: state.__setitem__("size", 1),
            again=lambda: None,
            size=lambda: state["size"])

    monkeypatch.setitem(probes_mod.PROBES, "synthetic.retrace",
                        stable_probe)
    assert verify_entry("synthetic.retrace") == []


def test_parser_reads_both_header_dialects():
    # post-optimization headers carry a signature; pre-optimization
    # (`.lower().as_text()`) headers are bare — both must parse
    post = """
HloModule m

%helper (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  ROOT %r = f32[8] add(f32[8] %x, f32[8] %x)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %c = f32[8] call(f32[8] %p), to_apply=%helper
}
"""
    comps = parse_hlo(post)
    assert "__entry__" in comps and "helper" in comps

    pre = """
HloModule m

helper {
  x = f32[8] parameter(0)
  ROOT r = f32[8] add(x, x)
}

ENTRY main {
  p = f32[8] parameter(0)
  ROOT c = f32[8] call(p), to_apply=helper
}
"""
    comps = parse_hlo(pre)
    assert "__entry__" in comps and "helper" in comps
    rep = analyze_program(pre)
    assert rep.collectives_total == 0


def test_violation_str_is_actionable():
    v = Violation(entry="X.y[range]", kind="collectives", message="boom")
    assert "X.y[range]" in str(v) and "collectives" in str(v)
