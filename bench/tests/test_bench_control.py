"""The control each cell file names — the reference in bfloat16, or with
keys matched by their first four bytes — fails each cell's check."""
import pytest

import control
import harness

# n = 14: keys of five digits, so a four-byte key is ambiguous
SIZES = {"paper18": {"n": 14}, "graph500-s14": {"SCALE": 9}}


@pytest.fixture(scope="module")
def checkout12(tmp_path_factory, checkout_maker):
    return checkout_maker(tmp_path_factory.mktemp("control"), SIZES)


@pytest.mark.parametrize("workload", ["paper18.select",
                                      "graph500-s14.twohop",
                                      "paper18.ingest"])
@pytest.mark.parametrize("seed", [2 ** 31 + 1, 2 ** 32 + 9, 12345])
def test_control_fails_the_limit(checkout12, workload, seed):
    limits = harness.load_cell(checkout12,
                               workload)["cell"]["compare"]["limits"]
    r = control.readings(checkout12, workload, seed, 10.0, 10)
    assert r["answers_compared"] >= 10
    assert (r["wrong_entries"] > limits["wrong_entries"]
            or r["max_rel_gap"] > limits["max_rel_gap"]), r
