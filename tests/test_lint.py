"""d4mlint — the host-side AST anti-pattern rules (D4M101…D4M104)."""
import textwrap

import pytest

from repro.analysis.lint import lint_file, lint_paths


def _lint(src, path="mod.py"):
    return lint_file(path, text=textwrap.dedent(src))


def _rules(findings):
    return sorted({f.rule for f in findings})


def test_numpy_in_jit_body_is_d4m101():
    f = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def go(x):
            return np.asarray(x) + 1
    """)
    assert _rules(f) == ["D4M101"]


def test_numpy_at_module_scope_is_fine():
    f = _lint("""
        import numpy as np
        TABLE = np.arange(16)

        def host_helper(x):
            return np.asarray(x)
    """)
    assert f == []


@pytest.mark.parametrize("call", ["shard_map", "jax.shard_map"])
def test_host_roundtrip_in_shard_map_body_is_d4m102(call):
    # body passed BY NAME to shard_map — no decorator in sight
    f = _lint(f"""
        import jax
        from jax import shard_map

        def body(x):
            x.block_until_ready()
            return x

        go = {call}(body, mesh=None, in_specs=None, out_specs=None)
    """)
    assert _rules(f) == ["D4M102"]


def test_nnz_loop_in_device_scope_is_d4m103():
    f = _lint("""
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("n",))
        def go(x, nnz, n):
            acc = 0
            for i in range(nnz):
                acc = acc + x[i]
            return acc
    """)
    assert _rules(f) == ["D4M103"]


def test_nested_def_inherits_device_scope():
    f = _lint("""
        import jax

        @jax.jit
        def outer(x):
            def inner(y):
                import numpy as np
                return np.sqrt(y)
            return inner(x)
    """)
    assert _rules(f) == ["D4M101"]


def test_kernel_ops_missing_triple_is_d4m104(tmp_path):
    d = tmp_path / "kernels" / "mykern"
    d.mkdir(parents=True)
    p = d / "ops.py"
    p.write_text('IMPLS = {"ref": 1, "interpret": 2}\n')  # no "pallas"
    f = lint_file(str(p))
    assert _rules(f) == ["D4M104"]
    assert "pallas" in f[0].message
    p.write_text('IMPLS = {"ref": 1, "interpret": 2, "pallas": 3}\n')
    assert lint_file(str(p)) == []


def test_non_kernel_ops_py_is_exempt(tmp_path):
    p = tmp_path / "ops.py"          # not under a kernels/ tree
    p.write_text("X = 1\n")
    assert lint_file(str(p)) == []


def test_file_level_disable_suppresses():
    f = _lint("""
        # d4mlint: disable=D4M101
        import jax
        import numpy as np

        @jax.jit
        def go(x):
            return np.asarray(x)
    """)
    assert f == []


def test_line_level_ignore_suppresses_only_that_line():
    f = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def go(x):
            a = np.asarray(x)  # d4mlint: ignore[D4M101]
            return np.asarray(a)
    """)
    assert len(f) == 1 and f[0].rule == "D4M101"


def test_repo_source_tree_is_clean():
    assert lint_paths(["src/repro"]) == []
