"""repro.lint: rule fixtures (one positive + one negative per rule),
jit-region resolver unit tests, suppression syntax, baseline round-trip
and the CLI contract — plus the self-check that the treecode packages
lint clean (the PR's acceptance bar)."""
import io
import json
import os
import textwrap

import pytest

from repro.lint import Severity, TraceResolver, main
from repro.lint import baseline as bl
from repro.lint.findings import Finding
from repro.lint.resolver import parse_module
from repro.lint.rules import ALL_RULES, get_rule, run_rules

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TREECODE_PACKAGES = ("core", "devtree", "dynamics", "kernels", "serve",
                     "obs", "distributed", "lint")


def _findings(src, path="src/repro/core/fixture.py"):
    mod = parse_module(path, textwrap.dedent(src))
    return run_rules([mod], TraceResolver([mod]))


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------
# rule fixtures: positive (fires) + negative (stays quiet) per rule
# ---------------------------------------------------------------------


def test_ts001_numpy_on_traced_fires():
    fs = _findings("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
    """)
    assert "TS001" in _rules(fs)


def test_ts001_numpy_on_static_scalar_quiet():
    fs = _findings("""
        import jax
        import numpy as np
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n: int):
            w = np.arange(n)
            return x * w.sum()
    """)
    assert "TS001" not in _rules(fs)


def test_ts002_item_in_jit_fires():
    fs = _findings("""
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()
    """)
    assert "TS002" in _rules(fs)


def test_ts002_device_get_on_host_quiet():
    fs = _findings("""
        import jax

        def host_pull(x):
            return jax.device_get(x).item()
    """)
    assert "TS002" not in _rules(fs)


def test_ts003_float_cast_on_traced_fires():
    fs = _findings("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
    """)
    assert "TS003" in _rules(fs)


def test_ts003_float_cast_on_annotated_scalar_quiet():
    fs = _findings("""
        import jax

        @jax.jit
        def f(x, dt: float):
            return x * float(dt)
    """)
    assert "TS003" not in _rules(fs)


def test_ts004_branch_on_traced_fires():
    fs = _findings("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    assert "TS004" in _rules(fs)


def test_ts004_identity_and_structure_branches_quiet():
    fs = _findings("""
        import jax

        @jax.jit
        def f(x, mode: str):
            if x is None:
                return 0.0
            if mode == "fast":
                return x
            return x * 2.0
    """)
    assert "TS004" not in _rules(fs)


def test_ts005_list_for_static_arg_fires():
    fs = _findings("""
        import jax

        def _impl(x, *, opts):
            return x

        run = jax.jit(_impl, static_argnames=("opts",))

        def caller(x):
            return run(x, opts=["a", "b"])
    """)
    assert "TS005" in _rules(fs)


def test_ts005_tuple_for_static_arg_quiet():
    fs = _findings("""
        import jax

        def _impl(x, *, opts):
            return x

        run = jax.jit(_impl, static_argnames=("opts",))

        def caller(x):
            return run(x, opts=("a", "b"))
    """)
    assert "TS005" not in _rules(fs)


def test_ts006_print_in_jit_warns():
    fs = _findings("""
        import jax

        @jax.jit
        def f(x):
            print("tracing", x)
            return x
    """)
    hits = [f for f in fs if f.rule == "TS006"]
    assert hits and all(f.severity == Severity.WARNING for f in hits)


def test_ts006_print_on_host_quiet():
    fs = _findings("""
        def report(x):
            print("result", x)
    """)
    assert "TS006" not in _rules(fs)


def test_nd001_python_random_in_jit_fires():
    fs = _findings("""
        import jax
        import random

        @jax.jit
        def f(x):
            return x + random.random()
    """)
    assert "ND001" in _rules(fs)


def test_nd001_random_on_host_quiet():
    fs = _findings("""
        import random

        def seed_positions(n):
            return [random.random() for _ in range(n)]
    """)
    assert "ND001" not in _rules(fs)


def test_dv001_scatter_in_devtree_fires():
    fs = _findings("""
        import jax.numpy as jnp

        def pack(buf, idx, vals):
            return buf.at[idx].set(vals)
    """, path="src/repro/devtree/fixture.py")
    assert "DV001" in _rules(fs)


def test_dv001_same_code_outside_devtree_quiet():
    fs = _findings("""
        import jax.numpy as jnp

        def pack(buf, idx, vals):
            return buf.at[idx].set(vals)
    """, path="src/repro/core/fixture.py")
    assert "DV001" not in _rules(fs)


def test_dv002_argsort_in_devtree_lists_fires():
    fs = _findings("""
        import jax.numpy as jnp

        def merge(keys):
            return jnp.argsort(keys)
    """, path="src/repro/devtree/lists.py")
    assert "DV002" in _rules(fs)


def test_dv002_argsort_elsewhere_in_devtree_quiet():
    fs = _findings("""
        import jax.numpy as jnp

        def order(keys):
            return jnp.argsort(keys)
    """, path="src/repro/devtree/build.py")
    assert "DV002" not in _rules(fs)


def test_ob001_ungated_block_fires():
    fs = _findings("""
        def flush(phi):
            phi.block_until_ready()
            return phi
    """)
    assert "OB001" in _rules(fs)


def test_ob001_gated_block_quiet():
    fs = _findings("""
        from repro.obs import trace

        def flush(phi):
            if trace.enabled():
                phi.block_until_ready()
            return phi
    """)
    assert "OB001" not in _rules(fs)


def test_dn001_read_after_donate_fires():
    fs = _findings("""
        import jax

        def _impl(arrays, charges):
            return charges * 2.0

        execute_donating = jax.jit(_impl, donate_argnums=(1,))

        def step(arrays, q):
            out = execute_donating(arrays, q)
            return out + q
    """)
    assert "DN001" in _rules(fs)


def test_dn001_donated_never_reread_quiet():
    fs = _findings("""
        import jax

        def _impl(arrays, charges):
            return charges * 2.0

        execute_donating = jax.jit(_impl, donate_argnums=(1,))

        def step(arrays, q):
            out = execute_donating(arrays, q)
            return out
    """)
    assert "DN001" not in _rules(fs)


def test_every_rule_has_a_fixture_pair():
    """The fixtures above must cover the full registry (>= 10 rules)."""
    covered = {"TS001", "TS002", "TS003", "TS004", "TS005", "TS006",
               "ND001", "DV001", "DV002", "OB001", "DN001"}
    assert {r.id for r in ALL_RULES} == covered
    assert len(ALL_RULES) >= 10
    for rid in covered:
        assert get_rule(rid).description


# ---------------------------------------------------------------------
# jit-region resolver
# ---------------------------------------------------------------------


def _resolve(src, path="src/repro/core/fixture.py"):
    mod = parse_module(path, textwrap.dedent(src))
    return mod, TraceResolver([mod])


def test_resolver_decorator_forms():
    mod, _ = _resolve("""
        import jax
        from functools import partial

        @jax.jit
        def plain(x):
            return x

        @partial(jax.jit, static_argnames=("k",))
        def with_static(x, k):
            return x

        def host(x):
            return x
    """)
    by_name = {f.name: f for f in mod.functions}
    assert by_name["plain"].traced and by_name["plain"].is_root
    assert by_name["with_static"].traced
    assert "k" in by_name["with_static"].static_params()
    assert not by_name["host"].traced


def test_resolver_binding_form_with_module_const():
    mod, res = _resolve("""
        import jax

        _OPTS = ("degree", "kernel")

        def _impl(arrays, charges, *, degree, kernel):
            return charges

        execute = jax.jit(_impl, static_argnames=_OPTS)
    """)
    assert "execute" in mod.bindings
    b = mod.bindings["execute"]
    assert set(b.static_argnames) >= {"degree", "kernel"}
    impl = next(f for f in mod.functions if f.name == "_impl")
    assert impl.traced


def test_resolver_call_graph_propagation():
    mod, _ = _resolve("""
        import jax

        def helper(x):
            return x * 2.0

        def deeper(x):
            return helper(x) + 1.0

        @jax.jit
        def root(x):
            return deeper(x)

        def unreached(x):
            return x
    """)
    by_name = {f.name: f for f in mod.functions}
    assert by_name["root"].traced and by_name["root"].is_root
    assert by_name["deeper"].traced and not by_name["deeper"].is_root
    assert by_name["helper"].traced
    assert not by_name["unreached"].traced


def test_resolver_vmap_and_shard_map_call_forms():
    mod, _ = _resolve("""
        import jax
        from jax import shard_map

        def body(x):
            return x + 1.0

        batched = jax.vmap(body)

        def spmd(x):
            return x * 2.0

        def build(mesh, spec):
            return shard_map(spmd, mesh=mesh, in_specs=spec,
                             out_specs=spec)
    """)
    by_name = {f.name: f for f in mod.functions}
    assert by_name["body"].traced
    assert by_name["spmd"].traced


# ---------------------------------------------------------------------
# suppressions, baseline, CLI
# ---------------------------------------------------------------------

_VIOLATION = textwrap.dedent("""
    import jax

    @jax.jit
    def f(x):
        return float(x)
""")


def _run_cli(args):
    out = io.StringIO()
    code = main(args, out=out)
    return code, out.getvalue()


def test_cli_clean_file_exits_zero(tmp_path):
    p = tmp_path / "clean.py"
    p.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x\n")
    code, out = _run_cli([str(p)])
    assert code == 0
    assert "0 error(s)" in out


def test_cli_violation_exits_one_gh_format(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(_VIOLATION)
    code, out = _run_cli([str(p), "--format", "gh"])
    assert code == 1
    assert "::error" in out and "TS003" in out


def test_cli_json_format(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(_VIOLATION)
    code, out = _run_cli([str(p), "--format", "json"])
    assert code == 1
    data = json.loads(out)
    assert data["errors"] >= 1
    assert any(f["rule"] == "TS003" for f in data["findings"])


def test_suppression_with_reason(tmp_path):
    p = tmp_path / "sup.py"
    p.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            # lint: disable=TS003 — fixture: cast is intentional here
            return float(x)
    """))
    code, out = _run_cli([str(p)])
    assert code == 0, out


def test_suppression_without_reason_is_sup001(tmp_path):
    p = tmp_path / "sup.py"
    p.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            # lint: disable=TS003
            return float(x)
    """))
    code, out = _run_cli([str(p)])
    assert code == 1
    assert "SUP001" in out and "[TS003]" not in out


def test_baseline_round_trip(tmp_path):
    f1 = Finding(rule="TS003", severity=Severity.ERROR,
                 path="src/repro/models/blocks.py", line=10, col=1,
                 message="m")
    f2 = Finding(rule="TS003", severity=Severity.ERROR,
                 path="src/repro/models/blocks.py", line=20, col=1,
                 message="m2")
    path = str(tmp_path / "baseline.json")
    bl.write_baseline(path, [f1])
    loaded = bl.load_baseline(path)
    assert loaded == {"src/repro/models/blocks.py": {"TS003": 1}}
    assert bl.check_scope(loaded) == []
    # count budget: one covered, the second (new) finding surfaces
    left = bl.apply_baseline([f1, f2], loaded)
    assert [f.line for f in left] == [20]


def test_baseline_scope_rejects_treecode(tmp_path):
    p = tmp_path / "bad_baseline.json"
    p.write_text(json.dumps({"src/repro/core/eval.py": {"TS001": 1}}))
    src = tmp_path / "clean.py"
    src.write_text("X = 1\n")
    code, _ = _run_cli([str(src), "--baseline", str(p)])
    assert code == 2


def test_baseline_scope_configs_only_lm_variants():
    assert bl.in_scope("src/repro/configs/tiny_b.py")
    assert not bl.in_scope("src/repro/configs/treecode.py")
    assert bl.in_scope("src/repro/models/attention.py")
    assert not bl.in_scope("src/repro/devtree/build.py")


def test_write_baseline_refuses_treecode_findings(tmp_path):
    p = tmp_path / "src" / "repro" / "core"
    p.mkdir(parents=True)
    bad = p / "bad.py"
    bad.write_text(_VIOLATION)
    code, _ = _run_cli([str(bad),
                        "--write-baseline", str(tmp_path / "b.json")])
    # tmp paths are outside the LM-skeleton scope -> refused
    assert code == 2
    assert not (tmp_path / "b.json").exists()


# ---------------------------------------------------------------------
# self-check: the treecode packages lint clean
# ---------------------------------------------------------------------


@pytest.mark.parametrize("pkg", TREECODE_PACKAGES)
def test_treecode_package_lints_clean(pkg):
    path = os.path.join(ROOT, "src", "repro", pkg)
    if not os.path.isdir(path):
        pytest.skip(f"package {pkg} not present")
    code, out = _run_cli([path])
    assert code == 0, f"{pkg}:\n{out}"


def test_full_src_tree_with_committed_baseline():
    """`python -m repro.lint src --baseline lint_baseline.json` == 0,
    exactly as CI runs it."""
    code, out = _run_cli([os.path.join(ROOT, "src"), "--baseline",
                          os.path.join(ROOT, "lint_baseline.json")])
    assert code == 0, out


def test_list_traced_reports_known_roots():
    out = io.StringIO()
    code = main([os.path.join(ROOT, "src", "repro", "core"),
                 "--list-traced"], out=out)
    assert code == 0
    assert "_execute_impl" in out.getvalue()
