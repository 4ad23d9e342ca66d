"""Fixture suite for ``repro.analysis`` (jaxlint).

Every rule gets positive snippets (the regression class it exists to
catch — each a distilled version of a real bug shape from PRs 3/6/7)
and negative snippets pinning the conservatism: the idioms this
codebase actually uses must NOT be flagged. Snippets are linted inside
a tmp fake repo tree so the path-scoped rules (JL003, JL100, JL101)
see in-scope paths; ``--select`` isolates each rule from the others.

The suite never imports jax — jaxlint is dependency-free by contract
and these tests must run in the CI static-analysis job's bare
environment.
"""

import json
import pathlib
import textwrap

import pytest

from repro.analysis import main, run_lint
from repro.analysis.registry import RULES

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

IN_SCOPE = "src/repro/core/sampling/snippet.py"      # JL003/JL100 scope
EXP_SCOPE = "src/repro/experiments/snippet.py"       # JL101 scope too
NO_SCOPE = "src/repro/models/snippet.py"             # outside JL003 scope


def lint(tmp_path, code, rel=IN_SCOPE, select=None, **kw):
    """Write one snippet into a fake tree and lint just that file."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return run_lint([rel], root=tmp_path,
                    baseline_path=tmp_path / "baseline.json",
                    select=select, **kw)


def rules_of(report):
    """Rule ids of the active findings, in report order."""
    return [f.rule for f in report.active]


# ---------------------------------------------------------------- registry
def test_rule_registry_complete():
    """The full pack is registered: jax discipline + repo contracts."""
    assert sorted(RULES) == ["JL001", "JL002", "JL003", "JL004", "JL005",
                             "JL006", "JL100", "JL101", "JL102"]


# ------------------------------------------------- JL001 host-sync-in-trace
def test_jl001_item_in_jitted_function(tmp_path):
    r = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return x.item()
    """, select=["JL001"])
    assert rules_of(r) == ["JL001"]
    assert ".item()" in r.active[0].message


def test_jl001_np_asarray_in_function_passed_to_jit(tmp_path):
    r = lint(tmp_path, """
        import jax
        import numpy as np

        def body(x):
            return np.asarray(x) + 1

        run = jax.jit(body)
    """, select=["JL001"])
    assert rules_of(r) == ["JL001"]


def test_jl001_print_in_transitively_traced_callee(tmp_path):
    r = lint(tmp_path, """
        import jax

        def helper(x):
            print(x)
            return x

        @jax.jit
        def f(x):
            return helper(x)
    """, select=["JL001"])
    assert rules_of(r) == ["JL001"]


_BODY = "def body(x):\n    return x.item()\n"


@pytest.mark.parametrize("site", [
    _BODY + "run = jax.shard_map(body, mesh=m, in_specs=s, out_specs=s)\n",
    _BODY + "run = shard_map(body, mesh=m, in_specs=s, out_specs=s)\n",
    "@functools.partial(jax.shard_map, mesh=m, in_specs=s, out_specs=s)\n"
    + _BODY,
])
def test_jl001_in_function_passed_to_shard_map(tmp_path, site):
    """The installed JAX spells it ``jax.shard_map``; each spelling of
    the call the repo uses is a trace site."""
    header = "import functools\n\nimport jax\nfrom jax import shard_map\n\n"
    r = lint(tmp_path, header + site, select=["JL001"])
    assert rules_of(r) == ["JL001"]


def test_jl001_negative_host_code_and_static_attrs(tmp_path):
    r = lint(tmp_path, """
        import jax
        import numpy as np

        def host_summary(x):
            return float(np.asarray(x).sum())

        @jax.jit
        def f(x):
            n = x.shape[0]
            return x * n
    """, select=["JL001"])
    assert rules_of(r) == []


# --------------------------------------------------- JL002 prng-key-reuse
def test_jl002_key_consumed_by_two_draws(tmp_path):
    r = lint(tmp_path, """
        import jax

        def f(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
    """, select=["JL002"])
    assert rules_of(r) == ["JL002"]
    assert "split" in r.active[0].message


def test_jl002_loop_invariant_key_reuse(tmp_path):
    r = lint(tmp_path, """
        import jax

        def f(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, ()))
            return out
    """, select=["JL002"])
    assert rules_of(r) == ["JL002"]


def test_jl002_negative_split_between_draws(tmp_path):
    r = lint(tmp_path, """
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, ())
            b = jax.random.normal(k2, ())
            return a + b

        def g(key, i):
            a = jax.random.normal(jax.random.fold_in(key, i), ())
            key = jax.random.fold_in(key, 1)
            b = jax.random.normal(key, ())
            return a + b
    """, select=["JL002"])
    assert rules_of(r) == []


def test_jl002_negative_branches_are_alternatives(tmp_path):
    r = lint(tmp_path, """
        import jax

        def f(key, flag):
            if flag:
                a = jax.random.normal(key, ())
            else:
                a = jax.random.uniform(key, ())
            return a
    """, select=["JL002"])
    assert rules_of(r) == []


# -------------------------------------------------- JL003 raw-dtype-literal
def test_jl003_jnp_dtype_attribute(tmp_path):
    r = lint(tmp_path, """
        import jax.numpy as jnp

        def f(x):
            return jnp.asarray(x, jnp.float32)
    """, select=["JL003"])
    assert rules_of(r) == ["JL003"]
    assert "jax.numpy.float32" in r.active[0].message


def test_jl003_astype_string_and_dtype_kwarg(tmp_path):
    r = lint(tmp_path, """
        import numpy as np

        def f(x):
            return x.astype("float32")

        def g(n):
            return np.zeros(n, dtype="bfloat16")
    """, select=["JL003"])
    assert rules_of(r) == ["JL003", "JL003"]


def test_jl003_negative_policy_and_host_f64(tmp_path):
    r = lint(tmp_path, """
        import numpy as np

        def f(x, policy):
            y = np.asarray(x, np.float64)
            return y.astype(policy.host_dtype)
    """, select=["JL003"])
    assert rules_of(r) == []


def test_jl003_negative_out_of_scope_path(tmp_path):
    r = lint(tmp_path, """
        import jax.numpy as jnp

        X = jnp.asarray([1.0], jnp.float32)
    """, rel=NO_SCOPE, select=["JL003"])
    assert rules_of(r) == []


# ------------------------------------------------ JL004 donation-after-use
def test_jl004_read_after_donating_dispatch(tmp_path):
    r = lint(tmp_path, """
        import jax

        def step(buf, x):
            return buf + x

        run = jax.jit(step, donate_argnums=(0,))

        def drive(buf, x):
            out = run(buf, x)
            return buf.sum() + out.sum()
    """, select=["JL004"])
    assert rules_of(r) == ["JL004"]
    assert "`buf` was donated" in r.active[0].message


def test_jl004_module_const_indirection(tmp_path):
    r = lint(tmp_path, """
        import jax

        _DONATE = (0,)

        def step(buf, x):
            return buf + x

        run = jax.jit(step, donate_argnums=_DONATE)
        y = run(table, delta)
        z = table + y
    """, select=["JL004"])
    assert rules_of(r) == ["JL004"]


def test_jl004_negative_reassignment_restores_ownership(tmp_path):
    r = lint(tmp_path, """
        import jax

        def step(buf, x):
            return buf + x

        run = jax.jit(step, donate_argnums=(0,))

        def drive(buf, x):
            buf = run(buf, x)
            return buf.sum()
    """, select=["JL004"])
    assert rules_of(r) == []


# -------------------------------------------- JL005 untraced-python-branch
def test_jl005_if_on_traced_param(tmp_path):
    r = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """, select=["JL005"])
    assert rules_of(r) == ["JL005"]
    assert "lax.cond" in r.active[0].message


def test_jl005_for_over_traced_param(tmp_path):
    r = lint(tmp_path, """
        import jax

        def body(xs):
            total = 0.0
            for x in xs:
                total = total + x
            return total

        run = jax.jit(body)
    """, select=["JL005"])
    assert rules_of(r) == ["JL005"]


def test_jl005_negative_static_argnames(tmp_path):
    r = lint(tmp_path, """
        from functools import partial

        import jax

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            if n > 2:
                return x
            return x * 2.0
    """, select=["JL005"])
    assert rules_of(r) == []


def test_jl005_negative_config_hint_and_shape(tmp_path):
    r = lint(tmp_path, """
        import jax

        @jax.jit
        def f(x, cfg):
            if cfg.deep:
                x = x * 2.0
            if x.ndim == 2:
                return x
            return x[None]
    """, select=["JL005"])
    assert rules_of(r) == []


# --------------------------------------------- JL006 vmap-of-pallas_call
def test_jl006_vmap_of_local_pallas_wrapper(tmp_path):
    r = lint(tmp_path, """
        import jax
        from jax.experimental import pallas as pl

        def op(x):
            return pl.pallas_call(kernel, out_shape=None)(x)

        batched = jax.vmap(op)
    """, select=["JL006"])
    assert rules_of(r) == ["JL006"]
    assert "batch" in r.active[0].message


def test_jl006_vmap_of_repro_kernels_op(tmp_path):
    r = lint(tmp_path, """
        import jax
        from repro.kernels.segment_stats.ops import segment_stats

        v = jax.vmap(segment_stats)
    """, select=["JL006"])
    assert rules_of(r) == ["JL006"]


def test_jl006_negative_vmap_of_plain_function(tmp_path):
    r = lint(tmp_path, """
        import jax
        import jax.numpy as jnp

        def plain(x):
            return jnp.sum(x * x)

        v = jax.vmap(plain)
    """, select=["JL006"])
    assert rules_of(r) == []


# ------------------------------------------------------ JL100 api-surface
def test_jl100_missing_dunder_all(tmp_path):
    r = lint(tmp_path, """
        X = 1
    """, select=["JL100"])
    assert rules_of(r) == ["JL100"]
    assert "__all__" in r.active[0].message


def test_jl100_string_literal_dispatch(tmp_path):
    r = lint(tmp_path, """
        __all__ = []

        def pick(scheme):
            if scheme == "bbv":
                return 1
            return 0
    """, select=["JL100"])
    assert rules_of(r) == ["JL100"]
    assert "registry" in r.active[0].message


def test_jl100_isinstance_dispatch_on_plan_type(tmp_path):
    r = lint(tmp_path, """
        __all__ = []

        def handle(s):
            return isinstance(s, (Stratifier, Centroid))
    """, select=["JL100"])
    assert rules_of(r) == ["JL100"]
    assert "isinstance" in r.active[0].message


def test_jl100_negative_plan_module_may_dispatch(tmp_path):
    r = lint(tmp_path, """
        __all__ = []

        def lookup(scheme, s):
            if scheme == "bbv" and isinstance(s, Stratifier):
                return 1
            return 0
    """, rel="src/repro/core/sampling/plan.py", select=["JL100"])
    assert rules_of(r) == []


def test_jl100_negative_clean_module(tmp_path):
    r = lint(tmp_path, """
        __all__ = ["f"]

        def f(kind):
            return kind == "weighted"
    """, select=["JL100"])
    assert rules_of(r) == []


# ------------------------------------------------ JL101 missing-docstring
def test_jl101_missing_module_docstring(tmp_path):
    r = lint(tmp_path, """
        X = 1
    """, rel=EXP_SCOPE, select=["JL101"])
    assert rules_of(r) == ["JL101"]


def test_jl101_missing_public_function_and_class_docstrings(tmp_path):
    r = lint(tmp_path, '''
        """Module docstring."""

        def public_fn():
            return 1

        class PublicClass:
            pass
    ''', rel=EXP_SCOPE, select=["JL101"])
    assert rules_of(r) == ["JL101", "JL101"]


def test_jl101_negative_documented_and_private(tmp_path):
    r = lint(tmp_path, '''
        """Module docstring."""

        def public_fn():
            """Documented."""

        def _private_fn():
            return 1
    ''', rel=EXP_SCOPE, select=["JL101"])
    assert rules_of(r) == []


# ------------------------------------------------ JL102 broken-doc-link
def test_jl102_broken_link_and_missing_anchor(tmp_path):
    (tmp_path / "README.md").write_text(
        "# Real Heading\n\n[gone](docs/missing.md)\n[frag](#nope)\n")
    r = run_lint(None, root=tmp_path, baseline_path=tmp_path / "bl.json",
                 select=["JL102"])
    assert rules_of(r) == ["JL102", "JL102"]


def test_jl102_negative_resolving_links(tmp_path):
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "guide.md").write_text("# Guide Heading\n")
    (tmp_path / "README.md").write_text(
        "# Top\n\n[ok](docs/guide.md#guide-heading)\n[self](#top)\n"
        "[web](https://example.com)\n")
    r = run_lint(None, root=tmp_path, baseline_path=tmp_path / "bl.json",
                 select=["JL102"])
    assert rules_of(r) == []


# ------------------------------------------------------------ suppression
_VIOLATION = """
    import jax.numpy as jnp

    X = jnp.asarray([1.0], jnp.float32)
"""


def test_inline_suppression_comment(tmp_path):
    code = _VIOLATION.replace(
        "jnp.float32)", "jnp.float32)  # jaxlint: disable=JL003")
    r = lint(tmp_path, code, select=["JL003"])
    assert rules_of(r) == []
    assert r.suppressed == 1


def test_file_level_suppression_comment(tmp_path):
    r = lint(tmp_path, """
        # jaxlint: disable-file=JL003
        import jax.numpy as jnp

        X = jnp.asarray([1.0], jnp.float32)
        Y = jnp.asarray([2.0], jnp.float16)
    """, select=["JL003"])
    assert rules_of(r) == []
    assert r.suppressed == 2


def test_suppression_is_rule_specific(tmp_path):
    code = _VIOLATION.replace(
        "jnp.float32)", "jnp.float32)  # jaxlint: disable=JL001")
    r = lint(tmp_path, code, select=["JL003"])
    assert rules_of(r) == ["JL003"]       # wrong rule id: not covered


# --------------------------------------------------------------- baseline
def test_baseline_roundtrip_and_staleness(tmp_path):
    bl = tmp_path / "baseline.json"
    r1 = lint(tmp_path, _VIOLATION, select=["JL003"])
    assert rules_of(r1) == ["JL003"] and not r1.ok

    r2 = lint(tmp_path, _VIOLATION, select=["JL003"], update_baseline=True)
    assert bl.exists() and len(r2.baselined) == 1

    entries = json.loads(bl.read_text())["entries"]
    assert len(entries) == 1 and entries[0]["rule"] == "JL003"
    assert entries[0]["justification"]          # placeholder is non-empty

    r3 = lint(tmp_path, _VIOLATION, select=["JL003"])
    assert r3.ok and rules_of(r3) == [] and len(r3.baselined) == 1

    # fixing the violation makes the baseline entry stale -> build fails
    r4 = lint(tmp_path, "import jax.numpy as jnp\nX = 1\n",
              select=["JL003"])
    assert not r4.ok and len(r4.stale) == 1 and rules_of(r4) == []


def test_baseline_survives_line_drift_but_not_new_violations(tmp_path):
    lint(tmp_path, _VIOLATION, select=["JL003"], update_baseline=True)
    drifted = "import jax.numpy as jnp\n\n\n# pushed down\n" \
        "X = jnp.asarray([1.0], jnp.float32)\n"
    r = lint(tmp_path, drifted, select=["JL003"])
    assert r.ok and len(r.baselined) == 1     # same code line, new lineno

    doubled = drifted + "Y = jnp.asarray([2.0], jnp.float32)\n"
    r2 = lint(tmp_path, doubled, select=["JL003"])
    assert rules_of(r2) == ["JL003"]          # the NEW line is active


# ------------------------------------------------------------ JSON schema
def test_json_report_schema(tmp_path):
    r = lint(tmp_path, _VIOLATION, select=["JL003"])
    d = r.to_json()
    assert d["version"] == 1
    assert set(d) == {"version", "root", "rules", "findings", "summary"}
    assert [row["id"] for row in d["rules"]] == sorted(RULES)
    f = d["findings"][0]
    assert set(f) == {"rule", "path", "line", "col", "message", "status"}
    assert f["status"] == "active"
    s = d["summary"]
    assert {"files", "active", "baselined", "suppressed", "stale_baseline",
            "errors", "duration_s", "ok"} <= set(s)
    assert s["active"] == 1 and s["ok"] is False
    json.dumps(d)                             # round-trips to JSON


# ------------------------------------------------------------------- CLI
def test_cli_list_rules_and_bad_select(capsys):
    assert main(["--list-rules"]) == 0
    assert "JL001" in capsys.readouterr().out
    assert main(["--select", "JL999"]) == 2


def test_cli_json_exit_codes(tmp_path, capsys):
    path = tmp_path / IN_SCOPE
    path.parent.mkdir(parents=True)
    path.write_text(textwrap.dedent(_VIOLATION))
    code = main([IN_SCOPE, "--root", str(tmp_path), "--select", "JL003",
                 "--baseline", str(tmp_path / "bl.json"), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert code == 1 and out["summary"]["active"] == 1


# ------------------------------------------------------------- self-check
def test_repo_lints_clean():
    """The committed tree passes its own gate (active findings = 0,
    every baseline entry alive and justified)."""
    report = run_lint(root=REPO_ROOT)
    detail = "\n".join(f.render() for f in report.active) or report.errors
    assert report.ok, f"repo must lint clean:\n{detail}"
    for entry in json.loads(
            (REPO_ROOT / "lint_baseline.json").read_text())["entries"]:
        assert "grandfathered" not in entry["justification"], \
            f"unjustified baseline entry: {entry}"
