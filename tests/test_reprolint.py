"""Tier-13 static analysis: the reprolint framework and every rule.

Each rule gets at least one firing and one non-firing fixture, plus the
framework pieces (suppressions, baseline round-trip) and the repo-level
meta check: the shipped tree must be clean against the committed
baseline. Fixtures go through ``FileContext.from_source`` / an injected
``RepoContext`` so no disk or git state is needed.
"""
import pathlib

import pytest

from tools.reprolint import (
    FileContext, RepoContext, all_rules, apply_baseline, build_repo_context,
    collect_files, load_baseline, run_rules, save_baseline,
)

REPO = pathlib.Path(__file__).resolve().parents[1]


def lint(sources, rules=None, **repo_kw):
    """Run the given rules over {relpath: source} fixtures."""
    files = [FileContext.from_source(p, s) for p, s in sources.items()]
    ctx = RepoContext(files=files, **repo_kw)
    return run_rules(ctx, all_rules(rules))


def names(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# framework
# ---------------------------------------------------------------------------


class TestFramework:
    def test_syntax_error_pseudo_finding(self):
        fs = lint({"src/x.py": "def broken(:\n"}, rules=[])
        assert names(fs) == ["syntax-error"]

    def test_unknown_rule_rejected(self):
        with pytest.raises(ValueError, match="unknown rule"):
            all_rules(["no-such-rule"])

    def test_rule_catalogue_complete(self):
        # the ISSUE's contract set, all registered with valid severities
        expected = {"twin-purity", "precision-contract", "traced-branch",
                    "engine-numpy", "key-reuse", "config-validation",
                    "json-hygiene", "dead-leaf", "bench-registry",
                    "design-ref", "repo-hygiene"}
        got = {r.name: r for r in all_rules()}
        assert expected <= set(got)
        assert all(r.severity in ("error", "warn") for r in got.values())
        assert all(r.description for r in got.values())

    def test_collect_files_skips_pycache(self, tmp_path):
        (tmp_path / "pkg" / "__pycache__").mkdir(parents=True)
        (tmp_path / "pkg" / "a.py").write_text("x = 1\n")
        (tmp_path / "pkg" / "__pycache__" / "a.py").write_text("x = 1\n")
        files = collect_files(["pkg"], tmp_path)
        assert [f.relpath for f in files] == ["pkg/a.py"]


TWIN_BAD = "import numpy as np\nimport jax\n"


class TestSuppressions:
    def test_same_line_disable(self):
        src = "import jax  # reprolint: disable=twin-purity\n"
        assert lint({"src/repro/sim/numpy_ref.py": src}) == []

    def test_disable_next_line(self):
        src = "# reprolint: disable-next-line=twin-purity\nimport jax\n"
        assert lint({"src/repro/sim/numpy_ref.py": src}) == []

    def test_disable_all(self):
        src = "import jax  # reprolint: disable=all\n"
        assert lint({"src/repro/sim/numpy_ref.py": src}) == []

    def test_other_rule_does_not_suppress(self):
        src = "import jax  # reprolint: disable=json-hygiene\n"
        fs = lint({"src/repro/sim/numpy_ref.py": src})
        assert names(fs) == ["twin-purity"]


class TestBaseline:
    def test_round_trip(self, tmp_path):
        bl = tmp_path / "baseline.json"
        findings = lint({"src/repro/sim/numpy_ref.py": TWIN_BAD})
        assert names(findings) == ["twin-purity"]
        save_baseline(bl, findings)
        new, old, stale = apply_baseline(findings, load_baseline(bl))
        assert new == [] and len(old) == 1 and stale == []

    def test_baseline_survives_line_moves(self, tmp_path):
        bl = tmp_path / "baseline.json"
        save_baseline(bl, lint({"src/repro/sim/numpy_ref.py": TWIN_BAD}))
        moved = "import numpy as np\n\n\nimport jax\n"
        new, old, _ = apply_baseline(
            lint({"src/repro/sim/numpy_ref.py": moved}), load_baseline(bl))
        assert new == [] and len(old) == 1

    def test_fixed_finding_goes_stale(self, tmp_path):
        bl = tmp_path / "baseline.json"
        save_baseline(bl, lint({"src/repro/sim/numpy_ref.py": TWIN_BAD}))
        clean = "import numpy as np\n"
        new, old, stale = apply_baseline(
            lint({"src/repro/sim/numpy_ref.py": clean}), load_baseline(bl))
        assert new == [] and old == [] and len(stale) == 1

    def test_malformed_baseline_rejected(self, tmp_path):
        bl = tmp_path / "baseline.json"
        bl.write_text("[]")
        with pytest.raises(ValueError, match="malformed"):
            load_baseline(bl)


# ---------------------------------------------------------------------------
# contract rules
# ---------------------------------------------------------------------------


class TestTwinPurity:
    def test_fires_on_jax_import(self):
        fs = lint({"src/repro/sim/numpy_ref.py": TWIN_BAD})
        assert names(fs) == ["twin-purity"]

    def test_fires_on_from_import(self):
        src = "from jax.numpy import where\n"
        assert names(lint({"src/repro/core/plan.py": src})) == ["twin-purity"]

    def test_numpy_only_twin_is_clean(self):
        assert lint({"src/repro/sim/numpy_ref.py": "import numpy as np\n"},
                    rules=["twin-purity"]) == []

    def test_jax_outside_twins_is_fine(self):
        assert lint({"src/repro/core/engine.py": "import jax\n"},
                    rules=["twin-purity"]) == []


class TestPrecisionContract:
    def test_float64_in_engine_fires(self):
        src = "import jax.numpy as jnp\nx = jnp.zeros(3, jnp.float64)\n"
        fs = lint({"src/repro/core/engine.py": src},
                  rules=["precision-contract"])
        assert names(fs) == ["precision-contract"]

    def test_dtype_string_kw_fires(self):
        src = "import jax.numpy as jnp\nx = jnp.zeros(3, dtype='float64')\n"
        fs = lint({"src/repro/kernels/rates.py": src},
                  rules=["precision-contract"])
        assert names(fs) == ["precision-contract"]

    def test_astype_fires(self):
        src = "def f(x):\n    return x.astype('float64')\n"
        fs = lint({"src/repro/core/matching.py": src},
                  rules=["precision-contract"])
        assert names(fs) == ["precision-contract"]

    def test_float32_in_twin_fires(self):
        src = "import numpy as np\nx = np.zeros(3, np.float32)\n"
        fs = lint({"src/repro/core/scheduler.py": src},
                  rules=["precision-contract"])
        assert names(fs) == ["precision-contract"]

    def test_correct_sides_are_clean(self):
        ok = {
            "src/repro/core/engine.py":
                "import jax.numpy as jnp\nx = jnp.zeros(3, jnp.float32)\n",
            "src/repro/core/scheduler.py":
                "import numpy as np\nx = np.zeros(3, np.float64)\n",
        }
        assert lint(ok, rules=["precision-contract"]) == []

    def test_bfloat16_in_engine_fires(self):
        src = "import jax.numpy as jnp\nx = jnp.zeros(3, jnp.bfloat16)\n"
        fs = lint({"src/repro/core/engine.py": src},
                  rules=["precision-contract"])
        assert names(fs) == ["precision-contract"]
        assert "DESIGN.md section 13" in fs[0].message

    def test_bfloat16_dtype_string_fires_in_kernels(self):
        src = "import jax.numpy as jnp\nx = jnp.zeros(3, dtype='bfloat16')\n"
        fs = lint({"src/repro/kernels/rates.py": src},
                  rules=["precision-contract"])
        assert names(fs) == ["precision-contract"]

    def test_bfloat16_sanctioned_in_planner(self):
        # planner.py is the ONE sanctioned mixed-precision kernel: its
        # bf16 table tiles are the whole point (DESIGN.md section 13)
        src = ("import jax.numpy as jnp\n"
               "x = jnp.zeros((8, 128), jnp.bfloat16)\n")
        assert lint({"src/repro/kernels/planner.py": src},
                    rules=["precision-contract"]) == []

    def test_bfloat16_outside_engine_is_fine(self):
        src = "import jax.numpy as jnp\nx = jnp.zeros(3, jnp.bfloat16)\n"
        assert lint({"src/repro/models/rwkv.py": src},
                    rules=["precision-contract"]) == []


CONFIG_OK = """\
_POST_INIT_EXEMPT = ("seed",)


class FLConfig:
    lr: float = 0.1
    rounds: int = 10
    seed: int = 0

    def __post_init__(self):
        for f in ("lr",):
            if getattr(self, f) <= 0:
                raise ValueError(f)
        if self.rounds < 1:
            raise ValueError("bad")
"""


class TestConfigValidation:
    PATH = "src/repro/configs/base.py"

    def test_covered_fields_are_clean(self):
        assert lint({self.PATH: CONFIG_OK}, rules=["config-validation"]) == []

    def test_unvalidated_field_fires(self):
        src = CONFIG_OK.replace("    seed: int = 0",
                                "    seed: int = 0\n    extra: float = 1.0")
        fs = lint({self.PATH: src}, rules=["config-validation"])
        assert names(fs) == ["config-validation"]
        assert "extra" in fs[0].message

    def test_stale_exempt_entry_fires(self):
        src = CONFIG_OK.replace('("seed",)', '("seed", "ghost")')
        fs = lint({self.PATH: src}, rules=["config-validation"])
        assert names(fs) == ["config-validation"]
        assert "ghost" in fs[0].message

    def test_other_files_ignored(self):
        src = "class FLConfig:\n    mystery: int = 0\n"
        assert lint({"src/repro/fl/other.py": src},
                    rules=["config-validation"]) == []


class TestJsonHygiene:
    def test_bare_dump_fires(self):
        src = "import json\njson.dump({}, open('x', 'w'))\n"
        fs = lint({"src/a.py": src}, rules=["json-hygiene"])
        assert names(fs) == ["json-hygiene"]

    def test_bare_dumps_fires(self):
        src = "import json\ns = json.dumps({'a': 1})\n"
        fs = lint({"src/a.py": src}, rules=["json-hygiene"])
        assert names(fs) == ["json-hygiene"]

    def test_allow_nan_false_is_clean(self):
        src = "import json\njson.dump({}, open('x', 'w'), allow_nan=False)\n"
        assert lint({"src/a.py": src}, rules=["json-hygiene"]) == []

    def test_json_safe_payload_is_clean(self):
        src = ("import json\nfrom repro.obs.metrics import json_safe\n"
               "s = json.dumps(json_safe({'a': 1}))\n")
        assert lint({"src/a.py": src}, rules=["json-hygiene"]) == []


class TestDeadLeaf:
    def test_unread_leaf_fires(self):
        src = ("from typing import NamedTuple\n"
               "class S(NamedTuple):\n"
               "    used: int\n"
               "    unused: int\n"
               "def f(s):\n"
               "    return s.used\n")
        fs = lint({"src/repro/sim/s.py": src}, rules=["dead-leaf"])
        assert names(fs) == ["dead-leaf"]
        assert "S.unused" in fs[0].message

    def test_read_in_another_file_is_clean(self):
        srcs = {
            "src/repro/sim/s.py": ("from typing import NamedTuple\n"
                                   "class S(NamedTuple):\n"
                                   "    leaf: int\n"),
            "tests/test_s.py": "def test(s):\n    assert s.leaf == 1\n",
        }
        assert lint(srcs, rules=["dead-leaf"]) == []

    def test_non_src_namedtuples_ignored(self):
        src = ("from typing import NamedTuple\n"
               "class T(NamedTuple):\n"
               "    scratch: int\n")
        assert lint({"tests/helpers.py": src}, rules=["dead-leaf"]) == []


BENCH_RUN = """\
_NON_BENCH = {"run", "__init__"}
_ALIASES = {"kernels": "kernels_bench"}


def _k():
    pass


def _f():
    pass


BENCHES = {"kernels": _k, "foo": _f}
"""


class TestBenchRegistry:
    def test_registered_modules_are_clean(self):
        srcs = {"benchmarks/run.py": BENCH_RUN,
                "benchmarks/kernels_bench.py": "x = 1\n",
                "benchmarks/foo.py": "x = 1\n"}
        assert lint(srcs, rules=["bench-registry"]) == []

    def test_unregistered_module_fires(self):
        srcs = {"benchmarks/run.py": BENCH_RUN,
                "benchmarks/kernels_bench.py": "x = 1\n",
                "benchmarks/foo.py": "x = 1\n",
                "benchmarks/bar.py": "x = 1\n"}
        fs = lint(srcs, rules=["bench-registry"])
        assert names(fs) == ["bench-registry"]
        assert "bar" in fs[0].message

    def test_stale_registry_entry_fires(self):
        srcs = {"benchmarks/run.py": BENCH_RUN,
                "benchmarks/kernels_bench.py": "x = 1\n"}
        fs = lint(srcs, rules=["bench-registry"])
        assert names(fs) == ["bench-registry"]
        assert "foo" in fs[0].message


DESIGN = "## 1. Intro\n\n## 2. Twins\n\n## 3. Engine\n"


class TestDesignRef:
    def test_resolving_reference_is_clean(self):
        src = "# contract per DESIGN.md section 2\n"
        assert lint({"src/a.py": src}, rules=["design-ref"],
                    design_md=DESIGN) == []

    def test_range_reference_checked(self):
        src = "# see DESIGN.md sections 2-3\n"
        assert lint({"src/a.py": src}, rules=["design-ref"],
                    design_md=DESIGN) == []

    def test_dangling_reference_fires(self):
        src = "# see DESIGN.md section 9\n"
        fs = lint({"src/a.py": src}, rules=["design-ref"], design_md=DESIGN)
        assert names(fs) == ["design-ref"]


GITIGNORE_OK = ("__pycache__/\n*.pyc\nexperiments/runs/\n.hypothesis/\n"
                ".jax_cache/\n")


class TestRepoHygiene:
    def test_clean_repo(self):
        assert lint({}, rules=["repo-hygiene"], gitignore=GITIGNORE_OK,
                    tracked_files=["src/a.py", "tests/test_a.py"]) == []

    def test_tracked_pycache_fires(self):
        fs = lint({}, rules=["repo-hygiene"], gitignore=GITIGNORE_OK,
                  tracked_files=["src/__pycache__/a.cpython-311.pyc"])
        assert names(fs) == ["repo-hygiene"]

    def test_tracked_run_ledger_fires(self):
        fs = lint({}, rules=["repo-hygiene"], gitignore=GITIGNORE_OK,
                  tracked_files=["experiments/runs/r1/ledger.jsonl"])
        assert names(fs) == ["repo-hygiene"]

    @pytest.mark.parametrize("path", [
        ".hypothesis/constants/0123abcd",
        ".jax_cache/jit_step-0123abcd-cache"])
    def test_tracked_cache_dir_fires(self, path):
        fs = lint({}, rules=["repo-hygiene"], gitignore=GITIGNORE_OK,
                  tracked_files=[path])
        assert names(fs) == ["repo-hygiene"]

    def test_missing_gitignore_pattern_fires(self):
        fs = lint({}, rules=["repo-hygiene"], gitignore="*.pyc\n",
                  tracked_files=[])
        # __pycache__/, experiments/runs/, .hypothesis/, .jax_cache/
        assert len(fs) == 4


# ---------------------------------------------------------------------------
# flow rules
# ---------------------------------------------------------------------------


JIT_HEADER = "import functools\nimport jax\nimport jax.numpy as jnp\n"


class TestTracedBranch:
    def test_branch_on_traced_param_fires(self):
        src = JIT_HEADER + (
            "@functools.partial(jax.jit, static_argnames=('n',))\n"
            "def f(x, n):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return x + n\n")
        fs = lint({"src/a.py": src}, rules=["traced-branch"])
        assert names(fs) == ["traced-branch"]
        assert "`f`" in fs[0].message

    def test_branch_on_static_param_is_clean(self):
        src = JIT_HEADER + (
            "@functools.partial(jax.jit, static_argnames=('n',))\n"
            "def f(x, n):\n"
            "    if n > 2:\n"
            "        return x * 2.0\n"
            "    return x\n")
        assert lint({"src/a.py": src}, rules=["traced-branch"]) == []

    def test_shape_metadata_branch_is_clean(self):
        src = JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    if x.ndim == 2 and len(x) > 1:\n"
            "        return x.sum(0)\n"
            "    return x\n")
        assert lint({"src/a.py": src}, rules=["traced-branch"]) == []

    def test_is_none_branch_is_clean(self):
        # structural checks retrace per pytree structure — legal
        src = JIT_HEADER + (
            "@jax.jit\n"
            "def f(x, cell=None):\n"
            "    if cell is not None:\n"
            "        return x + cell\n"
            "    return x\n")
        assert lint({"src/a.py": src}, rules=["traced-branch"]) == []

    def test_taint_propagates_through_assignment(self):
        src = JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    y = x * 2\n"
            "    while y.sum() > 0:\n"
            "        y = y - 1\n"
            "    return y\n")
        fs = lint({"src/a.py": src}, rules=["traced-branch"])
        assert names(fs) == ["traced-branch"]

    def test_unjitted_function_ignored(self):
        src = JIT_HEADER + "def f(x):\n    if x > 0:\n        return x\n"
        assert lint({"src/a.py": src}, rules=["traced-branch"]) == []


class TestEngineNumpy:
    def test_np_on_traced_fires(self):
        src = JIT_HEADER + ("import numpy as np\n"
                            "@jax.jit\n"
                            "def f(x):\n"
                            "    return np.sum(x)\n")
        fs = lint({"src/a.py": src}, rules=["engine-numpy"])
        assert names(fs) == ["engine-numpy"]

    def test_np_on_constants_is_clean(self):
        src = JIT_HEADER + ("import numpy as np\n"
                            "@jax.jit\n"
                            "def f(x):\n"
                            "    return x + np.zeros(3)\n")
        assert lint({"src/a.py": src}, rules=["engine-numpy"]) == []

    def test_np_on_static_arg_is_clean(self):
        src = JIT_HEADER + (
            "import numpy as np\n"
            "@functools.partial(jax.jit, static_argnames=('n',))\n"
            "def f(x, n):\n"
            "    return x + np.arange(n)\n")
        assert lint({"src/a.py": src}, rules=["engine-numpy"]) == []


KEY_HEADER = "import jax\n"


class TestKeyReuse:
    def test_double_consumption_fires(self):
        src = KEY_HEADER + (
            "def f(key):\n"
            "    a = jax.random.normal(key)\n"
            "    b = jax.random.uniform(key)\n"
            "    return a + b\n")
        fs = lint({"src/a.py": src}, rules=["key-reuse"])
        assert names(fs) == ["key-reuse"]
        assert "`key`" in fs[0].message

    def test_fold_in_derivation_is_clean(self):
        # the repo idiom: derive per-use keys, never reuse raw entropy
        src = KEY_HEADER + (
            "def f(key):\n"
            "    a = jax.random.normal(key)\n"
            "    b = jax.random.uniform(jax.random.fold_in(key, 1))\n"
            "    return a + b\n")
        assert lint({"src/a.py": src}, rules=["key-reuse"]) == []

    def test_split_refresh_is_clean(self):
        src = KEY_HEADER + (
            "def f(key):\n"
            "    a = jax.random.normal(key)\n"
            "    key, sub = jax.random.split(jax.random.PRNGKey(0))\n"
            "    b = jax.random.normal(key)\n"
            "    return a + b\n")
        assert lint({"src/a.py": src}, rules=["key-reuse"]) == []

    def test_exclusive_branches_are_clean(self):
        src = KEY_HEADER + (
            "def f(key, flag):\n"
            "    if flag:\n"
            "        return jax.random.normal(key)\n"
            "    return jax.random.uniform(key)\n")
        assert lint({"src/a.py": src}, rules=["key-reuse"]) == []

    def test_consumption_in_loop_fires(self):
        src = KEY_HEADER + (
            "def f(key):\n"
            "    out = []\n"
            "    for i in range(3):\n"
            "        out.append(jax.random.normal(key))\n"
            "    return out\n")
        fs = lint({"src/a.py": src}, rules=["key-reuse"])
        assert names(fs) == ["key-reuse"]
        assert "loop" in fs[0].message

    def test_per_iteration_fold_in_is_clean(self):
        src = KEY_HEADER + (
            "def f(key):\n"
            "    out = []\n"
            "    for i in range(3):\n"
            "        out.append(jax.random.normal(jax.random.fold_in(key, i)))\n"
            "    return out\n")
        assert lint({"src/a.py": src}, rules=["key-reuse"]) == []

    def test_non_jax_file_skipped(self):
        src = "def f(key):\n    g(key)\n    h(key)\n"
        assert lint({"src/a.py": src}, rules=["key-reuse"]) == []


# ---------------------------------------------------------------------------
# acceptance: the ISSUE's two deliberate regressions, against real sources
# ---------------------------------------------------------------------------


class TestAcceptance:
    def test_jax_import_in_numpy_ref_fires(self):
        real = (REPO / "src/repro/sim/numpy_ref.py").read_text()
        fs = lint({"src/repro/sim/numpy_ref.py": "import jax\n" + real},
                  rules=["twin-purity"])
        assert names(fs) == ["twin-purity"]

    def test_pr7_dead_fading_leaf_fires(self):
        # PR 7 shipped a fading leaf that was threaded through every jit
        # boundary but never read; re-introducing that shape must fire
        files = collect_files(["src"], REPO)
        bug = FileContext.from_source(
            "src/repro/sim/fading_cache.py",
            "from typing import NamedTuple\n"
            "class FadingCache(NamedTuple):\n"
            "    fading_gain_seq: object\n")
        ctx = RepoContext(files=files + [bug])
        fs = [f for f in run_rules(ctx, all_rules(["dead-leaf"]))
              if f.path == bug.relpath]
        assert names(fs) == ["dead-leaf"]
        assert "fading_gain_seq" in fs[0].message


# ---------------------------------------------------------------------------
# meta: the shipped tree is clean against the committed baseline
# ---------------------------------------------------------------------------


class TestRepoIsClean:
    def test_no_unbaselined_findings(self):
        files = collect_files(["src", "tests", "benchmarks"], REPO)
        assert len(files) > 50  # sanity: we really swept the tree
        ctx = build_repo_context(files, REPO)
        findings = run_rules(ctx, all_rules())
        baseline = load_baseline(REPO / "tools/reprolint/baseline.json")
        new, _, stale = apply_baseline(findings, baseline)
        errors = [f for f in new if f.severity == "error"]
        assert not errors, "reprolint findings:\n" + "\n".join(
            f.render() for f in errors)
        assert not stale, f"stale baseline entries: {stale}"
