"""Contract rules: twin purity, precision boundaries, eager config
validation, json hygiene, dead pytree leaves, and cross-reference /
repo-hygiene checks.

Module scoping: the fp64 reference twins (``TWIN_MODULES``) are the
semantic ground truth every jax path is parity-tested against
(DESIGN.md sections 5-8) — they must stay importable and runnable with
numpy alone, in fp64. The engine/kernel paths (``ENGINE_MODULES``) are
the fixed-shape fp32 jit surface — fp64 there either silently upcasts
a whole pipeline or (under default jax config) silently truncates,
either way diverging from the twin the tests compare against.
"""
from __future__ import annotations

import ast
import re
from typing import Iterable, List, Set

from tools.reprolint.core import (FileContext, Finding, RepoContext, Rule,
                                  register)
from tools.reprolint.flow import dotted_name, import_aliases

# fp64 numpy reference twins: no jax, no float32
TWIN_MODULES = (
    "repro/core/plan.py",
    "repro/core/pairing.py",
    "repro/core/noma.py",
    "repro/core/aoi.py",
    "repro/core/roundtime.py",
    "repro/core/scheduler.py",
    "repro/sim/numpy_ref.py",
)

# fp32 fixed-shape jit surface: no float64
ENGINE_MODULES = (
    "repro/core/engine.py",
    "repro/core/matching.py",
    "repro/kernels/",
)

# the ONLY modules allowed to mention bfloat16 on the engine side: the
# fused planner kernel stores its O(c^2) completion-table tiles bf16
# under the mixed-precision contract (DESIGN.md section 13); everywhere
# else bf16 silently halves the precision of threshold math the parity
# tolerances assume is fp32
SANCTIONED_BF16 = (
    "repro/kernels/planner.py",
)


def _is_twin(relpath: str) -> bool:
    return any(relpath.endswith(m) for m in TWIN_MODULES)


def _is_engine(relpath: str) -> bool:
    return any(m in relpath for m in ENGINE_MODULES)


@register
class TwinPurityRule(Rule):
    """The numpy twins are the golden reference the engine is tested
    against; importing jax there couples the reference to the thing it
    checks (and breaks fp64 purity via silent x32 defaults)."""
    name = "twin-purity"
    severity = "error"
    description = ("fp64 reference twin modules must not import jax "
                   "(directly or via jax.* submodules)")

    def check_file(self, fc: FileContext) -> Iterable[Finding]:
        if not _is_twin(fc.relpath):
            return
        for node in ast.walk(fc.tree):
            mods: List[str] = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module]
            for mod in mods:
                if mod == "jax" or mod.startswith("jax."):
                    yield self.finding(
                        fc.relpath, node.lineno,
                        f"fp64 reference twin imports `{mod}` — twins "
                        f"must stay numpy-only (DESIGN.md section 5)")


@register
class PrecisionContractRule(Rule):
    """fp32 on the engine side, fp64 on the twin side — the parity
    tests' tolerances encode exactly this split."""
    name = "precision-contract"
    severity = "error"
    description = ("no float64 in engine/kernel modules (and no bfloat16 "
                   "outside the sanctioned kernel tables); no float32 in "
                   "fp64 reference twins")

    def check_file(self, fc: FileContext) -> Iterable[Finding]:
        if _is_engine(fc.relpath):
            banned = {"float64": "violates the precision contract "
                                 "(DESIGN.md section 5)"}
            if not any(fc.relpath.endswith(m) for m in SANCTIONED_BF16):
                banned["bfloat16"] = (
                    "violates the mixed-precision contract — bf16 lives "
                    "only in the sanctioned kernel table tiles "
                    "(SANCTIONED_BF16; DESIGN.md section 13)")
        elif _is_twin(fc.relpath):
            banned = {"float32": "violates the precision contract "
                                 "(DESIGN.md section 5)"}
        else:
            return
        for node in ast.walk(fc.tree):
            hit = None
            if isinstance(node, ast.Attribute) and node.attr in banned:
                hit = node.attr
            elif isinstance(node, ast.keyword) and node.arg == "dtype" \
                    and isinstance(node.value, ast.Constant) \
                    and node.value.value in banned:
                hit = node.value.value
            elif isinstance(node, ast.Call):
                fname = dotted_name(node.func, {}) or ""
                if fname.endswith(".astype") and node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        node.args[0].value in banned:
                    hit = node.args[0].value
            if hit:
                side = ("engine/kernel" if _is_engine(fc.relpath)
                        else "fp64 twin")
                yield self.finding(
                    fc.relpath, node.lineno,
                    f"`{hit}` in {side} module — {banned[hit]}")


@register
class ConfigValidationRule(Rule):
    """FLConfig must fail at construction, not as NaN/shape nonsense
    deep inside a Monte-Carlo sweep. Every field is either referenced
    in ``__post_init__`` or explicitly exempted (with a reason) in the
    module-level ``_POST_INIT_EXEMPT`` tuple."""
    name = "config-validation"
    severity = "error"
    description = ("every FLConfig field appears in __post_init__ "
                   "validation or in the _POST_INIT_EXEMPT allowlist")

    target = "repro/configs/base.py"
    classname = "FLConfig"

    def check_file(self, fc: FileContext) -> Iterable[Finding]:
        if not fc.relpath.endswith(self.target):
            return
        exempt: Set[str] = set()
        for node in ast.walk(fc.tree):
            if isinstance(node, ast.Assign):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                if "_POST_INIT_EXEMPT" in names:
                    try:
                        exempt = set(ast.literal_eval(node.value))
                    except (ValueError, SyntaxError):
                        pass
        cls = next((n for n in ast.walk(fc.tree)
                    if isinstance(n, ast.ClassDef)
                    and n.name == self.classname), None)
        if cls is None:
            return
        fields = {st.target.id: st.lineno for st in cls.body
                  if isinstance(st, ast.AnnAssign)
                  and isinstance(st.target, ast.Name)}
        post = next((st for st in cls.body
                     if isinstance(st, ast.FunctionDef)
                     and st.name == "__post_init__"), None)
        referenced: Set[str] = set()
        if post is not None:
            for node in ast.walk(post):
                if isinstance(node, ast.Attribute) and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id == "self":
                    referenced.add(node.attr)
                # loop-over-field-names idiom:
                #   for f in ("lr", ...): getattr(self, f)
                elif isinstance(node, ast.Constant) and \
                        isinstance(node.value, str):
                    referenced.add(node.value)
        for name, lineno in sorted(fields.items()):
            if name not in referenced and name not in exempt:
                yield self.finding(
                    fc.relpath, lineno,
                    f"{self.classname}.{name} is neither validated in "
                    f"__post_init__ nor listed in _POST_INIT_EXEMPT")
        for name in sorted(exempt - set(fields)):
            yield self.finding(
                fc.relpath, 1,
                f"_POST_INIT_EXEMPT entry {name!r} is not a "
                f"{self.classname} field (stale allowlist)")


@register
class JsonHygieneRule(Rule):
    """NaN/Inf serialize to bare ``NaN`` tokens that no strict JSON
    parser reads back; numpy scalars fail outright. Every dump goes
    through ``json_safe`` or sets ``allow_nan=False`` (obs/metrics.py,
    DESIGN.md section 11)."""
    name = "json-hygiene"
    severity = "error"
    description = ("json.dump/json.dumps must pass allow_nan=False or "
                   "wrap the payload in json_safe(...)")

    def check_file(self, fc: FileContext) -> Iterable[Finding]:
        aliases = import_aliases(fc.tree)
        for node in ast.walk(fc.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted_name(node.func, aliases) or ""
            if fname not in ("json.dump", "json.dumps"):
                continue
            ok = any(kw.arg == "allow_nan"
                     and isinstance(kw.value, ast.Constant)
                     and kw.value.value is False
                     for kw in node.keywords)
            if not ok and node.args:
                payload = node.args[0]
                if isinstance(payload, ast.Call):
                    pname = dotted_name(payload.func, aliases) or ""
                    ok = pname.split(".")[-1] == "json_safe"
            if not ok:
                yield self.finding(
                    fc.relpath, node.lineno,
                    f"`{fname}` without allow_nan=False or a "
                    f"json_safe(...) payload")


@register
class DeadLeafRule(Rule):
    """A pytree (NamedTuple) field that is constructed but never read
    is carried through every jit boundary, scan and while_loop for
    nothing — exactly the PR 7 dead-fading-leaf bug class."""
    name = "dead-leaf"
    severity = "error"
    description = ("every NamedTuple pytree field under src/ must be "
                   "read (attribute access) somewhere in the repo")

    def check_repo(self, ctx: RepoContext) -> Iterable[Finding]:
        classes = []   # (fc, classname, {field: lineno})
        for fc in ctx.files:
            if fc.tree is None or not fc.relpath.startswith("src/"):
                continue
            for node in ast.walk(fc.tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                bases = {dotted_name(b, {}) or getattr(b, "id", "")
                         for b in node.bases}
                if not any(b and b.split(".")[-1] == "NamedTuple"
                           for b in bases):
                    continue
                fields = {st.target.id: st.lineno for st in node.body
                          if isinstance(st, ast.AnnAssign)
                          and isinstance(st.target, ast.Name)}
                if fields:
                    classes.append((fc, node.name, fields))
        if not classes:
            return
        read_attrs: Set[str] = set()
        for fc in ctx.files:
            if fc.tree is None:
                continue
            for node in ast.walk(fc.tree):
                if isinstance(node, ast.Attribute) and \
                        isinstance(node.ctx, ast.Load):
                    read_attrs.add(node.attr)
        for fc, classname, fields in classes:
            for name, lineno in sorted(fields.items()):
                if name not in read_attrs:
                    yield self.finding(
                        fc.relpath, lineno,
                        f"pytree leaf {classname}.{name} is never read "
                        f"anywhere in the linted tree (dead leaf)")


@register
class BenchRegistryRule(Rule):
    """Static twin of ``benchmarks/run.py --check-registry``: a new
    benchmark module that is not in ``BENCHES`` never runs under
    ``--smoke`` and silently misses CI."""
    name = "bench-registry"
    severity = "error"
    description = ("every benchmarks/*.py module is registered in "
                   "benchmarks/run.py BENCHES (modulo _NON_BENCH/_ALIASES)")

    def check_repo(self, ctx: RepoContext) -> Iterable[Finding]:
        run = ctx.file("benchmarks/run.py")
        if run is None or run.tree is None:
            return
        benches: Set[str] = set()
        non_bench: Set[str] = set()
        aliases = {}
        for node in ast.walk(run.tree):
            if not isinstance(node, ast.Assign):
                continue
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            try:
                value = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                if "BENCHES" in names and isinstance(node.value, ast.Dict):
                    benches = {k.value for k in node.value.keys
                               if isinstance(k, ast.Constant)}
                continue
            if "_NON_BENCH" in names:
                non_bench = set(value)
            elif "_ALIASES" in names:
                aliases = dict(value)
        modules = {fc.relpath.rsplit("/", 1)[-1][:-3]
                   for fc in ctx.files
                   if fc.relpath.startswith("benchmarks/")
                   and fc.relpath.count("/") == 1} - non_bench
        registered = {aliases.get(n, n) for n in benches}
        for missing in sorted(modules - registered):
            yield self.finding(
                run.relpath, 1,
                f"benchmarks/{missing}.py is not registered in BENCHES "
                f"(and not in _NON_BENCH) — CI --smoke will never run it")
        for stale in sorted(registered - modules):
            yield self.finding(
                run.relpath, 1,
                f"BENCHES entry {stale!r} has no benchmarks/{stale}.py "
                f"module on disk")


_DESIGN_REF_RE = re.compile(r"DESIGN\.md\s+sections?\s+(\d+)(?:\s*[-–]\s*"
                            r"(\d+))?")
_DESIGN_HEADING_RE = re.compile(r"^##\s+(\d+)\.", re.M)


@register
class DesignRefRule(Rule):
    """Docstring/comment references like ``DESIGN.md section 9`` are
    load-bearing documentation; when sections renumber they must all
    move or they point a reader at the wrong contract."""
    name = "design-ref"
    severity = "error"
    description = ("every `DESIGN.md section N` reference resolves to an "
                   "actual `## N.` heading in DESIGN.md")

    def check_repo(self, ctx: RepoContext) -> Iterable[Finding]:
        if ctx.design_md is None:
            return
        headings = {int(m.group(1))
                    for m in _DESIGN_HEADING_RE.finditer(ctx.design_md)}
        for fc in ctx.files:
            for lineno, text in enumerate(fc.lines, start=1):
                for m in _DESIGN_REF_RE.finditer(text):
                    lo = int(m.group(1))
                    hi = int(m.group(2)) if m.group(2) else lo
                    for n in range(lo, hi + 1):
                        if n not in headings:
                            yield self.finding(
                                fc.relpath, lineno,
                                f"reference to DESIGN.md section {n} "
                                f"does not resolve (headings: "
                                f"{sorted(headings)})")


# patterns that must never be tracked, and must be gitignored
_SCRATCH_DIRS = ("experiments/runs/", ".hypothesis/", ".jax_cache/")
_GITIGNORE_REQUIRED = ("__pycache__/", "*.pyc") + _SCRATCH_DIRS


def _is_scratch(path: str) -> bool:
    parts = path.split("/")
    return ("__pycache__" in parts or path.endswith(".pyc")
            or path.startswith(_SCRATCH_DIRS))


@register
class RepoHygieneRule(Rule):
    """Bytecode caches, run-ledger scratch, hypothesis's example database
    and the XLA compilation cache are machine-local; a tracked copy goes
    stale immediately and churns every diff."""
    name = "repo-hygiene"
    severity = "error"
    description = ("no __pycache__/*.pyc/experiments/runs/.hypothesis/"
                   ".jax_cache/ scratch is git-tracked, and .gitignore "
                   "covers those patterns")

    def check_repo(self, ctx: RepoContext) -> Iterable[Finding]:
        if ctx.tracked_files is not None:
            for path in ctx.tracked_files:
                if _is_scratch(path):
                    yield self.finding(
                        ".gitignore", 0,
                        f"scratch file `{path}` is git-tracked — "
                        f"`git rm --cached` it")
        if ctx.gitignore is not None:
            have = {ln.strip() for ln in ctx.gitignore.splitlines()}
            for pat in _GITIGNORE_REQUIRED:
                if pat not in have:
                    yield self.finding(
                        ".gitignore", 0,
                        f".gitignore is missing the `{pat}` pattern")
