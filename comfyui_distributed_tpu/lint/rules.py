"""cdtlint rules: the fleet's invariants as AST checks (docs/lint.md).

=====  =====================================================================
L001   lock-discipline: mutation of a lock-guarded shared-registry attribute
       outside a ``with self._lock`` block (BREAKERS, DRAIN, CacheTier,
       ShapeCatalog, ResidencyPlanner, telemetry registry, ...).
A001   async-hygiene: blocking calls (``time.sleep``, sync file I/O,
       ``subprocess``, ``fcntl``, ``Future.result()``) directly in an
       ``async def`` body without executor offload.
D001   determinism: wall-clock, ``random.*``, ``uuid4``, set-order
       dependence in modules declared bit-identity-critical.
K001   knob-discipline: raw ``os.environ`` reads of ``CDT_*`` outside the
       typed knob registry, plus the two-way code<->docs sync check.
J001   traced-purity: functions passed to ``jax.jit``/``shard_map`` must
       not perform I/O, env reads, or telemetry calls inside the trace.
=====  =====================================================================

Every rule is heuristic, not sound: the escape hatches are a same-line
``# cdtlint: disable=RULE`` comment (with justification) or a baseline
entry (``lint/baseline.json``). See docs/lint.md for the workflow.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from pathlib import Path
from typing import Iterator, Optional

from .core import Finding, ModuleCtx
from . import callgraph as _callgraph
from . import dataflow as _dataflow

CDT_NAME_RE = re.compile(r"CDT_[A-Z0-9_]*[A-Z0-9]$")

PACKAGE = "comfyui_distributed_tpu"


# ---------------------------------------------------------------------------
# shared AST helpers


class Imports:
    """Per-module import table so rules resolve ``sleep(...)`` ->
    ``time.sleep`` and ``sp.run(...)`` -> ``subprocess.run``."""

    def __init__(self, tree: ast.AST):
        self.module_alias: dict[str, str] = {}   # local name -> module
        self.from_name: dict[str, tuple[str, str]] = {}  # local -> (mod, orig)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    top = a.name if a.asname else a.name.split(".")[0]
                    self.module_alias[local] = top
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                for a in node.names:
                    self.from_name[a.asname or a.name] = (mod, a.name)

    def resolve(self, func: ast.AST) -> str:
        """Dotted name of a call target, import-aware. Attribute chains
        rooted in unknown objects keep their literal spelling
        (``self._lock.acquire`` -> ``self._lock.acquire``)."""
        parts: list[str] = []
        node = func
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            base = node.id
            if base in self.from_name:
                mod, orig = self.from_name[base]
                base = f"{mod}.{orig}" if mod else orig
            elif base in self.module_alias:
                base = self.module_alias[base]
            parts.append(base)
        elif isinstance(node, ast.Call):
            parts.append("()")
        else:
            parts.append("?")
        return ".".join(reversed(parts))

    def from_module_of(self, name: str) -> str:
        """Source module of a from-imported local name ('' if not one)."""
        return self.from_name.get(name, ("", ""))[0]


def imports_of(ctx: ModuleCtx) -> Imports:
    imp = getattr(ctx, "_imports", None)
    if imp is None:
        imp = Imports(ctx.tree)
        ctx._imports = imp
    return imp


def iter_functions(tree: ast.AST) -> Iterator[tuple[str, ast.AST]]:
    """Yield (qualname, FunctionDef|AsyncFunctionDef) for every function,
    methods included."""

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                yield q, child
                yield from walk(child, q)
            elif isinstance(child, ast.ClassDef):
                q = f"{prefix}.{child.name}" if prefix else child.name
                yield from walk(child, q)
            else:
                yield from walk(child, prefix)

    yield from walk(tree, "")


def qualname_map(ctx: ModuleCtx) -> dict[int, str]:
    """id(node) -> qualname of the innermost enclosing function."""
    cached = getattr(ctx, "_qualmap", None)
    if cached is not None:
        return cached
    out: dict[int, str] = {}
    for qual, fn in iter_functions(ctx.tree):   # outer first; inner wins
        for sub in ast.walk(fn):
            out[id(sub)] = qual
    ctx._qualmap = out
    return out


def is_self_attr(node: ast.AST) -> Optional[str]:
    """``self.X`` -> ``X``."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def mutated_self_attrs(stmt: ast.AST) -> list[tuple[str, ast.AST]]:
    """Self attributes this single node mutates: assignments to
    ``self.X`` / ``self.X[...]``, ``del``, and mutating method calls
    (``self.X.append(...)``, ``self.X[k].update(...)``)."""
    MUTATORS = {"append", "extend", "add", "remove", "discard", "clear",
                "pop", "popitem", "update", "setdefault", "insert",
                "appendleft", "popleft", "sort", "reverse"}
    out: list[tuple[str, ast.AST]] = []

    def target_attr(t: ast.AST) -> Optional[str]:
        a = is_self_attr(t)
        if a is not None:
            return a
        if isinstance(t, ast.Subscript):
            return target_attr(t.value)
        return None

    def scan_target(t: ast.AST):
        if isinstance(t, (ast.Tuple, ast.List)):
            for el in t.elts:
                scan_target(el)
            return
        a = target_attr(t)
        if a is not None:
            out.append((a, t))

    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            scan_target(t)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        if getattr(stmt, "value", True) is not None:   # AnnAssign decl only
            scan_target(stmt.target)
    elif isinstance(stmt, ast.Delete):
        for t in stmt.targets:
            scan_target(t)
    elif isinstance(stmt, ast.Call):
        f = stmt.func
        if isinstance(f, ast.Attribute) and f.attr in MUTATORS:
            base = f.value
            if isinstance(base, ast.Subscript):
                base = base.value
            a = is_self_attr(base)
            if a is not None:
                out.append((a, stmt))
    return out


# ---------------------------------------------------------------------------
# L001 — lock discipline


class LockDisciplineRule:
    """Classes are auto-discovered: any class that takes ``with self.X``
    on an attribute whose name contains "lock" is lock-disciplined; an
    attribute mutated at least once under the lock is *guarded*; mutating
    a guarded attribute outside the lock (outside ``__init__``/``__new__``
    and helpers named ``*_locked``, which the caller must hold the lock
    for) is a finding."""

    id = "L001"
    title = "lock-guarded registry attribute mutated outside its lock"

    def check_module(self, ctx: ModuleCtx) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(ctx, node)

    def _lock_attrs(self, cls: ast.ClassDef) -> set[str]:
        attrs: set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    a = is_self_attr(item.context_expr)
                    if a is not None and "lock" in a.lower():
                        attrs.add(a)
        return attrs

    def _check_class(self, ctx: ModuleCtx,
                     cls: ast.ClassDef) -> Iterator[Finding]:
        lock_attrs = self._lock_attrs(cls)
        if not lock_attrs:
            return

        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

        def holds_lock(with_node) -> bool:
            return any(is_self_attr(i.context_expr) in lock_attrs
                       for i in with_node.items)

        # pass 1: guarded attrs = mutated at least once under the lock
        guarded: set[str] = set()

        def collect(node, in_lock):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue
                inner = in_lock
                if isinstance(child, (ast.With, ast.AsyncWith)):
                    inner = in_lock or holds_lock(child)
                if in_lock or inner:
                    for attr, _ in mutated_self_attrs(child):
                        if inner:
                            guarded.add(attr)
                collect(child, inner)

        for m in methods:
            collect(m, False)
        guarded -= lock_attrs
        if not guarded:
            return

        # pass 2: mutations of guarded attrs outside the lock
        findings: list[Finding] = []

        def hunt(method, node, in_lock):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue
                inner = in_lock
                if isinstance(child, (ast.With, ast.AsyncWith)):
                    inner = in_lock or holds_lock(child)
                if not inner:
                    for attr, site in mutated_self_attrs(child):
                        if attr in guarded:
                            findings.append(ctx.finding(
                                self.id, site, f"{cls.name}.{method.name}",
                                attr,
                                f"{cls.name}.{method.name} mutates "
                                f"self.{attr} outside `with self."
                                f"{sorted(lock_attrs)[0]}` (guarded: "
                                f"mutated under the lock elsewhere in "
                                f"this class)"))
                hunt(method, child, inner)

        for m in methods:
            if m.name in ("__init__", "__new__") or m.name.endswith("_locked"):
                continue
            hunt(m, m, False)
        yield from findings


# ---------------------------------------------------------------------------
# A001 — async hygiene


class AsyncHygieneRule:
    id = "A001"
    title = "blocking call directly in an async def body"

    # single source of truth shared with the call-graph engine, so A001
    # and A002 can never disagree about what "blocking" means
    BLOCKING_EXACT = _callgraph.BLOCKING_EXACT
    BLOCKING_PREFIX = _callgraph.BLOCKING_PREFIX
    BLOCKING_METHODS = _callgraph.BLOCKING_METHODS

    def check_module(self, ctx: ModuleCtx) -> Iterator[Finding]:
        imp = imports_of(ctx)
        for qual, fn in iter_functions(ctx.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            yield from self._check_async_fn(ctx, imp, qual, fn)

    def _check_async_fn(self, ctx, imp, qual, fn) -> Iterator[Finding]:
        # Executor-offload exemption (ISSUE 20): callables handed to
        # run_in_executor / to_thread / submit run OFF the loop, so
        # blocking calls inside their partial/lambda wrappers (including
        # `run = lambda: ...; run_in_executor(None, run)` aliases) are
        # exempt. Everything else — lambdas included, since a lambda
        # invoked inline or scheduled via call_soon runs ON the loop —
        # is checked. A call nested in a partial's ARGUMENT list
        # (`partial(open(path).read)`) evaluates at wrapper-build time
        # on the loop and stays flagged.
        sanitized = _callgraph.offload_sanitized_ids(fn, imp)

        def walk(node):
            for child in ast.iter_child_nodes(node):
                # nested defs run on their own schedule (and nested async
                # defs are visited separately)
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    continue
                if isinstance(child, ast.Call) \
                        and id(child) not in sanitized:
                    yield from check_call(child)
                yield from walk(child)

        def check_call(call) -> Iterator[Finding]:
            name = imp.resolve(call.func)
            if name in self.BLOCKING_EXACT:
                yield ctx.finding(self.id, call, qual, name.split(".")[-1],
                                  f"{self.BLOCKING_EXACT[name]} "
                                  f"(async def {fn.name})")
                return
            for prefix, why in self.BLOCKING_PREFIX.items():
                if name.startswith(prefix):
                    yield ctx.finding(self.id, call, qual, name,
                                      f"{why} (async def {fn.name})")
                    return
            if isinstance(call.func, ast.Attribute):
                attr = call.func.attr
                if attr == "result" and not call.args and not call.keywords:
                    yield ctx.finding(
                        self.id, call, qual, "result",
                        f"blocking .result() in async def {fn.name} — "
                        "await the future (or wrap_future) instead")
                elif attr in self.BLOCKING_METHODS:
                    yield ctx.finding(
                        self.id, call, qual, attr,
                        f"{self.BLOCKING_METHODS[attr]} (.{attr}()) in "
                        f"async def {fn.name} — offload to an executor")

        yield from walk(fn)


# ---------------------------------------------------------------------------
# D001 — determinism in bit-identity-critical modules


class DeterminismRule:
    """Scope: the modules whose outputs feed the bit-identity guarantee
    (cache keys, microbatch demux, steal scheduling, the pipelines), as a
    path list plus a per-module ``__bit_identity_critical__ = True``
    opt-in dunder."""

    id = "D001"
    title = "nondeterminism in a bit-identity-critical module"

    MODULES = (
        f"{PACKAGE}/cluster/cache/keys.py",
        f"{PACKAGE}/cluster/frontdoor/microbatch.py",
        f"{PACKAGE}/cluster/elastic/scheduler.py",
        f"{PACKAGE}/diffusion/pipeline*.py",
    )

    # shared with the taint engine (lint/dataflow.py) so D001's direct
    # checks and D002's interprocedural taint use identical source tables
    BANNED_EXACT = _dataflow.NONDET_EXACT
    BANNED_PREFIX = _dataflow.NONDET_PREFIX

    def in_scope(self, ctx: ModuleCtx) -> bool:
        if any(fnmatch.fnmatch(ctx.rel, pat) for pat in self.MODULES):
            return True
        for node in ctx.tree.body:
            if (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name)
                            and t.id == "__bit_identity_critical__"
                            for t in node.targets)
                    and isinstance(node.value, ast.Constant)
                    and node.value.value is True):
                return True
        return False

    def check_module(self, ctx: ModuleCtx) -> Iterator[Finding]:
        if not self.in_scope(ctx):
            return
        imp = imports_of(ctx)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                name = imp.resolve(node.func)
                why = self.BANNED_EXACT.get(name)
                if why is None:
                    for prefix, w in self.BANNED_PREFIX.items():
                        if name.startswith(prefix):
                            why = w
                            break
                if why is not None:
                    yield ctx.finding(
                        self.id, node, "<module>", name,
                        f"{name}: {why} in a bit-identity-critical module")
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                it = node.iter
                if (isinstance(it, (ast.Set, ast.SetComp))
                        or (isinstance(it, ast.Call)
                            and imp.resolve(it.func) in ("set",
                                                         "frozenset"))):
                    yield ctx.finding(
                        self.id, node, "<module>", "set-iteration",
                        "iterating a set: order is not deterministic in a "
                        "bit-identity-critical module — sort it first")


# ---------------------------------------------------------------------------
# K001 — knob discipline (raw env reads + two-way doc sync)


class KnobDisciplineRule:
    id = "K001"
    title = "CDT_* knob read outside the typed registry / doc drift"

    REGISTRY_MODULE = f"{PACKAGE}/utils/constants.py"

    def check_module(self, ctx: ModuleCtx) -> Iterator[Finding]:
        if ctx.rel == self.REGISTRY_MODULE:
            return
        imp = imports_of(ctx)
        for qual, key_node, node in self._env_reads(ctx, imp):
            key = self._literal_key(ctx, key_node)
            if key is not None and key.startswith("CDT_"):
                yield ctx.finding(
                    self.id, node, qual, key,
                    f"raw env read of {key} — declare it in "
                    "utils/constants.py and read via the knob registry "
                    "(constants.<KNOB>.get())")
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                name = imp.resolve(node.func)
                if name.split(".")[-1] in ("env_int", "env_float") \
                        and "constants" in name:
                    key = self._literal_key(
                        ctx, node.args[0] if node.args else None)
                    if key and key.startswith("CDT_"):
                        yield ctx.finding(
                            self.id, node, "<module>", key,
                            f"legacy env_{'int' if 'int' in name else 'float'}"
                            f" read of {key} — declare a Knob in "
                            "utils/constants.py instead")

    def _env_reads(self, ctx, imp):
        """(qualname, key-node, call/subscript-node) for os.environ.get /
        os.getenv / os.environ[...] loads — one yield per site."""
        quals = qualname_map(ctx)
        for sub in ast.walk(ctx.tree):
            if isinstance(sub, ast.Call):
                name = imp.resolve(sub.func)
                if name in ("os.environ.get", "os.getenv"):
                    yield (quals.get(id(sub), "<module>"),
                           sub.args[0] if sub.args else None, sub)
            elif (isinstance(sub, ast.Subscript)
                  and isinstance(sub.ctx, ast.Load)
                  and imp.resolve(sub.value) == "os.environ"):
                yield quals.get(id(sub), "<module>"), sub.slice, sub

    def _literal_key(self, ctx, node) -> Optional[str]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.Name):
            return ctx.str_consts.get(node.id)
        return None

    # -- project-level two-way sync ------------------------------------

    def finalize(self, ctxs, repo_root: Path) -> list[Finding]:
        """Full-package checks (skipped when the registry module is not
        part of the lint run, e.g. fixture-snippet tests): every CDT_*
        literal in code must be a declared knob, and docs/knobs.md must
        be regeneration-clean against the registry."""
        if not any(c.rel == self.REGISTRY_MODULE for c in ctxs):
            return []
        try:
            from ..utils.constants import KNOBS
        except Exception as exc:                      # pragma: no cover
            return [Finding(self.id, self.REGISTRY_MODULE, 1,
                            f"cannot import the knob registry: {exc}",
                            f"{self.id}:{self.REGISTRY_MODULE}:registry")]
        declared = set(KNOBS.names())
        findings: list[Finding] = []
        for ctx in ctxs:
            for name, node in self._cdt_literals(ctx):
                if name not in declared and not ctx.suppressed(
                        node.lineno, self.id):
                    findings.append(ctx.finding(
                        self.id, node, "<module>", name,
                        f"{name} referenced in code but not declared in "
                        "the knob registry (utils/constants.py) — "
                        "undeclared knobs can't reach docs/knobs.md"))
        findings.extend(self._check_docs(repo_root, declared))
        return findings

    def _cdt_literals(self, ctx):
        docstrings = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                body = node.body
                if (body and isinstance(body[0], ast.Expr)
                        and isinstance(body[0].value, ast.Constant)
                        and isinstance(body[0].value.value, str)):
                    docstrings.add(id(body[0].value))
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and id(node) not in docstrings
                    and CDT_NAME_RE.fullmatch(node.value)):
                yield node.value, node

    def _check_docs(self, repo_root: Path, declared) -> list[Finding]:
        from .knobdocs import render_markdown

        rel = "docs/knobs.md"
        path = repo_root / rel
        want = render_markdown()
        have = path.read_text(encoding="utf-8") if path.is_file() else ""
        if have != want:
            verb = "missing" if not have else "stale"
            return [Finding(
                self.id, rel, 1,
                f"docs/knobs.md is {verb} — the knob docs are GENERATED "
                "from the registry; run `python -m "
                f"{PACKAGE}.lint --write-knob-docs`",
                f"{self.id}:{rel}:regen")]
        return []


# ---------------------------------------------------------------------------
# J001 — traced purity


class TracedPurityRule:
    """Functions handed to ``jax.jit``/``shard_map`` (decorator or call
    form) are traced: anything they do besides math is either silently
    baked into the compiled program (env reads, flags) or runs only at
    trace time (I/O, telemetry) — both are bugs. Resolution is
    module-local and shallow: helpers the traced function calls are not
    followed (docs/lint.md#limits)."""

    id = "J001"
    title = "impure call inside a jit/shard_map-traced function"

    # matched on the LAST dotted component so every spelling works:
    # jax.jit, jit, jax.shard_map, jax.experimental...shard_map
    TRACE_ENTRY_TAILS = ("jit", "pjit", "shard_map")

    IMPURE_EXACT = {
        "open": "file I/O", "print": "stdout I/O (use jax.debug.print)",
        "os.getenv": "env read (baked into the trace)",
        "os.environ.get": "env read (baked into the trace)",
        "time.time": "clock read (runs at trace time only)",
        "time.monotonic": "clock read (runs at trace time only)",
        "time.perf_counter": "clock read (runs at trace time only)",
    }
    IMPURE_PREFIX = {
        "random.": "python-level randomness (runs at trace time only — "
                   "use jax.random with a threaded key)",
        "logging.": "logging inside a trace runs at trace time only",
    }

    def check_module(self, ctx: ModuleCtx) -> Iterator[Finding]:
        imp = imports_of(ctx)
        defs: dict[str, ast.AST] = {name.split(".")[-1]: fn
                                    for name, fn in iter_functions(ctx.tree)}
        seen: set[int] = set()
        for target, how in self._traced_functions(ctx, imp, defs):
            if id(target) in seen:
                continue
            seen.add(id(target))
            yield from self._check_traced(ctx, imp, target, how)

    def _traced_functions(self, ctx, imp, defs):
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self._is_trace_entry(imp, dec):
                        yield node, f"@{imp.resolve(dec if not isinstance(dec, ast.Call) else dec.func)}"
            elif isinstance(node, ast.Call):
                name = imp.resolve(node.func)
                if name.split(".")[-1] in self.TRACE_ENTRY_TAILS \
                        and node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Lambda):
                        yield arg, name
                    elif isinstance(arg, ast.Name) and arg.id in defs:
                        yield defs[arg.id], name
                # functools.partial(jax.jit, f) is rare; skipped.

    def _is_trace_entry(self, imp, dec) -> bool:
        if isinstance(dec, ast.Call):
            dec = dec.func
        return imp.resolve(dec).split(".")[-1] in self.TRACE_ENTRY_TAILS

    def _check_traced(self, ctx, imp, fn, how) -> Iterator[Finding]:
        qual = getattr(fn, "name", "<lambda>")
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = imp.resolve(node.func)
            why = self.IMPURE_EXACT.get(name)
            if why is None:
                for prefix, w in self.IMPURE_PREFIX.items():
                    if name.startswith(prefix):
                        why = w
                        break
            if why is None and "telemetry" in name:
                why = "telemetry call (runs at trace time only — " \
                      "record outside the traced function)"
            if why is not None:
                yield ctx.finding(
                    self.id, node, qual, name,
                    f"{name} inside {how}-traced `{qual}`: {why}")


from .flowrules import FLOW_RULES  # noqa: E402

ALL_RULES = (LockDisciplineRule(), AsyncHygieneRule(), DeterminismRule(),
             KnobDisciplineRule(), TracedPurityRule()) + FLOW_RULES


def rule_by_id(rule_id: str):
    for r in ALL_RULES:
        if r.id == rule_id:
            return r
    raise KeyError(rule_id)
