"""AST core of the trial preflight analyzer.

Design: one walk per module with a scope-tracking visitor; rules are
stateless-ish objects dispatched per node (``rules/__init__.py``).  The
walker computes the two pieces of context every rule needs:

- **step context** — whether the current code is traced by XLA.  A
  ``JaxTrial`` subclass's ``loss``/``evaluate_batch``/``init_params``
  methods are traced (the Trainer jits them), as is any function carrying a
  ``jax.jit``-style decorator or named ``train_step``/``eval_step`` (the
  Trainer's own convention); nested functions inherit the property.
- **traced names** — which local names hold traced values inside a step
  function: its parameters (minus ``self``/``model`` and those its jit
  decorator names in ``static_argnames``/``static_argnums``) seeded, then a cheap
  two-pass forward taint (``x = f(batch)`` makes ``x`` traced).  Attribute
  reads of static metadata (``.shape``/``.dtype``/``.ndim``) break the
  taint, so shape-based Python branching stays legal.

Trial classes are detected structurally — a base name whose last segment
ends in ``Trial`` — so the analyzer works on source that cannot be
imported; ``analyze_class`` (an imported class object) force-marks the
class instead.

Suppressions: ``# dtpu: lint-ok[rule-a,rule-b]`` (or bare ``lint-ok`` for
all rules) on the finding's line, or alone on the line above it.
"""

from __future__ import annotations

import ast
import io
import os
import re
import textwrap
import tokenize
from typing import Any, Dict, List, Optional, Sequence, Set

from determined_tpu.lint._diag import ERROR, WARNING, Diagnostic

#: JaxTrial methods the Trainer traces under jit
STEP_METHODS = frozenset({"loss", "evaluate_batch", "init_params"})
#: function names treated as traced step bodies anywhere (Trainer idiom)
STEP_FUNCTION_NAMES = frozenset({"train_step", "eval_step"})
#: parameters of step methods that are NOT traced values
UNTRACED_PARAMS = frozenset({"self", "cls", "model"})
#: attribute reads that yield static (host) metadata of a traced array
STATIC_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "sharding", "aval"})

_SUPPRESS_RE = re.compile(r"#\s*dtpu:\s*lint-ok(?:\[([^\]]*)\])?")


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(node: ast.Call) -> Optional[str]:
    return dotted_name(node.func)


def parse_suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
    """line -> suppressed rule ids (None = all rules).

    A comment alone on its line also covers the next line, so findings can
    be suppressed above the statement they refer to.
    """
    out: Dict[int, Optional[Set[str]]] = {}
    lines = source.splitlines()
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            rules = (
                {r.strip() for r in m.group(1).split(",") if r.strip()}
                if m.group(1) is not None
                else None
            )
            line = tok.start[0]
            targets = [line]
            text_before = lines[line - 1][: tok.start[1]] if line <= len(lines) else ""
            if not text_before.strip():
                targets.append(line + 1)
            for t in targets:
                prev = out.get(t, set())
                if prev is None or rules is None:
                    out[t] = None
                else:
                    out[t] = prev | rules
    except tokenize.TokenError:
        pass
    return out


class FunctionScope:
    """One function on the walker's stack."""

    def __init__(self, node: ast.AST, is_step: bool, traced: Set[str]) -> None:
        self.node = node
        self.name = getattr(node, "name", "<lambda>")
        self.is_step = is_step
        self.traced = traced


class ClassScope:
    def __init__(self, node: ast.ClassDef, is_trial: bool) -> None:
        self.node = node
        self.name = node.name
        self.is_trial = is_trial


class LintContext:
    """What rules see: scope stacks, taint info, and the report sink."""

    def __init__(
        self,
        filename: str,
        source: str,
        *,
        line_offset: int = 0,
        assume_trial_classes: Optional[Set[str]] = None,
    ) -> None:
        self.filename = filename
        self.source = source
        self.line_offset = line_offset
        self.assume_trial_classes = assume_trial_classes or set()
        self.suppressions = parse_suppressions(source)
        self.diagnostics: List[Diagnostic] = []
        self.class_stack: List[ClassScope] = []
        self.func_stack: List[FunctionScope] = []
        #: ids of Call nodes that are bare expression statements (their
        #: value is discarded — the call exists for its side effect)
        self.stmt_calls: Set[int] = set()

    # -- scope queries -----------------------------------------------------

    @property
    def in_step(self) -> bool:
        return any(f.is_step for f in self.func_stack)

    @property
    def current_class(self) -> Optional[ClassScope]:
        return self.class_stack[-1] if self.class_stack else None

    @property
    def in_trial_class(self) -> bool:
        return any(c.is_trial for c in self.class_stack)

    def traced_names(self) -> Set[str]:
        """Union of traced names over the enclosing step functions (a
        nested helper inside ``loss`` sees the outer taint too)."""
        out: Set[str] = set()
        for f in self.func_stack:
            if f.is_step:
                out |= f.traced
        return out

    # -- reporting ---------------------------------------------------------

    def report(
        self,
        rule: Any,
        node: ast.AST,
        message: str,
        *,
        severity: Optional[str] = None,
    ) -> None:
        line = getattr(node, "lineno", 1)
        sup = self.suppressions.get(line)
        if sup is None and line in self.suppressions:
            return  # bare lint-ok: everything suppressed
        if sup is not None and rule.id in sup:
            return
        self.diagnostics.append(
            Diagnostic(
                rule=rule.id,
                severity=severity or rule.severity,
                message=message,
                file=self.filename,
                line=line + self.line_offset,
                col=getattr(node, "col_offset", 0),
            )
        )


def references_traced_value(node: ast.AST, traced: Set[str]) -> bool:
    """Does this expression's VALUE depend on a traced array (as opposed to
    static metadata like ``.shape``)?"""
    if isinstance(node, ast.Attribute):
        if node.attr in STATIC_ATTRS:
            return False
        return references_traced_value(node.value, traced)
    if isinstance(node, ast.Name):
        return node.id in traced
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "len":
            return False  # len() of an array is its leading shape dim
        if isinstance(fn, ast.Attribute) and fn.attr in ("items", "keys", "values"):
            # structure iteration over a pytree container is static
            return False
        return any(
            references_traced_value(c, traced) for c in ast.iter_child_nodes(node)
        )
    return any(references_traced_value(c, traced) for c in ast.iter_child_nodes(node))


def local_names(fn_node: ast.AST) -> Set[str]:
    """Names bound in this function: params, plain Name stores, nested
    defs — EXCLUDING names declared ``global``/``nonlocal`` (stores to
    those rebind an OUTER scope, so they are shared, not local).  Shared
    by the side-effect and concurrency rules; nested functions' bindings
    count toward the enclosing function (a deliberate coarse-grain)."""
    declared_outer: Set[str] = set()
    for sub in ast.walk(fn_node):
        if isinstance(sub, (ast.Global, ast.Nonlocal)):
            declared_outer.update(sub.names)
    out: Set[str] = set()
    args = getattr(fn_node, "args", None)
    if args is not None:
        for a in list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs):
            out.add(a.arg)
        for extra in (args.vararg, args.kwarg):
            if extra is not None:
                out.add(extra.arg)
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            out.add(sub.id)
        elif (
            isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
            and sub is not fn_node
        ):
            out.add(sub.name)
    return out - declared_outer


def _assigned_names(target: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(target):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
            out.add(n.id)
    return out


def _taint_function(node: ast.AST, seed: Set[str]) -> Set[str]:
    """Two forward passes of name-level taint over the function body."""
    traced = set(seed)
    body = getattr(node, "body", [])
    for _ in range(2):
        for stmt in body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Assign) and references_traced_value(
                    sub.value, traced
                ):
                    for t in sub.targets:
                        traced |= _assigned_names(t)
                elif isinstance(sub, ast.AugAssign) and references_traced_value(
                    sub.value, traced
                ):
                    traced |= _assigned_names(sub.target)
    return traced


def _jit_decorators(node: ast.AST) -> List[ast.AST]:
    """The decorators of ``node`` that jit it: ``@jax.jit``, ``@jit(...)``,
    ``@functools.partial(jax.jit, ...)``."""
    found: List[ast.AST] = []
    for dec in getattr(node, "decorator_list", []):
        names: List[Optional[str]] = [dotted_name(dec)]
        if isinstance(dec, ast.Call):
            names.append(dotted_name(dec.func))
            names.extend(dotted_name(a) for a in dec.args)
        if any(name and (name == "jit" or name.endswith(".jit")) for name in names):
            found.append(dec)
    return found


def _jit_static_params(node: ast.AST) -> Set[str]:
    """Parameters a jit decorator of ``node`` declares static, by name
    (``static_argnames``) or by position (``static_argnums``): Python values
    the trace is made FOR, not traced arrays, so branching on them is how a
    jitted function picks its form."""
    args = getattr(node, "args", None)
    positional = [] if args is None else [a.arg for a in list(args.posonlyargs) + list(args.args)]
    static: Set[str] = set()
    for dec in _jit_decorators(node):
        for kw in getattr(dec, "keywords", []):
            if kw.arg not in ("static_argnames", "static_argnums"):
                continue
            try:
                value = ast.literal_eval(kw.value)
            except ValueError:
                continue  # computed: nothing to read
            for item in value if isinstance(value, (tuple, list)) else (value,):
                if kw.arg == "static_argnames" and isinstance(item, str):
                    static.add(item)
                elif kw.arg == "static_argnums" and isinstance(item, int) and -len(positional) <= item < len(positional):
                    static.add(positional[item])
    return static


def _is_trial_classdef(node: ast.ClassDef, assume: Set[str]) -> bool:
    if node.name in assume:
        return True
    for base in node.bases:
        name = dotted_name(base)
        if name and name.split(".")[-1].endswith("Trial"):
            return True
    return False


class _Walker(ast.NodeVisitor):
    def __init__(self, ctx: LintContext, rules: Sequence[Any]) -> None:
        self.ctx = ctx
        self.rules = rules

    def _dispatch(self, hook: str, node: ast.AST) -> None:
        for rule in self.rules:
            fn = getattr(rule, hook, None)
            if fn is not None:
                fn(node, self.ctx)

    # -- scopes ------------------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        scope = ClassScope(
            node, _is_trial_classdef(node, self.ctx.assume_trial_classes)
        )
        self.ctx.class_stack.append(scope)
        self._dispatch("visit_classdef", node)
        self.generic_visit(node)
        self.ctx.class_stack.pop()

    def _visit_function(self, node: ast.AST) -> None:
        ctx = self.ctx
        name = getattr(node, "name", "<lambda>")
        in_trial_method = (
            ctx.current_class is not None
            and ctx.current_class.is_trial
            and not ctx.func_stack
        )
        is_step = (
            (in_trial_method and name in STEP_METHODS)
            or name in STEP_FUNCTION_NAMES
            or bool(_jit_decorators(node))
            or ctx.in_step  # nested in a step function
        )
        traced: Set[str] = set()
        if is_step:
            args = getattr(node, "args", None)
            if args is not None:
                params = [
                    a.arg
                    for a in (
                        list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
                    )
                ]
                for extra in (args.vararg, args.kwarg):
                    if extra is not None:
                        params.append(extra.arg)
                traced = {p for p in params if p not in UNTRACED_PARAMS} - _jit_static_params(node)
            traced |= ctx.traced_names()
            traced = _taint_function(node, traced)
        ctx.func_stack.append(FunctionScope(node, is_step, traced))
        self._dispatch("visit_functiondef", node)
        self.generic_visit(node)
        ctx.func_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_function(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_function(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        # lambdas inherit step context but add no scope bookkeeping
        self.generic_visit(node)

    # -- dispatched nodes ----------------------------------------------------

    def visit_Expr(self, node: ast.Expr) -> None:
        if isinstance(node.value, ast.Call):
            self.ctx.stmt_calls.add(id(node.value))
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        self._dispatch("visit_call", node)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        self._dispatch("visit_assign", node)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._dispatch("visit_augassign", node)
        self.generic_visit(node)

    def visit_If(self, node: ast.If) -> None:
        self._dispatch("visit_if", node)
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._dispatch("visit_while", node)
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        self._dispatch("visit_for", node)
        self.generic_visit(node)

    def visit_Global(self, node: ast.Global) -> None:
        self._dispatch("visit_global", node)
        self.generic_visit(node)


def analyze_source(
    source: str,
    filename: str = "<string>",
    *,
    rules: Optional[Sequence[str]] = None,
    disabled: Optional[Sequence[str]] = None,
    line_offset: int = 0,
    assume_trial_classes: Optional[Set[str]] = None,
    _program: bool = True,
) -> List[Diagnostic]:
    """Analyze one module's source; returns sorted diagnostics.

    Program-level rules (the concurrency pass) run over this one module
    too, so a self-contained fixture shows its lock cycle without a
    directory; ``analyze_path``/``analyze_paths`` pass ``_program=False``
    per file and run ONE cross-module pass over the whole target instead.
    """
    from determined_tpu.lint.rules import build_rules

    ctx = LintContext(
        filename,
        source,
        line_offset=line_offset,
        assume_trial_classes=assume_trial_classes,
    )
    try:
        tree = ast.parse(source, filename=filename)
    except SyntaxError as e:
        return [
            Diagnostic(
                rule="parse-error",
                severity=ERROR,
                message=f"cannot parse: {e.msg}",
                file=filename,
                line=(e.lineno or 1) + line_offset,
                col=e.offset or 0,
            )
        ]
    rule_objs = build_rules(only=rules, disabled=disabled)
    walker_rules = [r for r in rule_objs if not r.program_level]
    program_rules = [r for r in rule_objs if r.program_level]
    for rule in walker_rules:
        rule.before_module(tree, ctx)
    _Walker(ctx, walker_rules).visit(tree)
    diags = list(ctx.diagnostics)
    if _program and program_rules:
        from determined_tpu.lint._concurrency import analyze_program_sources

        diags.extend(
            analyze_program_sources(
                {filename: source},
                program_rules,
                line_offsets={filename: line_offset},
            )
        )
    return sorted(diags, key=lambda d: (d.file, d.line, d.col, d.rule))


def analyze_file(path: str, **kwargs: Any) -> List[Diagnostic]:
    with open(path, encoding="utf-8") as f:
        return analyze_source(f.read(), filename=path, **kwargs)


def analyze_paths(
    paths: Sequence[str],
    *,
    rules: Optional[Sequence[str]] = None,
    disabled: Optional[Sequence[str]] = None,
    exclude: Optional[Sequence[str]] = None,
    **file_kwargs: Any,
) -> List[Diagnostic]:
    """Lint several files/directories as ONE program.

    Per-module rules run file by file; the program-level concurrency pass
    runs once over the union, so a lock bound in one target and acquired
    under another target's lock still forms a graph edge (``scripts/``
    imports ``determined_tpu`` — its lock use belongs in the
    package's graph, which is why ``scripts/lint.sh`` passes every target
    in a single invocation).

    Extra keyword args (``assume_trial_classes`` etc.) are forwarded to
    the per-module ``analyze_source`` pass for every file, keeping
    ``analyze_path``'s directory mode on its historical contract.

    ``exclude``: fnmatch globs (matched against basenames and
    target-relative paths, pruning whole directories) — dir-mode over a
    live experiment checkout must skip journal/checkpoint/trace artifacts
    and shipped context code (``dtpu lint . --exclude 'checkpoints/*'``).
    """
    from determined_tpu.lint._concurrency import (
        analyze_program_sources,
        collect_py_files,
    )
    from determined_tpu.lint.rules import build_rules

    rule_objs = build_rules(only=rules, disabled=disabled)
    program_rules = [r.id for r in rule_objs if r.program_level]
    files: List[str] = []
    seen_real: Set[str] = set()
    for path in paths:
        for f in collect_py_files(path, exclude=tuple(exclude or ())):
            # overlapping targets can spell one physical file two ways
            # (`dtpu lint pkg ./pkg/mod.py`); linting it twice doubles
            # every finding and forks its module identity in the index
            key = os.path.realpath(f)
            if key not in seen_real:
                seen_real.add(key)
                files.append(f)
    out: List[Diagnostic] = []
    sources: Dict[str, str] = {}
    for f in files:
        with open(f, encoding="utf-8") as fh:
            sources[f] = fh.read()
        out.extend(
            analyze_source(
                sources[f], filename=f, rules=rules, disabled=disabled,
                _program=False, **file_kwargs,
            )
        )
    if program_rules:
        program_objs = [r for r in rule_objs if r.program_level]
        out.extend(analyze_program_sources(sources, program_objs))
    return sorted(out, key=lambda d: (d.file, d.line, d.col, d.rule))


def analyze_path(path: str, **kwargs: Any) -> List[Diagnostic]:
    """Lint a .py file or recursively every .py file under a directory
    (one whole-program concurrency pass across the directory).  Accepts
    ``exclude=`` globs in directory mode (see ``analyze_paths``)."""
    if os.path.isfile(path):
        kwargs.pop("exclude", None)  # a named file is always linted
        return analyze_file(path, **kwargs)
    return analyze_paths([path], **kwargs)


def analyze_class(trial_cls: type, **kwargs: Any) -> List[Diagnostic]:
    """Lint an imported JaxTrial subclass via ``inspect.getsource``.

    Diagnostics carry real ``file:line`` anchors (the class's source file
    and absolute line numbers).  Raises ``OSError`` when source is
    unavailable (REPL-defined classes) — callers decide whether that is
    fatal (CLI) or skippable (preflight warn mode).
    """
    import inspect

    src_lines, start = inspect.getsourcelines(trial_cls)
    filename = inspect.getsourcefile(trial_cls) or f"<{trial_cls.__qualname__}>"
    source = textwrap.dedent("".join(src_lines))
    return analyze_source(
        source,
        filename=filename,
        line_offset=start - 1,
        assume_trial_classes={trial_cls.__name__},
        **kwargs,
    )


def analyze_entrypoint(spec: str, **kwargs: Any) -> List[Diagnostic]:
    """Lint a ``pkg.module:ClassName`` entrypoint (imports the module)."""
    import importlib

    module_name, _, class_name = spec.partition(":")
    module = importlib.import_module(module_name)
    if not class_name:
        path = getattr(module, "__file__", None)
        if path is None:
            raise ValueError(f"module {module_name} has no source file")
        return analyze_file(path, **kwargs)
    return analyze_class(getattr(module, class_name), **kwargs)
