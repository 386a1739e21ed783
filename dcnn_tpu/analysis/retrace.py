"""Retrace/recompile detection (TS06).

An XLA compile is a wall of seconds to minutes; a *silent retrace*
re-pays its trace and lowering at runtime with no error — the jit cache
just misses. The misses this check can see
statically:

- **jit-of-lambda** — ``jax.jit(lambda ...)``: every evaluation creates
  a fresh callable, so the jit cache (keyed on function identity) can
  never hit across calls.
- **jit-per-call** — ``jax.jit(f)(x)``: the wrapper is rebuilt per
  invocation; hoist the ``jax.jit`` to module/init scope and call the
  stored wrapper.
- **jit-in-loop** — a ``jax.jit``/``pjit``/``precision_keyed_jit`` call
  lexically inside a ``for``/``while`` body: one fresh wrapper (and, for
  nested/lambda targets, one fresh trace) per iteration.
- **static-arg churn** — a call site of a known-jitted binding passing a
  *computed* expression (a call, arithmetic, subscript, f-string — not a
  constant and not a plain name, which may be a bounded flag) in a
  position named by ``static_argnums``/``static_argnames``: every
  distinct runtime value compiles a new executable.
- **shape-varying arg** — a call site of a known-jitted binding passing
  a subscript with a non-constant slice bound (``x[:n]``, ``x[i:j]``) in
  a traced position: each distinct length is a new avals signature →
  recompile. Pad to a bucket (the serve path) or mark the bound static.

Bindings are resolved within one module: ``name = jax.jit(f, ...)`` /
``self.attr = jax.jit(f, ...)`` (and through ``functools.partial``
decorators), then call sites of that name/attr in the same module (same
class for ``self.`` attrs). Cross-module bindings and dynamically
selected callables are out of scope — documented in
docs/static_analysis.md.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import call_name
from .core import Finding, SourceModule, register

JIT_TAILS = {"jit", "pjit", "precision_keyed_jit"}


def _is_jit_call(node: ast.Call) -> bool:
    return call_name(node.func) in JIT_TAILS


def _static_spec(node: ast.Call) -> Tuple[Set[int], Set[str]]:
    """(static positions, static names) declared on a jit call."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in node.keywords:
        if kw.arg == "static_argnums":
            vals = kw.value.elts if isinstance(
                kw.value, (ast.Tuple, ast.List)) else [kw.value]
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    nums.add(v.value)
        elif kw.arg == "static_argnames":
            vals = kw.value.elts if isinstance(
                kw.value, (ast.Tuple, ast.List)) else [kw.value]
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    names.add(v.value)
    return nums, names


def _computed(expr: ast.AST) -> bool:
    """True for expressions whose value plausibly varies per call:
    calls, arithmetic, subscripts, f-strings. Constants and bare names
    (bounded flags, loop-invariant locals) are not flagged."""
    return isinstance(expr, (ast.Call, ast.BinOp, ast.Subscript,
                             ast.JoinedStr))


def _varying_slice(expr: ast.AST) -> bool:
    """``x[:n]`` / ``x[i:j]`` with a non-constant bound."""
    if not (isinstance(expr, ast.Subscript)
            and isinstance(expr.slice, ast.Slice)):
        return False
    for bound in (expr.slice.lower, expr.slice.upper):
        if bound is not None and not isinstance(bound, ast.Constant):
            return True
    return False


def _in_loop(mod: SourceModule, node: ast.AST) -> bool:
    for anc in mod.ancestors(node):
        if isinstance(anc, (ast.For, ast.While)):
            return True
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return False
    return False


@register("TS06", "retrace",
          "jit usage that recompiles per call: fresh wrappers, "
          "static-arg churn, shape-varying call sites")
def check_retrace(project: Dict[str, SourceModule]) -> List[Finding]:
    out: List[Finding] = []
    for path, mod in project.items():
        # binding name -> (static nums, static names); "self.attr" keys
        # are scoped per class via "Class.attr"
        bindings: Dict[str, Tuple[Set[int], Set[str]]] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and _is_jit_call(node):
                fn = mod.enclosing_function(node)
                qn = mod.qualname(fn if fn is not None else mod.tree)
                if node.args and isinstance(node.args[0], ast.Lambda):
                    out.append(Finding(
                        "TS06", path, node.lineno, qn, "lambda",
                        "jax.jit over a lambda: a fresh callable per "
                        "evaluation can never hit the jit cache across "
                        "calls — name the function and jit it once"))
                parent = mod.parents.get(node)
                if isinstance(parent, ast.Call) and parent.func is node:
                    out.append(Finding(
                        "TS06", path, node.lineno, qn, "jit-per-call",
                        "jax.jit(f)(...) rebuilds the jit wrapper per "
                        "invocation; hoist the jit to init scope and "
                        "call the stored wrapper"))
                elif _in_loop(mod, node):
                    out.append(Finding(
                        "TS06", path, node.lineno, qn, "jit-in-loop",
                        "jit wrapper constructed inside a loop body — "
                        "one wrapper (and potentially one trace) per "
                        "iteration; hoist it out of the loop"))
                # record the binding for call-site checks
                if isinstance(parent, ast.Assign):
                    nums, names = _static_spec(node)
                    for t in parent.targets:
                        if isinstance(t, ast.Name):
                            bindings[t.id] = (nums, names)
                        elif (isinstance(t, ast.Attribute)
                              and isinstance(t.value, ast.Name)
                              and t.value.id == "self"):
                            cls = mod.enclosing_class(node)
                            if cls is not None:
                                bindings[f"{cls.name}.{t.attr}"] = (nums,
                                                                    names)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # @partial(jax.jit, static_argnames=...) decorated defs
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) \
                            and call_name(dec.func) == "partial" \
                            and dec.args \
                            and call_name(dec.args[0]) in JIT_TAILS:
                        bindings[node.name] = _static_spec(dec)
                    elif isinstance(dec, ast.Call) and _is_jit_call(dec):
                        bindings[node.name] = _static_spec(dec)

        # call sites of the recorded bindings
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            key: Optional[str] = None
            if isinstance(f, ast.Name) and f.id in bindings:
                key = f.id
            elif (isinstance(f, ast.Attribute)
                  and isinstance(f.value, ast.Name)
                  and f.value.id == "self"):
                cls = mod.enclosing_class(node)
                if cls is not None and f"{cls.name}.{f.attr}" in bindings:
                    key = f"{cls.name}.{f.attr}"
            if key is None:
                continue
            nums, names = bindings[key]
            fn = mod.enclosing_function(node)
            qn = mod.qualname(fn if fn is not None else mod.tree)
            for i, a in enumerate(node.args):
                if i in nums:
                    if _computed(a):
                        out.append(Finding(
                            "TS06", path, node.lineno, qn,
                            f"{key}:static#{i}",
                            f"computed expression in static position "
                            f"{i} of jitted '{key}' — every distinct "
                            f"value compiles a new executable"))
                elif _varying_slice(a):
                    out.append(Finding(
                        "TS06", path, node.lineno, qn,
                        f"{key}:shape#{i}",
                        f"shape-varying slice passed to jitted '{key}' "
                        f"(arg {i}) — each distinct length retraces; "
                        f"pad to a bucket or mark the bound static"))
            for kw in node.keywords:
                if kw.arg in names and _computed(kw.value):
                    out.append(Finding(
                        "TS06", path, node.lineno, qn,
                        f"{key}:static:{kw.arg}",
                        f"computed expression for static arg "
                        f"'{kw.arg}' of jitted '{key}' — every distinct "
                        f"value compiles a new executable"))
                elif kw.arg not in names and _varying_slice(kw.value):
                    out.append(Finding(
                        "TS06", path, node.lineno, qn,
                        f"{key}:shape:{kw.arg}",
                        f"shape-varying slice passed to jitted '{key}' "
                        f"(kwarg {kw.arg}) — each distinct length "
                        f"retraces; pad to a bucket"))
    return out
