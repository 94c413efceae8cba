"""retrace-hazard: compile-cache discipline for jit construction and
static arguments.

A serving path owes every jit root a WARM, REUSED compilation cache
(one trace+compile costs seconds on a real chip, PERF.md; a retrace
inside a request is a latency cliff the admission deadline then reads as
an outage).  The compile audit (``analysis/compile_audit.py``) proves the
steady state retrace-free; this rule catches the construction patterns
that defeat the cache before they ship:

1. **jit inside a loop** — ``jax.jit(f)`` / ``pjit(f)`` constructed in a
   ``for``/``while`` body builds a fresh wrapper (and an empty cache)
   every iteration.  Hoist the construction; only the *call* belongs in
   the loop.
2. **construct-and-invoke** — ``jax.jit(f)(x)`` in one expression: the
   wrapper (and its cache) dies with the expression, so every execution
   of that line retraces.  Cache the wrapper (module global, ``self``
   attribute, or the ``_fns`` dict idiom every engine here uses).
   AOT chains (``jax.jit(f).lower(...)``) are exempt — lowering once is
   the sanctioned audit/ahead-of-time pattern.
3. **unhashable static argument** — a call site passing a list/dict/set
   literal in a position the wrapper marks static
   (``static_argnums``/``static_argnames``): jit hashes static values,
   so this raises at runtime on the first call.
4. **per-value retrace on a static argument** — a static position fed by
   ``len(...)`` or an enclosing loop variable retraces once per distinct
   value (the cache keys on the VALUE of a static, not its shape).

Wrapper bindings are tracked through the module: decorated defs
(``@jax.jit`` / ``@partial(jax.jit, static_argnums=...)``), assignments
(``fn = jax.jit(f, static_argnames=("k",))``, including ``self._fn =``),
and the calls checked are the same-module call sites of those names —
the no-guess contract of the chassis (an import-crossing call is checked
in the defining module when it, too, is in scope).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from docqa_tpu.analysis.core import (
    Finding,
    FunctionInfo,
    Package,
    call_name,
    dotted_name,
)
# Construction rules cover the CACHED wrappers only: ``shard_map`` builds
# a plain traceable callable with no compile cache of its own, and the
# canonical idiom applies it immediately inside an enclosing jit (the
# construction re-runs per TRACE, not per call) — flagging it would mark
# every sharded kernel in the tree.
_CACHED_WRAPPERS = frozenset({"jit", "pjit"})


def _jit_call(module, node: ast.AST) -> Optional[ast.Call]:
    """The ``jax.jit(...)`` / ``pjit(...)`` Call node, or None.  Unwraps
    ``functools.partial(jax.jit, ...)`` the way jit-purity does."""
    if not isinstance(node, ast.Call):
        return None
    name = call_name(node)
    tail = module.resolve_alias(name).rsplit(".", 1)[-1] if name else ""
    if tail in _CACHED_WRAPPERS:
        return node
    if tail == "partial" and node.args:
        inner = node.args[0]
        if isinstance(inner, (ast.Name, ast.Attribute)):
            inner_tail = module.resolve_alias(
                dotted_name(inner)
            ).rsplit(".", 1)[-1]
            if inner_tail in _CACHED_WRAPPERS:
                return node
    return None


def _static_spec(module, jit_node: ast.Call) -> Tuple[Set[int], Set[str]]:
    """(static positional indices, static argnames) declared on a jit
    call/decorator; unresolvable (computed) specs return empty sets."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in jit_node.keywords:
        if kw.arg == "static_argnums":
            for elt in _literal_elts(kw.value):
                if isinstance(elt, ast.Constant) and isinstance(
                    elt.value, int
                ):
                    nums.add(elt.value)
        elif kw.arg == "static_argnames":
            for elt in _literal_elts(kw.value):
                if isinstance(elt, ast.Constant) and isinstance(
                    elt.value, str
                ):
                    names.add(elt.value)
    return nums, names


def _literal_elts(node: ast.AST) -> List[ast.AST]:
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return list(node.elts)
    return [node]


class RetraceHazardChecker:
    rule = "retrace-hazard"

    def check(self, package: Package) -> List[Finding]:
        out: List[Finding] = []
        for module in package.modules:
            self._check_module(module, out)
        return out

    # -- per-module ----------------------------------------------------------

    def _check_module(self, module, out: List[Finding]) -> None:
        # name -> (static nums incl. any self offset, static names)
        bindings: Dict[str, Tuple[Set[int], Set[str]]] = {}

        # decorated defs: @jax.jit / @partial(jax.jit, static_argnums=...)
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                jc = _jit_call(module, dec) if isinstance(
                    dec, ast.Call
                ) else None
                if jc is None and isinstance(dec, (ast.Name, ast.Attribute)):
                    tail = module.resolve_alias(
                        dotted_name(dec)
                    ).rsplit(".", 1)[-1]
                    if tail in _CACHED_WRAPPERS:
                        bindings[node.name] = (set(), set())
                        continue
                if jc is not None:
                    bindings[node.name] = _static_spec(module, jc)

        # assignments: fn = jax.jit(f, ...), self._fn = jax.jit(...)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Assign):
                continue
            jc = _jit_call(module, node.value)
            if jc is None:
                continue
            spec = _static_spec(module, jc)
            for target in node.targets:
                name = dotted_name(target)
                if name:
                    bindings[name.rsplit(".", 1)[-1]] = spec
                    bindings[name] = spec

        self._construction_hazards(module, out)
        if any(spec[0] or spec[1] for spec in bindings.values()):
            self._static_hazards(module, bindings, out)

    def _construction_hazards(self, module, out: List[Finding]) -> None:
        """Rules 1-2: loop construction and construct-and-invoke."""

        # annotate loop membership + enclosing function with one walk
        def walk(node, in_loop: bool, qual: str) -> None:
            for child in ast.iter_child_nodes(node):
                child_loop = in_loop
                child_qual = qual
                if isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                    child_loop = True
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    child_qual = (
                        f"{qual}.{child.name}" if qual != "<module>"
                        else child.name
                    )
                    child_loop = False  # a def resets loop context
                if isinstance(child, ast.Call):
                    jc = _jit_call(module, child)
                    if jc is child and child_loop:
                        out.append(
                            Finding(
                                self.rule, module.relpath, child.lineno,
                                qual,
                                "jax.jit constructed inside a loop — a "
                                "fresh wrapper discards the compile "
                                "cache every iteration; hoist the "
                                "construction out of the loop",
                            )
                        )
                    # construct-and-invoke: func of THIS call is a jit call
                    if isinstance(child.func, ast.Call) and _jit_call(
                        module, child.func
                    ):
                        out.append(
                            Finding(
                                self.rule, module.relpath, child.lineno,
                                qual,
                                "jit-wrapped function constructed and "
                                "invoked in one expression — the compiled "
                                "program cannot be reused across calls; "
                                "cache the wrapper and call that",
                            )
                        )
                walk(child, child_loop, child_qual)

        walk(module.tree, False, "<module>")

    def _static_hazards(
        self, module, bindings: Dict[str, Tuple[Set[int], Set[str]]],
        out: List[Finding],
    ) -> None:
        """Rules 3-4 at same-module call sites of known jit bindings."""

        def visit(node, loop_vars: Set[str], qual: str) -> None:
            for child in ast.iter_child_nodes(node):
                child_vars = loop_vars
                child_qual = qual
                if isinstance(child, (ast.For, ast.AsyncFor)):
                    child_vars = loop_vars | {
                        n.id
                        for n in ast.walk(child.target)
                        if isinstance(n, ast.Name)
                    }
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    child_qual = (
                        f"{qual}.{child.name}" if qual != "<module>"
                        else child.name
                    )
                    child_vars = set()
                if isinstance(child, ast.Call):
                    name = call_name(child)
                    spec = bindings.get(name) or bindings.get(
                        name.rsplit(".", 1)[-1] if name else ""
                    )
                    if spec and (spec[0] or spec[1]):
                        self._check_call(
                            module, child, spec, child_vars, child_qual, out
                        )
                visit(child, child_vars, child_qual)

        visit(module.tree, set(), "<module>")

    def _check_call(
        self, module, node: ast.Call, spec, loop_vars: Set[str],
        qual: str, out: List[Finding],
    ) -> None:
        nums, names = spec
        static_args: List[Tuple[str, ast.AST]] = []
        for i, arg in enumerate(node.args):
            if i in nums:
                static_args.append((f"position {i}", arg))
        for kw in node.keywords:
            if kw.arg in names:
                static_args.append((f"'{kw.arg}'", kw.value))
        for where, arg in static_args:
            if isinstance(arg, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                                ast.DictComp, ast.SetComp)):
                out.append(
                    Finding(
                        self.rule, module.relpath, arg.lineno, qual,
                        f"unhashable literal in static argument {where} — "
                        "jit hashes static values; pass a tuple or mark "
                        "the argument non-static",
                    )
                )
                continue
            varying = None
            if isinstance(arg, ast.Call) and call_name(arg) == "len":
                varying = "len(...)"
            elif isinstance(arg, ast.Name) and arg.id in loop_vars:
                varying = f"loop variable '{arg.id}'"
            if varying:
                out.append(
                    Finding(
                        self.rule, module.relpath, arg.lineno, qual,
                        f"static argument {where} takes {varying} — the "
                        "cache keys on each distinct static VALUE, so "
                        "this retraces per call; bucket the value or "
                        "make it a traced argument",
                    )
                )
