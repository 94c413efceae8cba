"""host-sync: no blocking device→host syncs on the /ask serving path
outside jit-traced code.

jit-purity polices host syncs INSIDE traced code (they break tracing);
this rule covers the blind spot it deliberately leaves: plain host
functions on the request path.  There, ``np.asarray``/``.item()``/
``jax.device_get``/``float(device_value)`` are legal Python — and each
one BLOCKS the calling thread until the device pipeline drains.  The
serving
loop's whole design is ONE packed fetch per decode chunk
(``serve._process_chunk``) with everything else chained device-side; a
stray scalar sync re-serializes the pipeline invisibly.

Scope: the /ask chain (``deadline_flow.REQUEST_PATH_MODULES``; fixtures
opt in with ``# docqa-lint: request-path``), minus every function the
jit-purity discovery marks traced (those belong to that rule).

Findings — patterns that are *unambiguously* a sync; the sanctioned
fetch idiom (``host = np.asarray(device_ref)`` on a name/attribute, one
per dispatch) is deliberately NOT flagged:

1. ``jax.device_get(...)`` — a fetch by definition;
2. ``.item()`` / ``.tolist()`` — scalar/list syncs (host containers have
   no ``.item``; a numpy receiver would already be host-side and cheap,
   so the conservative flag is still actionable);
3. ``float(x)`` / ``int(x)`` / ``bool(x)`` where ``x``'s fact says
   device: assigned from a ``jnp.*``/``jax.*`` call or from calling a
   known jit wrapper (a local ``fn = jax.jit(...)`` / the engines'
   ``_get_*_fn()`` accessors);
4. ``np.asarray(...)`` / ``np.array(...)`` applied DIRECTLY to a
   ``jnp``/``jax`` call or a jit-wrapper call — materializing a freshly
   computed device intermediate on the host mid-pipeline, instead of the
   fetch-a-held-reference idiom.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set

from docqa_tpu.analysis.core import (
    Finding,
    FunctionInfo,
    Package,
    call_name,
)
from docqa_tpu.analysis.deadline_flow import REQUEST_PATH_MODULES
from docqa_tpu.analysis.jit_purity import (
    JIT_WRAPPERS,
    JitPurityChecker,
    discover_jit_roots,
)

_GET_FN_RE = re.compile(r"_get_\w*fn$")
_SYNC_METHODS = frozenset({"item", "tolist"})


def traced_function_ids(package: Package) -> Set[int]:
    """ids of every function node jit-purity considers traced (direct
    roots + transitive closure over package calls) — host-sync must not
    double-report inside them."""
    checker = JitPurityChecker()
    traced, lambdas = discover_jit_roots(package)
    frontier = [(fn, fn.node) for fn, _via in traced.values()]
    frontier.extend((fn, lam) for fn, lam, _via in lambdas)
    while frontier:
        fn, body = frontier.pop()
        for node in ast.walk(body):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            tail = fn.module.resolve_alias(name).rsplit(".", 1)[-1]
            if tail in JIT_WRAPPERS:
                continue
            callee = package.resolve_call(fn, node)
            if callee is None and name and "." not in name:
                callee = checker._partial_alias(package, fn, name)
            if callee is not None and id(callee.node) not in traced:
                traced[id(callee.node)] = (callee, "")
                frontier.append((callee, callee.node))
    return set(traced)


class HostSyncChecker:
    rule = "host-sync"

    def check(self, package: Package) -> List[Finding]:
        out: List[Finding] = []
        traced = traced_function_ids(package)
        for fn in package.functions:
            module = fn.module
            if not (
                module.name in REQUEST_PATH_MODULES
                or module.request_path_pragma
            ):
                continue
            if id(fn.node) in traced:
                continue
            self._scan(fn, out)
        return out

    # -- per-function --------------------------------------------------------

    def _scan(self, fn: FunctionInfo, out: List[Finding]) -> None:
        module = fn.module

        def add(node, message) -> None:
            out.append(
                Finding(
                    self.rule, module.relpath,
                    getattr(node, "lineno", 1), fn.qualname, message,
                )
            )

        # device facts: name -> True when the value lives on device
        device: Dict[str, bool] = {}
        # names bound to jit wrappers (calling them yields device values)
        wrappers: Set[str] = set()

        def is_device_call(call: ast.Call) -> bool:
            name = call_name(call)
            if not name:
                return False
            resolved = module.resolve_alias(name)
            head = resolved.split(".")[0]
            tail = resolved.rsplit(".", 1)[-1]
            if head in ("jnp",) or resolved.startswith("jax.numpy."):
                return True
            if resolved.startswith("jax.lax.") or resolved.startswith(
                "jax.random."
            ):
                return True
            if tail in JIT_WRAPPERS:
                return False  # constructing a wrapper is not a dispatch
            base = name.split(".")[0]
            if base in wrappers or name in wrappers:
                return True
            return False

        def expr_is_device(node: ast.AST) -> bool:
            if isinstance(node, ast.Name):
                return device.get(node.id, False)
            if isinstance(node, ast.Subscript):
                return expr_is_device(node.value)
            if isinstance(node, ast.Attribute) and node.attr in (
                "T", "mT", "real", "imag"
            ):
                return expr_is_device(node.value)
            if isinstance(node, ast.Call):
                name = call_name(node)
                resolved = module.resolve_alias(name) if name else ""
                # np.asarray(...) LAUNDERS: its result is host-side
                if resolved.rsplit(".", 1)[-1] in (
                    "asarray", "array"
                ) and resolved.split(".")[0] in ("np", "numpy"):
                    return False
                return is_device_call(node)
            if isinstance(node, ast.BinOp):
                return expr_is_device(node.left) or expr_is_device(
                    node.right
                )
            return False

        def handle_expr(node: ast.AST) -> None:
            """Check every call in an expression tree, without descending
            into nested defs/lambdas (their own scopes)."""
            stack = [node]
            while stack:
                cur = stack.pop()
                if isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                ):
                    continue
                if isinstance(cur, ast.Call):
                    check_call(cur)
                stack.extend(ast.iter_child_nodes(cur))

        def bind_assign(stmt: ast.Assign) -> None:
            value = stmt.value
            jitish = False
            if isinstance(value, ast.Call):
                name = call_name(value)
                tail = (
                    module.resolve_alias(name).rsplit(".", 1)[-1]
                    if name else ""
                )
                attr_tail = name.rsplit(".", 1)[-1] if name else ""
                jitish = tail in JIT_WRAPPERS or bool(
                    _GET_FN_RE.search(attr_tail)
                )
            dev = expr_is_device(value)
            for target in stmt.targets:
                for n in ast.walk(target):
                    if not isinstance(n, ast.Name):
                        continue
                    if jitish:
                        wrappers.add(n.id)
                        device[n.id] = False
                    else:
                        device[n.id] = dev

        # statement-order scan (no nested defs: they have their own pass)
        def walk(stmts) -> None:
            for stmt in stmts:
                if isinstance(
                    stmt, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                if isinstance(stmt, ast.Assign):
                    handle_expr(stmt.value)
                    bind_assign(stmt)
                    continue
                for _name, field in ast.iter_fields(stmt):
                    if isinstance(field, ast.expr):
                        handle_expr(field)
                    elif isinstance(field, list):
                        if field and isinstance(field[0], ast.stmt):
                            walk(field)
                        elif field and isinstance(
                            field[0], ast.excepthandler
                        ):
                            for handler in field:
                                walk(handler.body)
                        elif field and isinstance(field[0], ast.expr):
                            for e in field:
                                handle_expr(e)
                        elif field and isinstance(field[0], ast.withitem):
                            for item in field:
                                handle_expr(item.context_expr)

        def check_call(node: ast.Call) -> None:
            name = call_name(node)
            if not name:
                return
            resolved = module.resolve_alias(name)
            tail = name.rsplit(".", 1)[-1]
            if resolved == "jax.device_get":
                add(node, "jax.device_get() on the request path — a "
                         "blocking device fetch outside the sanctioned "
                         "one-fetch-per-dispatch idiom")
                return
            if tail in _SYNC_METHODS and isinstance(node.func, ast.Attribute):
                add(node, f".{tail}() on the request path — a blocking "
                         "scalar/host sync; batch it into the dispatch's "
                         "single packed fetch")
                return
            if name in ("float", "int", "bool") and len(node.args) == 1:
                if expr_is_device(node.args[0]):
                    add(node, f"{name}() on a device value — an implicit "
                             "blocking sync per scalar; fetch once with "
                             "np.asarray and convert host-side")
                return
            if tail in ("asarray", "array") and resolved.split(".")[0] in (
                "np", "numpy"
            ):
                if node.args and isinstance(node.args[0], ast.Call) and (
                    is_device_call(node.args[0])
                ):
                    add(node, "np.asarray() directly over a device "
                             "computation — materializes a mid-pipeline "
                             "intermediate on host; keep the value "
                             "device-side or fetch a held reference once")

        body = getattr(fn.node, "body", None)
        if body:
            walk(body)
