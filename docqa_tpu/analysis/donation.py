"""donation: a buffer donated to a jitted call must not be read afterwards.

``jax.jit(..., donate_argnums=...)`` hands the argument's device buffer to
XLA for in-place reuse — the continuous batcher's KV cache and the vector
store's append buffers depend on it.  After the call the
donated array is *deleted*: any later read raises
``RuntimeError: Array has been deleted`` — but only on real backends under
real donation (CPU tests often keep the buffer alive), so the bug class
ships silently and detonates on the TPU.  The safe idiom is rebinding the
result over the donated name (``self._dev = self._append_jit(self._dev,
...)``), which this checker recognizes.

Resolution model (no type inference; unresolvable sites stay silent):

* donated callables are found at ``jax.jit``/``pjit`` call sites carrying
  ``donate_argnums=(...)``/``donate_argnames=(...)`` with literal values,
  tracked through (a) local names — ``fn = jax.jit(step, donate_argnums=
  (0,))`` … ``fn(state, batch)``; (b) ``self.X = jax.jit(...)``
  attributes, called as ``self.X(...)`` from any method of the same
  class (multiple assignments to one attribute union their donated
  positions — the spec-decode/plain branches of the batcher); (c) local
  names assigned from a same-class getter that trivially ``return
  self.X`` (the ``fn = self._get_decode_fn()`` idiom); (d) immediate
  ``jax.jit(f, donate_argnums=...)(args)`` calls.
* at each such call, the argument expression at every donated position
  (a bare name or dotted ``self.…`` chain) is tracked; a READ of that
  exact expression on any later line of the same function flags —
  unless a rebind (assignment to the same name/chain, including tuple
  unpacking of the call's own result) happens on an earlier-or-equal
  line.  Reads inside the donating call itself don't count; line order
  approximates control flow (a loop back-edge read is out of scope),
  EXCEPT that a read in the mutually-exclusive arm of the same ``if``
  as the donating call never flags — exactly one arm executes (the
  spec/non-spec dispatch branches in ``serve.warmup`` donate the same
  fresh buffer from either arm).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from docqa_tpu.analysis.core import (
    Finding,
    FunctionInfo,
    Package,
    call_name,
    dotted_name,
    expr_text,
)

_JIT_NAMES = frozenset({"jit", "pjit"})


def _donated_positions(call: ast.Call) -> Optional[Tuple[Set[int], Set[str]]]:
    """(argnums, argnames) from a jax.jit call, or None when it donates
    nothing / nothing literal."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            for el in _elements(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, int):
                    nums.add(el.value)
        elif kw.arg == "donate_argnames":
            for el in _elements(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.add(el.value)
    return (nums, names) if (nums or names) else None


def _elements(node: ast.AST) -> Sequence[ast.AST]:
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return node.elts
    return [node]


def _is_jit_call(fn: FunctionInfo, node: ast.Call) -> bool:
    name = call_name(node)
    if not name:
        return False
    resolved = fn.module.resolve_alias(name)
    return resolved.rsplit(".", 1)[-1] in _JIT_NAMES


def _branch_paths(root: ast.AST) -> Dict[int, Tuple[Tuple[int, str], ...]]:
    """Node id -> chain of ``(id(If node), arm)`` ancestors, where arm is
    ``"body"`` or ``"orelse"``.  Two nodes whose chains disagree on any
    shared If sit in mutually-exclusive arms — at most one executes."""
    paths: Dict[int, Tuple[Tuple[int, str], ...]] = {}

    def visit(node: ast.AST, path: Tuple[Tuple[int, str], ...]) -> None:
        is_if = isinstance(node, ast.If)
        for field_name, field in ast.iter_fields(node):
            children = field if isinstance(field, list) else [field]
            child_path = path
            if is_if and field_name in ("body", "orelse"):
                child_path = path + ((id(node), field_name),)
            for child in children:
                if isinstance(child, ast.AST):
                    paths[id(child)] = child_path
                    visit(child, child_path)

    paths[id(root)] = ()
    visit(root, ())
    return paths


def _mutually_exclusive(
    a: Tuple[Tuple[int, str], ...], b: Tuple[Tuple[int, str], ...]
) -> bool:
    arms = dict(a)
    return any(
        if_id in arms and arms[if_id] != arm for if_id, arm in b
    )


class DonationChecker:
    rule = "donation"

    def check(self, package: Package) -> List[Finding]:
        out: List[Finding] = []
        # class-level donated attributes: (module id, class) -> attr ->
        # (argnums, argnames); plus trivial getters returning them
        attr_donations: Dict[Tuple[int, str], Dict[str, Tuple[Set[int], Set[str]]]] = {}
        getters: Dict[Tuple[int, str], Dict[str, str]] = {}

        for fn in package.functions:
            if fn.class_name is None:
                continue
            cls_key = (id(fn.module), fn.class_name)
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call
                ) and _is_jit_call(fn, node.value):
                    donated = _donated_positions(node.value)
                    if donated is None:
                        continue
                    for t in node.targets:
                        text = expr_text(t)
                        if text.startswith("self."):
                            slot = attr_donations.setdefault(cls_key, {})
                            old = slot.get(text)
                            if old:  # union across branches/assignments
                                old[0].update(donated[0])
                                old[1].update(donated[1])
                            else:
                                slot[text] = (
                                    set(donated[0]), set(donated[1])
                                )
            # trivial getter: def _get_x(self): ... return self._x
            for stmt in ast.walk(fn.node):
                if isinstance(stmt, ast.Return) and stmt.value is not None:
                    text = expr_text(stmt.value)
                    if text.startswith("self."):
                        getters.setdefault(cls_key, {})[fn.name] = text

        for fn in package.functions:
            out.extend(self._check_function(fn, attr_donations, getters))
        return out

    # -- per-function ---------------------------------------------------------

    def _check_function(
        self,
        fn: FunctionInfo,
        attr_donations,
        getters,
    ) -> List[Finding]:
        out: List[Finding] = []
        cls_key = (id(fn.module), fn.class_name) if fn.class_name else None
        cls_attrs = attr_donations.get(cls_key, {}) if cls_key else {}
        cls_getters = getters.get(cls_key, {}) if cls_key else {}

        # local donated callables: name -> (argnums, argnames)
        local: Dict[str, Tuple[Set[int], Set[str]]] = {}
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Assign):
                continue
            value = node.value
            donated: Optional[Tuple[Set[int], Set[str]]] = None
            if isinstance(value, ast.Call) and _is_jit_call(fn, value):
                donated = _donated_positions(value)
            elif isinstance(value, ast.Call):
                # fn = self._get_decode_fn() -> trivial getter -> attr
                name = call_name(value)
                if name.startswith("self.") and name.count(".") == 1:
                    attr = cls_getters.get(name.split(".", 1)[1])
                    if attr is not None:
                        donated = cls_attrs.get(attr)
            if donated is None:
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    local[t.id] = donated

        # find donating calls
        paths = _branch_paths(fn.node)
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            donated = None
            name = call_name(node)
            if isinstance(node.func, ast.Call) and _is_jit_call(
                fn, node.func
            ):
                donated = _donated_positions(node.func)
            elif isinstance(node.func, ast.Name):
                donated = local.get(node.func.id)
            elif name.startswith("self."):
                donated = cls_attrs.get(name)
            if donated is None:
                continue
            out.extend(self._check_call(fn, node, donated, paths))
        return out

    def _check_call(
        self,
        fn: FunctionInfo,
        call: ast.Call,
        donated: Tuple[Set[int], Set[str]],
        paths: Dict[int, Tuple[Tuple[int, str], ...]],
    ) -> List[Finding]:
        out: List[Finding] = []
        argnums, argnames = donated
        exprs: List[str] = []
        for i in sorted(argnums):
            if i < len(call.args):
                text = expr_text(call.args[i])
                if text and _is_trackable(call.args[i]):
                    exprs.append(text)
        for kw in call.keywords:
            if kw.arg in argnames:
                text = expr_text(kw.value)
                if text and _is_trackable(kw.value):
                    exprs.append(text)
        if not exprs:
            return out

        call_line = call.lineno
        in_call = {id(n) for n in ast.walk(call)}
        # rebinds: line -> set of rebound expression texts
        rebinds: List[Tuple[int, str]] = []
        reads: List[Tuple[int, str, ast.AST]] = []
        for node in ast.walk(fn.node):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for t in targets:
                    for el in _flatten_targets(t):
                        text = expr_text(el)
                        if text:
                            rebinds.append((node.lineno, text))
            if isinstance(node, (ast.Name, ast.Attribute)):
                if id(node) in in_call:
                    continue
                if isinstance(getattr(node, "ctx", None), ast.Load):
                    text = expr_text(node)
                    if text in exprs:
                        reads.append((node.lineno, text, node))

        call_path = paths.get(id(call), ())
        for line, text, node in reads:
            if line <= call_line:
                continue
            if _mutually_exclusive(call_path, paths.get(id(node), ())):
                continue  # other arm of the same if: never both execute
            rebound = any(
                rl <= line and rb == text and rl >= call_line
                for rl, rb in rebinds
            )
            if rebound:
                continue
            out.append(
                Finding(
                    self.rule,
                    fn.module.relpath,
                    line,
                    fn.qualname,
                    f"'{text}' read after being donated to the jitted call "
                    f"on line {call_line} (donated buffers are deleted; "
                    f"rebind the result or drop the donation)",
                )
            )
        return out


def _is_trackable(node: ast.AST) -> bool:
    """Only bare names and dotted chains are tracked (a temporary like
    ``jnp.asarray(x)`` cannot be read again)."""
    return bool(dotted_name(node))


def _flatten_targets(node: ast.AST):
    if isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            yield from _flatten_targets(el)
    elif isinstance(node, ast.Starred):
        yield from _flatten_targets(node.value)
    else:
        yield node
