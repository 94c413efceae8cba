"""dispatch-streams: device work is SPINE-DELEGATED or it is ledgered.

History: the reproduced CPU-client capacity deadlock (PRs 6–8: >= 3
threads holding concurrent sharded dispatches park the process at 0%
CPU; evidence preserved under ``budget.evidence`` in
``dispatch_streams.json``) was first held off by enumerating every
device-dispatching thread and gating the count against a budget.  The
dispatch spine (``engines/spine.py``) retired the hazard class
architecturally: device work is submitted as work items and executed on
the spine's bounded lanes, so the checker is now RE-POINTED at the
spine boundary:

* **ownership** — a function OWNS a device stream when it can reach a
  jax dispatch on its own thread: a direct ``jax.*``/``jnp.*`` call in
  its own body (nested closures handed to ``spine_run``/``spine_submit``
  are the spine's work, not the caller's; pure wrapper constructors —
  ``jax.jit``, ``ShapeDtypeStruct``, ``eval_shape``, ``shard_map``,
  ``tree_map`` — build programs without dispatching), or a resolvable
  call into an owning function.  Calls INTO the spine module never
  propagate ownership — that is the delegation boundary;
* **the thread gate** — every thread entry point whose target OWNS a
  stream must appear in the ledger with a justification, exactly as
  before.  With full delegation the owning set shrinks to the spine's
  own lane loop (plus conservatively-capable entries whose targets are
  statically unresolvable — executor lanes running caller-supplied
  functions); the entries whose justification was "gated by budget" are
  deleted, and ``budget.max_concurrent_device_streams`` counts stream
  FAMILIES (the spine's internal lane concurrency is its ``n_lanes``
  runtime bound, live on ``/api/telemetry`` as ``dispatch_occupancy``);
* **the stage gate** — every ``spine_run("<stage>", …)`` submission
  site must use a stage name listed under the ledger's ``spine.stages``
  (with a one-line description); unknown stages and stale stage entries
  fail like stale baselines.  Adding a device workload now means naming
  its stage in a reviewed file, not adding the Nth dispatching thread.
"""

from __future__ import annotations

import ast
import json
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

from docqa_tpu.analysis.concurrency import (
    ThreadEntry,
    enumerate_thread_entries,
)
from docqa_tpu.analysis.core import (
    Finding,
    FunctionInfo,
    GENERIC_NAMES,
    Package,
    call_name,
)

LEDGER_NAME = "dispatch_streams.json"

# the spine submission idiom (engines/spine.py): closures passed to
# these names are executed on spine lanes, never on the calling thread
SPINE_SUBMIT_TAILS = frozenset({"spine_run", "spine_submit"})
_SPINE_MODULE_SUFFIX = os.sep.join(("engines", "spine.py"))

# jax namespace calls that BUILD programs/wrappers without enqueueing
# device work — owning one of these is not owning a stream.
# TraceAnnotation is the profiler scope metrics.span opens (host-only);
# jnp.dtype is a dtype constructor; NamedSharding / PartitionSpec
# describe a placement, they place nothing.
_JAX_WRAPPER_TAILS = frozenset(
    {
        "jit", "ShapeDtypeStruct", "eval_shape", "shard_map", "tree_map",
        "TraceAnnotation", "dtype", "NamedSharding", "PartitionSpec",
    }
)

# method names that mean device work by convention when the call cannot
# be resolved to ANY package function (fixture trees): every `warmup`
# compiles and dispatches
_DISPATCHING_ATTRS = frozenset({"warmup"})


def default_ledger_path() -> str:
    """The checked-in ledger: ``<repo>/dispatch_streams.json``."""
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg_dir), LEDGER_NAME)


def _package_ledger_path(package: Package) -> Optional[str]:
    """Ledger next to the analyzed package's root (fixture trees carry
    their own or none; the real runs resolve to the repo's)."""
    for module in package.modules:
        rel = module.relpath.replace("/", os.sep)
        if module.path.endswith(rel):
            base = module.path[: -len(rel)].rstrip(os.sep)
            cand = os.path.join(os.path.dirname(base), LEDGER_NAME)
            if os.path.exists(cand):
                return cand
            cand = os.path.join(base, LEDGER_NAME)
            if os.path.exists(cand):
                return cand
    return None


def load_ledger(path: Optional[str]) -> Dict:
    if not path or not os.path.exists(path):
        return {"streams": {}, "budget": {}, "spine": {}}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    data.setdefault("streams", {})
    data.setdefault("budget", {})
    data.setdefault("spine", {})
    return data


def _is_spine_module(fn: FunctionInfo) -> bool:
    rel = fn.module.relpath.replace("/", os.sep)
    return rel.endswith(_SPINE_MODULE_SUFFIX)


def _iter_own_body(fn_node: ast.AST) -> Iterable[ast.AST]:
    """Nodes of a function's OWN body — nested def/lambda subtrees are
    skipped (each nested def is its own FunctionInfo; a closure's device
    work belongs to whoever EXECUTES it, which for spine submissions is
    a ledgered lane, not this function's thread)."""
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        node = stack.pop()
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _direct_dispatch(fn: FunctionInfo) -> Optional[str]:
    """First jax-namespace call in the function's own body that enqueues
    device work (wrapper constructors excluded), or None."""
    for node in _iter_own_body(fn.node):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if not name:
            continue
        resolved = fn.module.resolve_alias(name)
        if resolved.split(".")[0] == "jax" and "." in resolved:
            if resolved.rsplit(".", 1)[-1] in _JAX_WRAPPER_TAILS:
                continue
            return resolved
    return None


def spine_aware_owners(package: Package) -> Dict[int, str]:
    """fn-node-id -> reason, for functions that OWN device dispatch on
    their calling thread (spine-delegated work excluded).  Fixed point
    over package-resolvable calls; calls into the spine module are the
    delegation boundary and never propagate."""
    cache = getattr(package, "_concurrency_memo", None)
    if cache is None:
        cache = {}
        package._concurrency_memo = cache  # type: ignore[attr-defined]
    if "spine_owners" in cache:
        return cache["spine_owners"]

    inits: Dict[str, FunctionInfo] = {}
    for fn in package.functions:
        if fn.name == "__init__" and fn.class_name:
            inits.setdefault(fn.class_name, fn)

    owners: Dict[int, str] = {}
    for fn in package.functions:
        if _is_spine_module(fn):
            # the spine's own lane machinery is THE ledgered stream
            # family; mark its executor so the lane-loop thread entry is
            # gated, but never let callers inherit it (delegation)
            hit = _direct_dispatch(fn)
            if hit is not None:
                owners[id(fn.node)] = hit
            continue
        hit = _direct_dispatch(fn)
        if hit is not None:
            owners[id(fn.node)] = hit

    def propagated(fn: FunctionInfo) -> Optional[str]:
        for node in _iter_own_body(fn.node):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if not name:
                continue
            tail = name.rsplit(".", 1)[-1]
            if tail in SPINE_SUBMIT_TAILS:
                continue  # delegated: the spine's lanes execute it
            callee = package.resolve_call(fn, node)
            if callee is not None:
                if _is_spine_module(callee) and not _is_spine_module(fn):
                    continue  # delegation boundary (cross-module only:
                    # the spine's own machinery still chains to its
                    # lane loop, THE ledgered stream family)
                sub = owners.get(id(callee.node))
                if sub is not None:
                    return f"via {callee.qualname} ({sub})"
                continue
            if "." in name:
                if tail in GENERIC_NAMES:
                    continue  # ambiguity never guesses (core.resolve_call)
                # an external-module receiver (np.linalg.norm, os.path.x)
                # never resolves into the package (mirrors resolve_call)
                head = name.rsplit(".", 1)[0].split(".")[0]
                origin = fn.module.imports.get(head)
                pkg_root = fn.module.name.split(".")[0]
                if origin is not None and origin.split(".")[0] != pkg_root:
                    continue
                # candidates are methods/module functions only — a
                # nested def cannot be the target of an attribute call
                cands = [
                    c
                    for c in package.by_bare_name.get(tail, ())
                    if not _is_spine_module(c)
                    and "<locals>" not in c.qualname
                ]
                if cands:
                    for c in cands:
                        sub = owners.get(id(c.node))
                        if sub is not None:
                            return f"via candidate {c.qualname} ({sub})"
                    continue
                if tail in _DISPATCHING_ATTRS:
                    return f"{name} (compile/dispatch by convention)"
            else:
                ctor = inits.get(tail)
                if ctor is not None:
                    sub = owners.get(id(ctor.node))
                    if sub is not None:
                        return f"via {ctor.qualname} ({sub})"
        return None

    changed = True
    while changed:
        changed = False
        for fn in package.functions:
            if id(fn.node) in owners:
                continue
            why = propagated(fn)
            if why is not None:
                owners[id(fn.node)] = why
                changed = True
    cache["spine_owners"] = owners
    return owners


def enumerate_spine_sites(
    package: Package,
) -> List[Tuple[FunctionInfo, int, Optional[str]]]:
    """Every ``spine_run``/``spine_submit`` call site: (enclosing fn,
    lineno, stage literal or None when the stage is dynamic)."""
    out: List[Tuple[FunctionInfo, int, Optional[str]]] = []
    for fn in package.functions:
        for node in _iter_own_body(fn.node):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if not name or name.rsplit(".", 1)[-1] not in SPINE_SUBMIT_TAILS:
                continue
            stage: Optional[str] = None
            if node.args and isinstance(node.args[0], ast.Constant) and (
                isinstance(node.args[0].value, str)
            ):
                stage = node.args[0].value
            out.append((fn, node.lineno, stage))
    return out


class DispatchStreamsChecker:
    rule = "dispatch-streams"

    def __init__(self, ledger_path: Optional[str] = None) -> None:
        self.ledger_path = ledger_path

    def check(self, package: Package) -> List[Finding]:
        ledger_path = self.ledger_path or _package_ledger_path(package)
        ledger = load_ledger(ledger_path)
        streams: Dict[str, Dict] = ledger["streams"]
        owners = spine_aware_owners(package)
        out: List[Finding] = []

        present: Dict[str, ThreadEntry] = {}
        for entry in enumerate_thread_entries(package):
            capable, why = self._capability(entry, owners)
            if not capable:
                continue
            present.setdefault(entry.key, entry)
            row = streams.get(entry.key)
            if row is None:
                out.append(
                    Finding(
                        self.rule,
                        entry.module_relpath,
                        entry.lineno,
                        entry.site_qualname,
                        f"unledgered device-dispatch stream {entry.key!r} "
                        f"({why}) — route the device work through the "
                        f"dispatch spine (engines/spine.py spine_run), or "
                        f"add the entry to {LEDGER_NAME} with a "
                        "justification and account for it in the "
                        "concurrency budget",
                    )
                )

        analyzed = {m.relpath for m in package.modules}
        if ledger_path is not None:
            for key, row in sorted(streams.items()):
                rel = key.split(":", 1)[0]
                if rel not in analyzed:
                    continue  # another package's entries (scripts vs pkg)
                if key not in present:
                    out.append(
                        Finding(
                            self.rule,
                            rel,
                            1,
                            "<ledger>",
                            f"stale {LEDGER_NAME} entry {key!r}: no such "
                            "dispatch-owning thread entry point exists "
                            "any more — remove it (and reclaim its "
                            "budget slot)",
                        )
                    )
            budget = ledger["budget"].get("max_concurrent_device_streams")
            if budget is not None and present:
                # PROCESS-WIDE count: entries this package run verified
                # as present, plus every declared entry belonging to
                # another package (docqa_tpu vs scripts/ run over the
                # same ledger — each prunes only its own stale entries,
                # so a scripts-side stream must still count against the
                # one budget here, or splitting the analysis into two
                # Package runs would silently split the budget too)
                concurrent = [
                    key
                    for key, row in sorted(streams.items())
                    if row.get("concurrent_with_serving")
                    and (
                        key in present
                        or key.split(":", 1)[0] not in analyzed
                    )
                ]
                if len(concurrent) > int(budget):
                    anchor = next(
                        (present[k] for k in concurrent if k in present),
                        next(iter(present.values())),
                    )
                    out.append(
                        Finding(
                            self.rule,
                            anchor.module_relpath,
                            anchor.lineno,
                            "<ledger>",
                            f"{len(concurrent)} streams marked "
                            "concurrent_with_serving exceed the ledger "
                            f"budget max_concurrent_device_streams="
                            f"{budget} — device work belongs on the "
                            "dispatch spine (engines/spine.py); raise "
                            "the budget only with new capacity evidence",
                        )
                    )
        out.extend(self._check_spine_stages(package, ledger, ledger_path))
        return out

    def _check_spine_stages(
        self, package: Package, ledger: Dict, ledger_path: Optional[str]
    ) -> List[Finding]:
        """The re-pointed gate: spine submission sites must use stage
        names the ledger's ``spine.stages`` section declares, and every
        declared stage must still have a submission site in SOME
        analyzed package (stale stages are pruned only by the package
        that contains spine sites at all, mirroring the streams rule)."""
        sites = enumerate_spine_sites(package)
        if not sites or ledger_path is None:
            return []
        stages: Dict[str, str] = dict(ledger.get("spine", {}).get(
            "stages", {}
        ))
        out: List[Finding] = []
        used: Set[str] = set()
        for fn, lineno, stage in sites:
            if stage is None:
                continue  # dynamic stage: the submitting API's problem
            used.add(stage)
            if stage not in stages:
                out.append(
                    Finding(
                        self.rule,
                        fn.module.relpath,
                        lineno,
                        fn.qualname,
                        f"spine stage {stage!r} is not declared in "
                        f"{LEDGER_NAME} spine.stages — name the new "
                        "device workload there with a one-line "
                        "description (the reviewed list of everything "
                        "that can occupy a dispatch lane)",
                    )
                )
        for stage in sorted(set(stages) - used):
            out.append(
                Finding(
                    self.rule,
                    package.modules[0].relpath,
                    1,
                    "<ledger>",
                    f"stale spine stage {stage!r} in {LEDGER_NAME}: no "
                    "spine_run/spine_submit site uses it any more — "
                    "remove the entry",
                )
            )
        return out

    @staticmethod
    def _capability(entry: ThreadEntry, owners: Dict[int, str]):
        if entry.target is not None:
            why = owners.get(id(entry.target.node))
            if why is None:
                return False, ""
            return True, f"target {entry.target.qualname} dispatches: {why}"
        return True, (
            f"dynamic target {entry.target_text!r} — unresolvable "
            "statically, conservatively dispatch-capable"
        )
