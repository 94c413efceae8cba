"""docqa-lint: AST invariant analysis for the docqa_tpu tree.

Twenty-four project-specific checkers (docs/STATIC_ANALYSIS.md):

* ``cv-protocol``     — condition waits in predicate loops, notify under
  the lock, request-path waits carry a Deadline.
* ``deadline-flow``   — request deadlines thread through; waits clamp.
* ``dispatch-streams``— thread entry points that can reach a jax dispatch
  are ledgered in ``dispatch_streams.json`` under a concurrency budget.
* ``donation``        — buffers donated to jitted calls aren't read after.
* ``dtype-flow``      — bf16/int8 matmuls accumulate f32; bf16 reductions
  upcast; no float64 / silent widening in device code.
* ``entropy-in-state``— no wall-clock/uuid/urandom values in cache keys,
  prefix keys, or replayed journal records; telemetry timestamp fields
  are sanctioned by naming convention.
* ``guarded-state``   — a field written under a lock anywhere is accessed
  under that lock everywhere (per-class + cross-object bridge facts).
* ``host-sync``       — no blocking device→host syncs on the /ask path
  outside jit (jit-purity's deliberate blind spot).
* ``jit-purity``      — no side effects / host syncs in traced code.
* ``lock-discipline`` — one lock order (full-DFS cycles over a transitive
  acquisition graph); no blocking I/O under a lock.
* ``mesh-axes``       — sharding/collective axis names resolve to the
  declared mesh; collectives stay inside their ``shard_map``.
* ``order-stability`` — set/listdir/glob iteration (and dict iteration
  inside order-sink functions) feeding pack order, batch assembly, key
  construction, or journal serialization must be sorted or justified
  via ``# docqa-lint: ordered(<reason>)``.
* ``phi-taint``       — raw pre-deid text never reaches logs/metrics/
  external payloads.
* ``replay-key-integrity`` — no builtin ``hash()`` of str/bytes in
  cross-restart-persistent keys (per-process hash salting); hashlib/
  crc32/pure-integer arithmetic are the sanctioned derivations.
* ``resource-flow``   — every acquired resource (KV block table, cost
  record, spine ticket, trace) reaches exactly one release on every
  control-flow path: leak-on-exception-edge, double-release, and
  release-of-unacquired are findings.
* ``retire-once``     — every request path hits exactly one retirement
  site; terminal sites are ledgered in ``retirement_sites.json``
  (stale entries fail).
* ``retrace-hazard``  — jit wrappers are built once and reused; static
  arguments stay hashable and stable.
* ``rng-discipline``  — jax.random keys are affine on the serving path
  (consume once, then split/fold_in); no literal ``PRNGKey`` reachable
  from the request path (per-request keys come from the counter-minted
  scheme); no module-global numpy/``random`` RNG on device-result or
  replay-key paths.
* ``shed-taxonomy``   — every raise reachable from the request path is a
  ledgered typed shed in ``shed_taxonomy.json`` carrying its declared
  HTTP status, cost outcome, and trace flag; bare ``Exception`` raises
  and subtype-swallowing catches are findings.
* ``spec-shape``      — PartitionSpec arity matches the annotated rank.
* ``thread-lifecycle``— every thread has a reachable join on its owner's
  stop/close path (daemon threads that can reach jax especially).
* ``wire-consumer``   — every subscript/``.get`` read of an HTTP
  response, broker body, journal record, or dotted metric path resolves
  to a declared producer key; orphaned producer keys also flag.
* ``wire-safety``     — device arrays, numpy scalars, locks, Trace/Span
  objects, and non-finite floats at serialization boundaries
  (``json_response`` / broker publish / journal write) are findings;
  ``to_wire()`` coercion sanctions the site.
* ``wire-schema``     — each route handler's response key tree, derived
  from the AST, matches its ``api_contract.json`` entry (per-endpoint
  versioning; NEW, REMOVED, and STALE keys all fail; pydantic models in
  service/schemas.py must mirror their endpoint's contract).

Tier B lives in ``analysis/shard_audit.py`` (docs/SHARDING.md) — lower
the device-plane programs on virtual meshes, hold their collective counts
to the checked-in ``shard_budget.json`` — in
``analysis/compile_audit.py``: drive the canonical serving workloads
under compile counting, AOT-measure each root's ``memory_analysis()``
bytes, and hold both to ``compile_budget.json`` (zero steady-state
retraces, per-root HBM ceilings) — in ``analysis/race_witness.py``
(docs/STATIC_ANALYSIS.md "Concurrency witness"): opt-in runtime
instrumentation of lock acquisition whose witnessed order graph is
cross-checked edge-for-edge against lock-discipline's static graph by
the chaos/soak gates — and in ``analysis/ledger_audit.py``
(docs/STATIC_ANALYSIS.md "Ledger witness"): opt-in runtime
instrumentation of KV-table / cost-record lifecycle events whose
witnessed acquire sites are cross-checked against resource-flow's
static protocol table, failing on leaks, unretired records, or static
blind spots — and in ``analysis/wire_audit.py`` (docs/STATIC_ANALYSIS.md
"Wire contract"): boot the fake-mode runtime, drive every registered
route over real HTTP, validate each live response key tree and JSON
types against ``api_contract.json``, and round-trip a broker journal
across a simulated restart — and in ``analysis/replay_audit.py``
(docs/STATIC_ANALYSIS.md "Replay witness"): run the deterministic CPU
smoke twice under identical seeds but different hash salts and gate on
bitwise equality of token streams, retrieval ids, journal replay, and
the shadow-sampler selection, with every entropy source in the tree
ledgered and justified in ``determinism_manifest.json``.

Entry points: ``scripts/lint.py`` / ``scripts/shard_audit.py`` /
``scripts/compile_audit.py`` / ``scripts/serve_cluster_loop.py`` /
``scripts/ledger_audit.py`` / ``scripts/wire_audit.py`` /
``scripts/replay_audit.py`` (CLIs) and
``pytest -m lint`` (tier-1 gate, tests/test_analysis.py,
tests/test_numcheck.py, tests/test_shardcheck.py,
tests/test_racecheck.py, tests/test_shard_audit.py,
tests/test_compile_audit.py, tests/test_lifecheck.py,
tests/test_wirecheck.py, tests/test_detcheck.py).
"""

from docqa_tpu.analysis.core import (  # noqa: F401
    Baseline,
    Finding,
    Package,
    all_checkers,
    analyze_paths,
    default_baseline_path,
    run,
)
