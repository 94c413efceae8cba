"""docqa-lint: fixture tests per rule + the tier-1 gate itself.

Each rule gets three fixture classes: a seeded violation (detected), the
same violation with a ``# docqa-lint: disable=<rule>`` suppression
(silent), and a clean/sanctioned variant (silent).  The gate tests then
run the full twenty-four-checker suite over the real ``docqa_tpu`` tree and
assert it is exactly in sync with the committed baseline — zero new
findings AND zero stale entries (the acceptance contract of
``scripts/lint.py``).
"""

import json
import os
import textwrap

import pytest

from docqa_tpu.analysis import Baseline, Finding, all_checkers, run
from docqa_tpu.analysis.core import default_baseline_path

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "docqa_tpu")


def run_fixture(tmp_path, rule, sources):
    """Write fixture modules and run ONE rule over them."""
    for name, src in sources.items():
        (tmp_path / name).write_text(textwrap.dedent(src))
    return run(str(tmp_path), rules=[rule], package_name="fixture")


# ---------------------------------------------------------------------------
# deadline-flow
# ---------------------------------------------------------------------------


class TestDeadlineFlow:
    def test_dropped_deadline_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def retrieve(query, deadline=None):
                    return query

                def ask(question, deadline=None):
                    return retrieve(question)
                """
            },
        )
        assert len(findings) == 1
        assert "drops the in-scope deadline" in findings[0].message
        assert findings[0].symbol == "ask"

    def test_threaded_deadline_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def retrieve(query, deadline=None):
                    return query

                def ask(question, deadline=None):
                    return retrieve(question, deadline=deadline)
                """
            },
        )
        assert findings == []

    def test_kwargs_forwarding_trusted(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def submit(prompt, deadline=None):
                    return prompt

                def ask(question, deadline=None):
                    kw = {} if deadline is None else {"deadline": deadline}
                    return submit(question, **kw)
                """
            },
        )
        assert findings == []

    def test_unclamped_wait_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def resolve(handle, deadline=None):
                    handle.done.wait(30.0)
                """
            },
        )
        assert len(findings) == 1
        assert "not clamped" in findings[0].message

    def test_unbounded_wait_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def resolve(handle, deadline=None):
                    handle.done.wait()
                """
            },
        )
        assert len(findings) == 1
        assert "unbounded wait" in findings[0].message

    def test_clamped_wait_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def resolve(handle, timeout, deadline=None):
                    if deadline is not None:
                        timeout = deadline.bound(timeout)
                    handle.done.wait(timeout)
                """
            },
        )
        assert findings == []

    def test_derived_clamp_propagates(self, tmp_path):
        # clamp-ness flows through assignments and list.append
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def pull(cv, deadline=None):
                    waits = []
                    waits.append(deadline.remaining())
                    budget = min(waits)
                    cv.wait(budget)
                """
            },
        )
        assert findings == []

    def test_sleep_on_request_path_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                # docqa-lint: request-path
                import time

                def poll():
                    time.sleep(0.005)
                """
            },
        )
        assert len(findings) == 1
        assert "request path" in findings[0].message

    def test_sleep_off_request_path_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                import time

                def poll():
                    time.sleep(0.005)
                """
            },
        )
        assert findings == []

    def test_positional_deadline_expression_counts(self, tmp_path):
        # deadline passed positionally as a non-Name expression is passing
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def retrieve(query, deadline=None):
                    return query

                def ask(req, question, deadline=None):
                    return retrieve(question, req.deadline)
                """
            },
        )
        assert findings == []

    def test_get_many_timeout_is_third_positional(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def pull(broker, deadline=None):
                    a = broker.get_many("queue", 8)
                    b = broker.get_many("queue", 8, deadline.bound(0.1))
                    return a or b
                """
            },
        )
        # first call: NO timeout anywhere -> unbounded (not "unclamped
        # queue-name"); second call: clamped third positional -> clean
        assert len(findings) == 1
        assert "unbounded wait" in findings[0].message

    def test_str_join_not_a_wait(self, tmp_path):
        # ".join" on a string is not a thread join — must not demand a
        # deadline clamp (thread joins still flag via timeout=/receiver)
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def ask(parts, worker, deadline=None):
                    joined = " ".join(parts)
                    worker.join(timeout=10)
                    return joined
                """
            },
        )
        assert len(findings) == 1
        assert "join() timeout is not clamped" in findings[0].message

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "deadline-flow",
            {
                "mod.py": """
                def retrieve(query, deadline=None):
                    return query

                def ask(question, deadline=None):
                    return retrieve(question)  # docqa-lint: disable=deadline-flow
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------


class TestJitPurity:
    def test_print_in_decorated_jit(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                import jax

                @jax.jit
                def kernel(x):
                    print("tracing", x)
                    return x * 2
                """
            },
        )
        assert len(findings) == 1
        assert "print()" in findings[0].message

    def test_time_in_jit_call_site(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                import jax
                import time

                def kernel(x):
                    t0 = time.perf_counter()
                    return x + t0

                fn = jax.jit(kernel)
                """
            },
        )
        assert len(findings) == 1
        assert "host clock" in findings[0].message

    def test_transitive_callee_flagged(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                import jax

                def helper(x):
                    METRICS.counter("steps").inc()
                    return x

                @jax.jit
                def kernel(x):
                    return helper(x) * 2
                """
            },
        )
        assert len(findings) == 1
        assert "metrics" in findings[0].message
        assert "traced via kernel" in findings[0].message

    def test_lock_in_shard_map_body(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                from jax import shard_map

                def build(mesh, lock):
                    def body(v):
                        with lock._lock:
                            return v
                    return shard_map(body, mesh=mesh)
                """
            },
        )
        assert len(findings) == 1
        assert "lock acquisition" in findings[0].message

    def test_host_sync_escape(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                import jax
                import numpy as np

                @jax.jit
                def kernel(x):
                    return np.asarray(x)
                """
            },
        )
        assert len(findings) == 1
        assert "host-sync escape" in findings[0].message

    def test_pure_kernel_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                import jax
                import jax.numpy as jnp

                @jax.jit
                def kernel(x):
                    y = jnp.mean(x)
                    return x.mean() + y.astype(jnp.float32)
                """
            },
        )
        assert findings == []

    def test_host_code_clean(self, tmp_path):
        # the same side effects OUTSIDE traced code are fine
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                import time

                def host_loop(x):
                    print("serving", time.time())
                    return x
                """
            },
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "jit-purity",
            {
                "mod.py": """
                import jax

                @jax.jit
                def kernel(x):
                    print("debug")  # docqa-lint: disable=jit-purity
                    return x
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------


class TestLockDiscipline:
    def test_blocking_under_lock(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import threading

                class Worker:
                    def __init__(self, broker):
                        self._lock = threading.Lock()
                        self.broker = broker

                    def flush(self, body):
                        with self._lock:
                            self.broker.publish("queue", body)
                """
            },
        )
        assert len(findings) == 1
        assert "blocking call" in findings[0].message
        assert "Worker._lock" in findings[0].message

    def test_blocking_through_callee(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import os
                import threading

                class Journal:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def _write(self, f, rec):
                        f.write(rec)
                        os.fsync(f.fileno())

                    def record(self, f, rec):
                        with self._lock:
                            self._write(f, rec)
                """
            },
        )
        assert len(findings) == 1
        assert "blocks (via" in findings[0].message

    def test_inconsistent_order(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import threading

                class Pair:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def one(self):
                        with self._a_lock:
                            with self._b_lock:
                                return 1

                    def two(self):
                        with self._b_lock:
                            with self._a_lock:
                                return 2
                """
            },
        )
        assert len(findings) == 1
        assert "inconsistent lock order" in findings[0].message

    def test_multi_item_with_orders_its_own_items(self, tmp_path):
        # `with a, b:` acquires a then b — must conflict with `with b:
        # with a:` elsewhere (the canonical deadlock pair)
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import threading

                class Pair:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def one(self):
                        with self._a_lock, self._b_lock:
                            return 1

                    def two(self):
                        with self._b_lock:
                            with self._a_lock:
                                return 2
                """
            },
        )
        assert len(findings) == 1
        assert "inconsistent lock order" in findings[0].message

    def test_cv_wait_on_held_lock_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import threading

                class Q:
                    def __init__(self):
                        self._cv = threading.Condition()

                    def pop(self):
                        with self._cv:
                            while not self.items:
                                self._cv.wait(0.5)
                            return self.items.pop()
                """
            },
        )
        assert findings == []

    def test_str_join_not_blocking(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import os
                import threading

                class S:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def fmt(self, parts, d):
                        with self._lock:
                            return os.path.join(d, ",".join(parts))
                """
            },
        )
        assert findings == []

    def test_thread_join_under_lock_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import threading

                class S:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._worker = threading.Thread(target=print)

                    def stop(self):
                        with self._lock:
                            self._worker.join(timeout=10)
                """
            },
        )
        assert len(findings) == 1

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "lock-discipline",
            {
                "mod.py": """
                import threading

                class Worker:
                    def __init__(self, broker):
                        self._lock = threading.Lock()
                        self.broker = broker

                    def flush(self, body):
                        with self._lock:
                            self.broker.publish("q", body)  # docqa-lint: disable=lock-discipline
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# phi-taint
# ---------------------------------------------------------------------------


class TestPhiTaint:
    def test_raw_text_logged(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "phi-taint",
            {
                "mod.py": """
                def handler(log, bodies):
                    for body in bodies:
                        log.info("processing %s", body["text"])
                """
            },
        )
        assert len(findings) == 1
        assert "logging" in findings[0].message

    def test_raw_text_to_clean_queue(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "phi-taint",
            {
                "mod.py": """
                def handler(broker, cfg, body):
                    broker.publish(
                        cfg.clean_queue,
                        {"doc_id": body["doc_id"], "masked": body["text"]},
                    )
                """
            },
        )
        assert len(findings) == 1
        assert "published" in findings[0].message

    def test_raw_queue_publish_sanctioned(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "phi-taint",
            {
                "mod.py": """
                def ingest(broker, cfg, doc_id, text_blob):
                    text, why = extract_text_ex(text_blob, "f.txt")
                    broker.publish(cfg.raw_queue, {"doc_id": doc_id, "text": text})
                """
            },
        )
        assert findings == []

    def test_deidentified_text_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "phi-taint",
            {
                "mod.py": """
                def handler(log, deid, broker, cfg, bodies):
                    texts = [b["text"] for b in bodies]
                    masked = deid.deidentify_batch(texts)
                    for b, clean in zip(bodies, masked):
                        log.info("masked doc %s", clean)
                        broker.publish(cfg.clean_queue, {"masked": clean})
                """
            },
        )
        assert findings == []

    def test_taint_through_assignment_and_fstring(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "phi-taint",
            {
                "mod.py": """
                def handler(registry, body):
                    raw = body["text"]
                    label = f"doc:{raw[:20]}"
                    registry.counter(label).inc()
                """
            },
        )
        assert len(findings) == 1
        assert "metrics label" in findings[0].message

    def test_nested_extractor_taints_retry_call(self, tmp_path):
        # the pipeline's retry.call(_extract) idiom
        findings = run_fixture(
            tmp_path,
            "phi-taint",
            {
                "mod.py": """
                def ingest(log, retry, data):
                    def _extract():
                        return extract_text_ex(data, "f.txt")

                    text, why = retry.call(_extract, name="extract")
                    log.info("got %s", text)
                """
            },
        )
        assert len(findings) == 1
        assert "logging" in findings[0].message

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "phi-taint",
            {
                "mod.py": """
                def handler(log, body):
                    log.debug("raw: %s", body["text"])  # docqa-lint: disable=phi-taint
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


class TestBaseline:
    def _finding(self, msg="m", path="a.py", rule="jit-purity", symbol="f"):
        return Finding(rule=rule, path=path, line=3, symbol=symbol, message=msg)

    def test_split_new_matched_stale(self):
        f1, f2 = self._finding("one"), self._finding("two")
        baseline = Baseline.from_findings([f1])
        baseline.entries.append(
            {
                "rule": "phi-taint",
                "path": "gone.py",
                "symbol": "g",
                "message": "vanished",
                "justification": "was accepted",
            }
        )
        new, matched, stale = baseline.split([f1, f2])
        assert new == [f2]
        assert matched == [f1]
        assert len(stale) == 1 and stale[0]["path"] == "gone.py"

    def test_fingerprint_ignores_line(self):
        a = Finding("r", "p.py", 10, "f", "msg")
        b = Finding("r", "p.py", 99, "f", "msg")
        assert a.fingerprint == b.fingerprint

    def test_save_load_roundtrip(self, tmp_path):
        baseline = Baseline.from_findings([self._finding()], "because")
        path = str(tmp_path / "baseline.json")
        baseline.save(path)
        loaded = Baseline.load(path)
        assert loaded.entries == baseline.entries
        new, matched, stale = loaded.split([self._finding()])
        assert not new and not stale and len(matched) == 1

    def test_scoped_update_preserves_out_of_scope_entries(self):
        """A --rules/sub-path --update-baseline must not destroy justified
        entries for rules or files the run never analyzed."""
        other_rule = {
            "rule": "lock-discipline",
            "path": "a.py",
            "symbol": "f",
            "message": "held",
            "justification": "the lock IS the journal order",
        }
        other_path = {
            "rule": "jit-purity",
            "path": "elsewhere.py",
            "symbol": "g",
            "message": "print",
            "justification": "debug build only",
        }
        still_firing = self._finding("kept", path="a.py")
        old = Baseline.from_findings([still_firing], "real reason")
        old.entries += [other_rule, other_path]
        updated = old.updated(
            [still_firing],
            active_rules={"jit-purity"},  # lock-discipline NOT run
            analyzed_paths={"a.py"},  # elsewhere.py NOT analyzed
        )
        fps = {Baseline._fp(e) for e in updated.entries}
        assert Baseline._fp(other_rule) in fps
        assert Baseline._fp(other_path) in fps
        kept = [e for e in updated.entries if e["message"] == "kept"]
        assert kept and kept[0]["justification"] == "real reason"
        # a full-scope update still drops entries that no longer fire
        full = old.updated(
            [still_firing],
            active_rules={"jit-purity", "lock-discipline"},
            analyzed_paths={"a.py", "elsewhere.py"},
        )
        assert {e["message"] for e in full.entries} == {"kept"}

    def test_single_file_paths_match_package_paths(self, tmp_path):
        """Fingerprint paths are package-root-relative no matter what root
        the analyzer was pointed at — a single-file run must match the
        baseline a package run wrote."""
        pkg = tmp_path / "pkg"
        sub = pkg / "sub"
        sub.mkdir(parents=True)
        (pkg / "__init__.py").write_text("")
        (sub / "__init__.py").write_text("")
        (sub / "mod.py").write_text(
            textwrap.dedent(
                """
                import jax

                @jax.jit
                def kernel(x):
                    print(x)
                    return x
                """
            )
        )
        from_pkg = run(str(pkg), rules=["jit-purity"])
        from_file = run(str(sub / "mod.py"), rules=["jit-purity"])
        assert [f.path for f in from_pkg] == ["sub/mod.py"]
        assert [f.fingerprint for f in from_file] == [
            f.fingerprint for f in from_pkg
        ]


# ---------------------------------------------------------------------------
# the tier-1 gate: the real tree is exactly in sync with the baseline
# ---------------------------------------------------------------------------


class TestTreeGate:
    def test_all_rules_active(self):
        assert sorted(all_checkers()) == [
            "cv-protocol",
            "deadline-flow",
            "dispatch-streams",
            "donation",
            "dtype-flow",
            "entropy-in-state",
            "guarded-state",
            "host-sync",
            "jit-purity",
            "lock-discipline",
            "mesh-axes",
            "order-stability",
            "phi-taint",
            "replay-key-integrity",
            "resource-flow",
            "retire-once",
            "retrace-hazard",
            "rng-discipline",
            "shed-taxonomy",
            "spec-shape",
            "thread-lifecycle",
            "wire-consumer",
            "wire-safety",
            "wire-schema",
        ]

    def test_tree_in_sync_with_baseline(self):
        """`python scripts/lint.py` must exit 0 over its full default
        scope (docqa_tpu + scripts): every finding baselined (with a
        justification), no stale entries."""
        from docqa_tpu.analysis import analyze_paths

        findings, _analyzed = analyze_paths(
            [PKG, os.path.join(REPO, "scripts")]
        )
        baseline = Baseline.load(default_baseline_path())
        new, matched, stale = baseline.split(findings)
        assert not new, "unbaselined findings:\n" + "\n".join(
            f.format() for f in new
        )
        assert not stale, "stale baseline entries:\n" + json.dumps(
            stale, indent=2
        )

    def test_baseline_entries_justified(self):
        baseline = Baseline.load(default_baseline_path())
        for entry in baseline.entries:
            justification = entry.get("justification", "")
            assert justification and "TODO" not in justification, (
                f"baseline entry without a real justification: {entry}"
            )
