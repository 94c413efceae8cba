"""docqa-numcheck Tier A: fixture tests for the three numerics/compile
rules (dtype-flow, retrace-hazard, host-sync).

Same shape as tests/test_analysis.py: per rule, a seeded violation
(detected), the violation under a ``# docqa-lint: disable=<rule>``
suppression (silent), and a clean/sanctioned variant (silent) — plus the
rule-specific propagation mechanics the docstrings promise (astype/.dtype
rebinds, cross-module facts through call resolution, quant-boundary
return facts, static-arg hazards, device-fact laundering).
"""

import textwrap

import pytest

from docqa_tpu.analysis import run

pytestmark = pytest.mark.lint


def run_fixture(tmp_path, rule, sources):
    for name, src in sources.items():
        (tmp_path / name).write_text(textwrap.dedent(src))
    return run(str(tmp_path), rules=[rule], package_name="fixture")


# ---------------------------------------------------------------------------
# dtype-flow
# ---------------------------------------------------------------------------


class TestDtypeFlow:
    def test_bf16_matmul_operator_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax.numpy as jnp

                def score(w):
                    x = jnp.ones((8, 8), jnp.bfloat16)
                    return x @ w
                """
            },
        )
        assert len(findings) == 1
        assert "bf16 matmul via '@'" in findings[0].message

    def test_bf16_dot_call_without_preferred_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax.numpy as jnp

                def score(x, w):
                    xq = x.astype(jnp.bfloat16)
                    return jnp.dot(xq, w)
                """
            },
        )
        assert len(findings) == 1
        assert "preferred_element_type" in findings[0].message

    def test_preferred_f32_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax
                import jax.numpy as jnp

                def score(x, w):
                    xq = x.astype(jnp.bfloat16)
                    a = jnp.dot(xq, w, preferred_element_type=jnp.float32)
                    b = jax.lax.dot_general(
                        xq, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    return a + b
                """
            },
        )
        assert findings == []

    def test_preferred_too_narrow_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax.numpy as jnp

                def score(x, w):
                    xq = x.astype(jnp.bfloat16)
                    return jnp.dot(
                        xq, w, preferred_element_type=jnp.bfloat16
                    )
                """
            },
        )
        assert len(findings) == 1
        assert "float32 or wider" in findings[0].message

    def test_int8_quant_boundary_return_fact_propagates(self, tmp_path):
        # the models/quant.py shape: a helper mints int8 via astype, the
        # caller matmuls the returned tensor — cross-function return fact
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "quantish.py": """
                import jax.numpy as jnp

                def quantize(w):
                    scale = jnp.max(jnp.abs(w), axis=0) / 127.0
                    q = jnp.round(w / scale).astype(jnp.int8)
                    return q, scale

                def forward(x, w):
                    q, scale = quantize(w)
                    return x @ q
                """
            },
        )
        assert len(findings) == 1
        assert "i8 matmul" in findings[0].message
        assert findings[0].symbol == "forward"

    def test_cross_module_param_fact_propagates(self, tmp_path):
        # bf16 fact crosses a package-resolved call into the callee
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "kernels.py": """
                import jax.numpy as jnp

                def project(x, w):
                    return jnp.matmul(x, w)
                """,
                "caller.py": """
                import jax.numpy as jnp
                from kernels import project

                def run(w):
                    x = jnp.zeros((4, 4), jnp.bfloat16)
                    return project(x, w)
                """,
            },
        )
        assert len(findings) == 1
        assert findings[0].path == "kernels.py"
        assert "dtype via" in findings[0].message

    def test_bf16_reduction_detected_and_upcast_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax.numpy as jnp

                def bad(x):
                    h = x.astype(jnp.bfloat16)
                    return jnp.sum(h)

                def good(x):
                    h = x.astype(jnp.bfloat16)
                    a = jnp.sum(h, dtype=jnp.float32)
                    b = jnp.sum(h.astype(jnp.float32))
                    return a + b
                """
            },
        )
        assert len(findings) == 1
        assert findings[0].symbol == "bad"
        assert "f32 accumulator" in findings[0].message

    def test_bf16_softmax_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax
                import jax.numpy as jnp

                def attend(scores):
                    s = scores.astype(jnp.bfloat16)
                    return jax.nn.softmax(s, axis=-1)
                """
            },
        )
        assert len(findings) == 1
        assert "softmax" in findings[0].message

    def test_dtype_rebind_through_other_arrays_dtype(self, tmp_path):
        # x.astype(y.dtype) takes y's fact — the serve._prefill_program
        # idiom; an unknown-dtype rebind must stay silent (no guessing)
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax.numpy as jnp

                def scatter(cache, w):
                    low = jnp.zeros((4, 4), jnp.bfloat16)
                    relabeled = w.astype(low.dtype)
                    bad = relabeled @ w
                    unknown = w.astype(cache.dtype)
                    fine = unknown @ w
                    return bad + fine
                """
            },
        )
        assert len(findings) == 1
        assert findings[0].line == 7  # only the bf16-rebound matmul

    def test_float64_in_device_code_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax.numpy as jnp

                def widen(x):
                    return jnp.asarray(x, jnp.float64)
                """
            },
        )
        assert len(findings) == 1
        assert "float64" in findings[0].message

    def test_f64_operand_widens_bf16_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import numpy as np
                import jax.numpy as jnp

                def mix(x):
                    h = x.astype(jnp.bfloat16)
                    bias = np.zeros((4,), np.float64)
                    return h + bias
                """
            },
        )
        assert len(findings) == 1
        assert "silently widens" in findings[0].message

    def test_host_float64_alone_clean(self, tmp_path):
        # numpy f64 on the host, never touching a jax value, is fine
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import numpy as np

                def stats(rows):
                    acc = np.zeros((4,), np.float64)
                    return acc + len(rows)
                """
            },
        )
        assert findings == []

    def test_upcast_pipeline_clean(self, tmp_path):
        # the attention_reference recipe: upcast first, then math
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax
                import jax.numpy as jnp

                def attend(q, k):
                    qf = q.astype(jnp.float32)
                    kf = k.astype(jnp.float32)
                    scores = jnp.einsum("qd,kd->qk", qf, kf)
                    return jax.nn.softmax(scores, axis=-1)
                """
            },
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "dtype-flow",
            {
                "mod.py": """
                import jax.numpy as jnp

                def score(w):
                    x = jnp.ones((8, 8), jnp.bfloat16)
                    return x @ w  # docqa-lint: disable=dtype-flow
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------


class TestRetraceHazard:
    def test_jit_in_loop_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                def sweep(fns, xs):
                    outs = []
                    for f in fns:
                        g = jax.jit(f)
                        outs.append(g(xs))
                    return outs
                """
            },
        )
        assert len(findings) == 1
        assert "inside a loop" in findings[0].message

    def test_construct_and_invoke_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                def step(f, x):
                    return jax.jit(f)(x)
                """
            },
        )
        assert len(findings) == 1
        assert "constructed and invoked" in findings[0].message

    def test_aot_lower_chain_clean(self, tmp_path):
        # jax.jit(f).lower(...).compile() is the sanctioned AOT pattern
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                def audit(f, x):
                    return jax.jit(f).lower(x).compile().as_text()
                """
            },
        )
        assert findings == []

    def test_cached_wrapper_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                class Engine:
                    def __init__(self):
                        self._fn = None

                    def get(self, f):
                        if self._fn is None:
                            self._fn = jax.jit(f)
                        return self._fn
                """
            },
        )
        assert findings == []

    def test_shard_map_apply_clean(self, tmp_path):
        # shard_map(body, ...)(x) inside a traced program is the idiom
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                from jax import shard_map

                def kernel(body, mesh, x):
                    return shard_map(body, mesh=mesh)(x)
                """
            },
        )
        assert findings == []

    def test_unhashable_static_literal_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                def kernel(x, shape):
                    return x.reshape(shape)

                fast = jax.jit(kernel, static_argnums=(1,))

                def run(x):
                    return fast(x, [4, 4])
                """
            },
        )
        assert len(findings) == 1
        assert "unhashable" in findings[0].message

    def test_varying_static_value_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                def kernel(x, n):
                    return x[:n]

                fast = jax.jit(kernel, static_argnums=(1,))

                def serve(x, prompt):
                    return fast(x, len(prompt))
                """
            },
        )
        assert len(findings) == 1
        assert "retraces per call" in findings[0].message

    def test_stable_static_value_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                def kernel(x, n):
                    return x[:n]

                fast = jax.jit(kernel, static_argnums=(1,))

                def serve(x):
                    return fast(x, 16)
                """
            },
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "retrace-hazard",
            {
                "mod.py": """
                import jax

                def probe(f, x):
                    return jax.jit(f)(x)  # docqa-lint: disable=retrace-hazard
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


class TestHostSync:
    def test_item_on_request_path_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path

                def score_of(vals):
                    return vals.item()
                """
            },
        )
        assert len(findings) == 1
        assert ".item()" in findings[0].message

    def test_device_get_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path
                import jax

                def fetch(x):
                    return jax.device_get(x)
                """
            },
        )
        assert len(findings) == 1
        assert "device_get" in findings[0].message

    def test_float_on_device_value_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path
                import jax.numpy as jnp

                def best(scores):
                    top = jnp.max(scores)
                    return float(top)
                """
            },
        )
        assert len(findings) == 1
        assert "implicit blocking sync" in findings[0].message

    def test_asarray_over_device_computation_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path
                import numpy as np
                import jax.numpy as jnp

                def norms(x):
                    return np.asarray(jnp.linalg.norm(x, axis=-1))
                """
            },
        )
        assert len(findings) == 1
        assert "mid-pipeline" in findings[0].message

    def test_sanctioned_fetch_of_held_reference_clean(self, tmp_path):
        # the serve._process_chunk idiom: ONE np.asarray over a held
        # device reference, then host-side conversion of the host copy
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path
                import numpy as np

                def process(packed_dev):
                    packed_h = np.asarray(packed_dev)
                    return int(packed_h[0, 0])
                """
            },
        )
        assert findings == []

    def test_off_request_path_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                def score_of(vals):
                    return vals.item()
                """
            },
        )
        assert findings == []

    def test_inside_jit_left_to_jit_purity(self, tmp_path):
        # traced code is jit-purity's territory; host-sync must not
        # double-report there
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path
                import jax
                import numpy as np

                @jax.jit
                def kernel(x):
                    return np.asarray(x)
                """
            },
        )
        assert findings == []

    def test_laundered_fact_clean(self, tmp_path):
        # np.asarray produces a HOST value: float() of it is free
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path
                import numpy as np

                def first(dev_ref):
                    host = np.asarray(dev_ref)
                    return float(host[0])
                """
            },
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "host-sync",
            {
                "mod.py": """
                # docqa-lint: request-path

                def score_of(vals):
                    return vals.item()  # docqa-lint: disable=host-sync
                """
            },
        )
        assert findings == []
