"""docqa-shardcheck Tier A: fixture tests for the sharding-layer rules.

Mirrors tests/test_analysis.py's contract per rule: a seeded violation
produces exactly one finding, the suppressed variant and the clean
variant produce zero.  The seeded mutations here are the sharding bug
classes the checkers exist for: a misspelled mesh axis (silent
replication), a collective outside / wrongly bound inside its
``shard_map``, a donated-then-read buffer (deleted-array crash on real
backends only), and a PartitionSpec whose arity contradicts the
schema-declared rank.
"""

import textwrap

import pytest

from docqa_tpu.analysis import run

pytestmark = pytest.mark.lint


def run_fixture(tmp_path, rule, sources):
    for name, src in sources.items():
        (tmp_path / name).write_text(textwrap.dedent(src))
    return run(str(tmp_path), rules=[rule], package_name="fixture")


# every fixture declares its axes the way runtime/mesh.py does (a config
# field default) so the checker's declared-axis set is self-contained;
# indented to the test strings' margin so the concatenation dedents evenly
_MESH_DECL = """
                class MeshConfig:
                    data_axis: str = "data"
                    model_axis: str = "model"
"""


# ---------------------------------------------------------------------------
# mesh-axes
# ---------------------------------------------------------------------------


class TestMeshAxes:
    def test_misspelled_axis_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                from jax.sharding import PartitionSpec as P

                def pspecs():
                    return {"w": P(None, "modle")}
                """
            },
        )
        assert len(findings) == 1
        assert "'modle' is not a declared mesh axis" in findings[0].message
        assert findings[0].symbol == "pspecs"

    def test_declared_axis_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                from jax.sharding import PartitionSpec as P

                def pspecs(mesh):
                    return {
                        "w": P(None, "model"),
                        "cache": P(mesh.data_axis, None, mesh.model_axis),
                    }
                """
            },
        )
        assert findings == []

    def test_axis_through_local_literal(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                from jax.sharding import PartitionSpec as P

                def pspecs():
                    ax = "modell"
                    return P(ax, None)
                """
            },
        )
        assert len(findings) == 1
        assert "'modell'" in findings[0].message

    def test_mesh_construction_declares(self, tmp_path):
        # a literal Mesh(...) axis tuple is a declaration, not a use
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": """
                from jax.sharding import Mesh, PartitionSpec as P

                def make(devices):
                    return Mesh(devices, ("rows", "cols"))

                def spec():
                    return P("rows", "cols")
                """
            },
        )
        assert findings == []

    def test_collective_outside_shard_map(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                import jax

                def reduce_loss(x):
                    return jax.lax.psum(x, "model")
                """
            },
        )
        assert len(findings) == 1
        assert "outside any shard_map body" in findings[0].message

    def test_collective_wrong_axis_inside_shard_map(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                import jax
                from jax.sharding import PartitionSpec as P
                from jax import shard_map

                def build(mesh):
                    def body(v):
                        return jax.lax.psum(v, "model")

                    return shard_map(
                        body, mesh=mesh,
                        in_specs=(P("data"),), out_specs=P("data"),
                    )
                """
            },
        )
        assert len(findings) == 1
        assert "not bound by the enclosing shard_map" in findings[0].message

    def test_two_sites_bind_independently(self, tmp_path):
        # two shard_maps in ONE function: each body checks against its
        # own site's specs, not the union (the union would hide B's
        # wrong-axis psum behind A's binding)
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                import jax
                from jax.sharding import PartitionSpec as P
                from jax import shard_map

                def build(mesh):
                    def body_a(v):
                        return jax.lax.psum(v, "data")

                    def body_b(v):
                        return jax.lax.psum(v, "data")

                    a = shard_map(
                        body_a, mesh=mesh,
                        in_specs=(P("data"),), out_specs=P("data"),
                    )
                    b = shard_map(
                        body_b, mesh=mesh,
                        in_specs=(P("model"),), out_specs=P("model"),
                    )
                    return a, b
                """
            },
        )
        assert len(findings) == 1
        assert findings[0].symbol == "build.<locals>.body_b"
        assert "not bound" in findings[0].message

    def test_collective_via_partial_helper_clean(self, tmp_path):
        # the ring_attention_local idiom: body -> partial-bound helper ->
        # collective over the parameter the shard_map site bound
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                import functools
                import jax
                from jax.sharding import PartitionSpec as P
                from jax import shard_map

                def helper(v, axis_name):
                    n = jax.lax.psum(1, axis_name)
                    return v * n

                def build(mesh, ax):
                    fn = functools.partial(helper, axis_name=ax)

                    def body(v):
                        return fn(v)

                    return shard_map(
                        body, mesh=mesh,
                        in_specs=(P(ax, None),), out_specs=P(ax, None),
                    )
                """
            },
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "mesh-axes",
            {
                "mod.py": _MESH_DECL + """
                from jax.sharding import PartitionSpec as P

                def pspecs():
                    return P(None, "modle")  # docqa-lint: disable=mesh-axes
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


class TestDonation:
    def test_donated_then_read_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "donation",
            {
                "mod.py": """
                import jax

                def step(state, batch):
                    return state

                def train(state, batch):
                    fn = jax.jit(step, donate_argnums=(0,))
                    new_state = fn(state, batch)
                    return state.loss, new_state
                """
            },
        )
        assert len(findings) == 1
        assert "'state' read after being donated" in findings[0].message
        assert findings[0].symbol == "train"

    def test_rebind_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "donation",
            {
                "mod.py": """
                import jax

                def step(state, batch):
                    return state

                def train(state, batches):
                    fn = jax.jit(step, donate_argnums=(0,))
                    for batch in batches:
                        state = fn(state, batch)
                    return state
                """
            },
        )
        assert findings == []

    def test_attribute_donation_across_methods(self, tmp_path):
        # the VectorStore._append_jit / ContinuousBatcher._decode_fn shape:
        # jit assigned to a self attribute in one method, called in another
        findings = run_fixture(
            tmp_path,
            "donation",
            {
                "mod.py": """
                import jax

                def _append(buf, rows, off):
                    return buf

                class Store:
                    def __init__(self):
                        self._append_jit = jax.jit(
                            _append, donate_argnums=(0,)
                        )

                    def add_bad(self, rows, off):
                        out = self._append_jit(self._dev, rows, off)
                        return self._dev.shape, out

                    def add_good(self, rows, off):
                        self._dev = self._append_jit(self._dev, rows, off)
                        return self._dev.shape
                """
            },
        )
        assert len(findings) == 1
        assert findings[0].symbol == "Store.add_bad"
        assert "'self._dev'" in findings[0].message

    def test_donate_argnames_kwarg(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "donation",
            {
                "mod.py": """
                import jax

                def step(params, cache):
                    return cache

                def drive(params, cache):
                    fn = jax.jit(step, donate_argnames=("cache",))
                    out = fn(params, cache=cache)
                    return cache[0], out
                """
            },
        )
        assert len(findings) == 1
        assert "'cache'" in findings[0].message

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "donation",
            {
                "mod.py": """
                import jax

                def step(state, batch):
                    return state

                def train(state, batch):
                    fn = jax.jit(step, donate_argnums=(0,))
                    new_state = fn(state, batch)
                    return state.loss, new_state  # docqa-lint: disable=donation
                """
            },
        )
        assert findings == []


# ---------------------------------------------------------------------------
# spec-shape
# ---------------------------------------------------------------------------


class TestSpecShape:
    def test_arity_mismatch_detected(self, tmp_path):
        # schema and specs in DIFFERENT modules, like decoder.py/sharding.py
        findings = run_fixture(
            tmp_path,
            "spec-shape",
            {
                "schema.py": """
                def param_schema(cfg):
                    yield ("tok_emb", "normal", (cfg.vocab, cfg.h), cfg.h)
                    for i in range(cfg.n):
                        yield (f"l{i}_wq", "normal", (cfg.h, cfg.q), cfg.h)
                """,
                "specs.py": """
                from jax.sharding import PartitionSpec as P

                def pspecs(m):
                    return {"tok_emb": P(None, m, None)}
                """,
            },
        )
        assert len(findings) == 1
        assert "'tok_emb' has 3 entries but the array is rank 2" in (
            findings[0].message
        )
        assert findings[0].path == "specs.py"

    def test_matching_arity_clean(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "spec-shape",
            {
                "mod.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P

                def cache(cfg, b):
                    shape = (b, cfg.s, cfg.kv, cfg.d)
                    out = {}
                    for i in range(cfg.n):
                        out[f"k{i}"] = jnp.zeros(shape, jnp.float32)
                    return out

                def cache_specs(mesh):
                    out = {}
                    spec = P(mesh.data_axis, None, mesh.model_axis, None)
                    for i in range(4):
                        out[f"k{i}"] = spec
                    return out
                """
            },
        )
        assert findings == []

    def test_subscript_spec_mismatch_detected(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "spec-shape",
            {
                "mod.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P

                def cache(cfg, b):
                    shape = (b, cfg.s, cfg.kv, cfg.d)
                    out = {}
                    for i in range(cfg.n):
                        out[f"k{i}"] = jnp.zeros(shape, jnp.float32)
                    return out

                def cache_specs(mesh):
                    out = {}
                    spec = P(mesh.data_axis, None)
                    for i in range(4):
                        out[f"k{i}"] = spec
                    return out
                """
            },
        )
        assert len(findings) == 1
        assert "'k{}' has 2 entries but the array is rank 4" in (
            findings[0].message
        )

    def test_replicated_spec_matches_any_rank(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "spec-shape",
            {
                "mod.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P

                def arrays(b):
                    return {"x": jnp.zeros((b, 4, 4), jnp.float32)}

                def specs():
                    return {"x": P()}
                """
            },
        )
        assert findings == []

    def test_ambiguous_rank_never_guesses(self, tmp_path):
        # two conflicting shape declarations for one name: silent
        findings = run_fixture(
            tmp_path,
            "spec-shape",
            {
                "mod.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P

                def a(b):
                    return {"x": jnp.zeros((b, 4), jnp.float32)}

                def c(b):
                    return {"x": jnp.zeros((b, 4, 4), jnp.float32)}

                def specs(m):
                    return {"x": P(None, m)}
                """
            },
        )
        assert findings == []

    def test_suppression(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            "spec-shape",
            {
                "schema.py": """
                def param_schema(cfg):
                    yield ("tok_emb", "normal", (cfg.vocab, cfg.h), cfg.h)
                """,
                "specs.py": """
                from jax.sharding import PartitionSpec as P

                def pspecs(m):
                    return {"tok_emb": P(None, m, None)}  # docqa-lint: disable=spec-shape
                """,
            },
        )
        assert findings == []
