"""Ops layer: norms, rope, attention (XLA + pallas interpret), top-k."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from docqa_tpu.ops import (
    apply_rope,
    attention,
    layer_norm,
    merge_topk,
    rms_norm,
    rope_angles,
    sharded_topk,
)
from docqa_tpu.ops.attention import attention_reference, flash_attention


def _np_softmax(x, axis=-1):
    x = x - x.max(axis=axis, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=axis, keepdims=True)


class TestNorms:
    def test_layer_norm_golden(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, 5, 16)).astype(np.float32)
        g = rng.normal(size=(16,)).astype(np.float32)
        b = rng.normal(size=(16,)).astype(np.float32)
        got = np.asarray(layer_norm(jnp.array(x), jnp.array(g), jnp.array(b)))
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        want = (x - mu) / np.sqrt(var + 1e-12) * g + b
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_rms_norm_golden(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(3, 8)).astype(np.float32)
        g = rng.normal(size=(8,)).astype(np.float32)
        got = np.asarray(rms_norm(jnp.array(x), jnp.array(g)))
        want = x / np.sqrt((x**2).mean(-1, keepdims=True) + 1e-5) * g
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_bf16_roundtrip(self):
        x = jnp.ones((4, 8), jnp.bfloat16)
        out = rms_norm(x, jnp.ones((8,)))
        assert out.dtype == jnp.bfloat16


class TestRope:
    def test_rotation_preserves_norm(self):
        cos, sin = rope_angles(8, 32)
        x = jnp.ones((1, 4, 2, 8))
        pos = jnp.arange(4)[None, :]
        y = apply_rope(x, cos, sin, pos)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(y), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1),
            rtol=1e-5,
        )

    def test_position_zero_identity(self):
        cos, sin = rope_angles(8, 32)
        x = jnp.arange(16.0).reshape(1, 1, 2, 8)
        y = apply_rope(x, cos, sin, jnp.zeros((1, 1), jnp.int32))
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)

    def test_relative_property(self):
        # <rope(q,m), rope(k,n)> depends only on m-n
        cos, sin = rope_angles(16, 64)
        rng = np.random.default_rng(2)
        q = jnp.array(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
        k = jnp.array(rng.normal(size=(1, 1, 1, 16)), jnp.float32)

        def dot_at(m, n):
            qm = apply_rope(q, cos, sin, jnp.array([[m]]))
            kn = apply_rope(k, cos, sin, jnp.array([[n]]))
            return float(jnp.sum(qm * kn))

        assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)


def _golden_attention(q, k, v, causal=False, lengths=None):
    b, sq, h, d = q.shape
    skv = k.shape[1]
    groups = h // k.shape[2]
    kk = np.repeat(k, groups, axis=2)
    vv = np.repeat(v, groups, axis=2)
    out = np.zeros_like(q)
    for bi in range(b):
        kvl = skv if lengths is None else int(lengths[bi])
        for hi in range(h):
            s = (q[bi, :, hi] @ kk[bi, :, hi].T) / np.sqrt(d)
            mask = np.zeros((sq, skv), bool)
            mask[:, :kvl] = True
            if causal:
                qpos = np.arange(sq) + kvl - sq
                mask &= np.arange(skv)[None, :] <= qpos[:, None]
            s = np.where(mask, s, -1e30)
            p = _np_softmax(s, -1)
            p = np.where(mask.any(-1, keepdims=True), p, 0.0)
            out[bi, :, hi] = p @ vv[bi, :, hi]
    return out


class TestAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("gqa", [1, 4])
    def test_reference_vs_numpy(self, causal, gqa):
        rng = np.random.default_rng(3)
        b, sq, h, d = 2, 16, 4, 8
        q = rng.normal(size=(b, sq, h, d)).astype(np.float32)
        k = rng.normal(size=(b, sq, h // gqa, d)).astype(np.float32)
        v = rng.normal(size=(b, sq, h // gqa, d)).astype(np.float32)
        lengths = np.array([16, 11], np.int32)
        got = np.asarray(
            attention_reference(
                jnp.array(q), jnp.array(k), jnp.array(v),
                causal=causal, lengths=jnp.array(lengths),
            )
        )
        want = _golden_attention(q, k, v, causal=causal, lengths=lengths)
        np.testing.assert_allclose(got, want, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_matches_reference(self, causal):
        rng = np.random.default_rng(4)
        b, sq, h, hkv, d = 2, 256, 4, 2, 64
        q = jnp.array(rng.normal(size=(b, sq, h, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(b, sq, hkv, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(b, sq, hkv, d)), jnp.float32)
        lengths = jnp.array([256, 190], jnp.int32)
        want = attention_reference(q, k, v, causal=causal, lengths=lengths)
        got = flash_attention(
            q, k, v, causal=causal, lengths=lengths,
            block_q=128, block_kv=128, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("sq", [1, 4])
    def test_flash_decode_shapes(self, sq):
        # the batcher's decode shapes: q_len 1 (plain step) and 4 (the
        # speculative verify) against a long KV prefix, with per-lane
        # lengths, the q rows' absolute offsets, and a sliding window —
        # block_q collapses to sq, the sub-tile case Mosaic must accept
        rng = np.random.default_rng(5)
        b, skv, h, hkv, d = 2, 256, 4, 2, 64
        q = jnp.array(rng.normal(size=(b, sq, h, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(b, skv, hkv, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(b, skv, hkv, d)), jnp.float32)
        lengths = jnp.array([100, 37], jnp.int32)
        kw = dict(
            causal=True, lengths=lengths, q_offset=lengths - sq,
            sliding_window=48,
        )
        want = attention_reference(q, k, v, **kw)
        got = flash_attention(
            q, k, v, block_kv=128, interpret=True, **kw
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_flash_on_a_mesh_lowers_for_tpu(self):
        """GSPMD cannot partition a Mosaic call: on a mesh the kernel must
        be shard_map-wrapped or the decode program does not lower at all.
        Cross-lowered for TPU on the virtual 1x4 mesh (no chip needed)."""
        from jax import export
        from jax.sharding import NamedSharding

        from docqa_tpu.runtime.mesh import host_cpu_mesh

        mesh = host_cpu_mesh(4)
        b, sq, skv, h, hkv, d = 2, 4, 512, 8, 4, 128
        heads = NamedSharding(mesh.mesh, P("data", None, "model", None))
        lanes = NamedSharding(mesh.mesh, P("data"))
        args = (
            jax.ShapeDtypeStruct((b, sq, h, d), jnp.bfloat16, sharding=heads),
            jax.ShapeDtypeStruct((b, skv, hkv, d), jnp.bfloat16, sharding=heads),
            jax.ShapeDtypeStruct((b, skv, hkv, d), jnp.bfloat16, sharding=heads),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=lanes),
        )

        def attend(use_mesh):
            return jax.jit(
                lambda q, k, v, n: flash_attention(
                    q, k, v, causal=True, lengths=n, q_offset=n - sq,
                    sliding_window=256, mesh=use_mesh,
                )
            )

        exported = export.export(attend(mesh), platforms=["tpu"])(*args)
        assert "tpu_custom_call" in exported.mlir_module()
        with pytest.raises(NotImplementedError, match="shard_map"):
            export.export(attend(None), platforms=["tpu"])(*args)

    def test_sliding_window(self):
        rng = np.random.default_rng(6)
        b, sq, h, d = 1, 128, 2, 64
        q = jnp.array(rng.normal(size=(b, sq, h, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(b, sq, h, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(b, sq, h, d)), jnp.float32)
        want = attention_reference(q, k, v, causal=True, sliding_window=32)
        got = flash_attention(
            q, k, v, causal=True, sliding_window=32,
            block_q=64, block_kv=64, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_dispatcher_cpu_path(self):
        q = jnp.ones((1, 8, 2, 16))
        out = attention(q, q, q, causal=True)
        assert out.shape == q.shape


class TestTopK:
    def test_merge_exact(self):
        rng = np.random.default_rng(7)
        scores = rng.normal(size=(4, 3, 5)).astype(np.float32)  # 4 shards
        gids = np.arange(20).reshape(4, 1, 5).repeat(3, axis=1)
        vals, ids = merge_topk(jnp.array(scores), jnp.array(gids), k=6)
        flat = scores.transpose(1, 0, 2).reshape(3, 20)
        want_vals = np.sort(flat, axis=-1)[:, ::-1][:, :6]
        np.testing.assert_allclose(np.asarray(vals), want_vals, atol=1e-6)

    def test_sharded_topk_matches_global(self, mesh_tp8):
        rng = np.random.default_rng(8)
        n, q, k = 64, 4, 5
        corpus_scores = rng.normal(size=(q, n)).astype(np.float32)
        n_local = n // 8

        def body(scores_shard):
            offset = jax.lax.axis_index("model") * n_local
            return sharded_topk(scores_shard, offset, k, "model")

        fn = shard_map(
            body,
            mesh=mesh_tp8.mesh,
            in_specs=P(None, "model"),
            out_specs=P(),
            check_vma=False,  # all_gather output replication isn't inferred
        )
        vals, ids = fn(jnp.array(corpus_scores))
        order = np.argsort(-corpus_scores, axis=-1)[:, :k]
        np.testing.assert_allclose(
            np.asarray(vals), np.take_along_axis(corpus_scores, order, -1),
            atol=1e-6,
        )
        np.testing.assert_array_equal(np.asarray(ids), order)
