"""TPU decode engine: prefill + on-device sampling loop with KV cache.

Replaces the reference's external Ollama round-trip (``llm-qa/main.py:66-69``,
SURVEY §3.2 "the real hot loop, external").  Everything after tokenization is
one jit program per (prompt-bucket, max-new) pair:

  prefill (batched matmuls over the prompt bucket)
    → ``lax.while_loop`` decode: forward(1 token) → sample → append to cache
    → early exit when every lane has emitted EOS

No host↔device round trip per token (SURVEY §7 hard part (b)).  Batched
lanes carry independent lengths, so requests of different sizes share one
program — the slot-based precursor to continuous batching.

TP: params/cache shardings from ``parallel/sharding.py``; GSPMD inserts the
ICI collectives.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.models.decoder import (
    KVCache,
    Params,
    block_serving,
    decoder_forward,
    init_decoder_params,
    init_kv_cache,
    kernel_forms,
    ragged_prefill_counts,
)
from docqa_tpu.engines.spine import spine_run
from docqa_tpu.ops.sampling import sample
from docqa_tpu.parallel.sharding import cache_pspecs, shard_decoder_params
from docqa_tpu.runtime.mesh import MeshContext
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span
from docqa_tpu.text.tokenizer import Tokenizer, default_tokenizer
from docqa_tpu.utils import pick_bucket, round_up

log = get_logger("docqa.generate")

BATCH_BUCKETS = (1, 2, 4, 8, 16)

# Named chat-template aliases (cfg.chat_template).  Kept to formats that
# are plain text in the target vocabularies; a checkpoint with a bespoke
# format passes the format string itself.
CHAT_TEMPLATES = {
    "mistral-inst": "[INST] {prompt} [/INST]",
}


def draft_tokens(table, cur, K):
    """Chained bigram drafting: K-1 draft tokens per lane from the lookup
    table (misses repeat the current token — a cheap guess).  The one
    drafting implementation: the solo speculative loop AND the batcher's
    paged speculative chunk both call this, so their draft streams can
    never diverge."""
    lane = jnp.arange(cur.shape[0])

    def draft_step(tok, _):
        nt = table[lane, tok]
        nt = jnp.where(nt < 0, tok, nt)
        return nt, nt

    _, drafts_t = jax.lax.scan(draft_step, cur, None, length=K - 1)
    return jnp.swapaxes(drafts_t, 0, 1)  # [b, K-1]


def accept_drafts(logits, drafts, eos_id):
    """Verify-step acceptance math shared by every speculative path:
    greedy targets ``g`` [b, K] from the verify logits, accepted-draft
    count ``m``, the emission-candidate mask (g0..gm), EOS hits among
    candidates, and the first-EOS position (K = none).  Every emitted
    token is an argmax of the model's own logits — acceptance only
    decides how many argmaxes one weight read yields."""
    K = logits.shape[1]
    karange = jnp.arange(K)[None, :]
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [b, K]
    match = (drafts == g[:, :-1]).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # accepted drafts
    cand = karange <= m[:, None]  # emission candidates g0..gm
    is_eos = (g == eos_id) & cand
    eos_pos = jnp.where(jnp.any(is_eos, 1), jnp.argmax(is_eos, 1), K)
    return g, m, cand, is_eos, eos_pos


class GenerateEngine:
    def __init__(
        self,
        cfg: DecoderConfig,
        gen: Optional[GenerateConfig] = None,
        mesh: Optional[MeshContext] = None,
        params: Optional[Params] = None,
        tokenizer: Optional[Tokenizer] = None,
        seed: int = 0,
        use_flash: Optional[bool] = None,
        param_dtype=None,
    ):
        """``param_dtype``: storage dtype for the weights.  Defaults to
        ``cfg.dtype`` (bf16 for serving configs) — decode is HBM-bandwidth
        bound, and storing f32 masters in an inference-only engine doubles
        the bytes read per token (measured ~2x tok/s on v5e from this alone).
        Pass float32 explicitly to share a training master copy."""
        self.cfg = cfg
        self.gen = gen or GenerateConfig()
        self.mesh = mesh
        self.tokenizer = tokenizer or default_tokenizer(
            cfg.vocab_size, vocab_path=cfg.tokenizer_path
        )
        # a real vocabulary (tokenizer.json / .model) carries the
        # checkpoint's own special ids — the decode loop must stop on THAT
        # eos, not the hash-fallback default of 2.  Only the DEFAULT ids
        # are replaced: a caller who set a custom eos_id (e.g. a structured
        # -output stop token) keeps it.
        tok_eos = getattr(self.tokenizer, "eos_id", None)
        tok_pad = getattr(self.tokenizer, "pad_id", None)
        if (tokenizer is not None or cfg.tokenizer_path) and tok_eos is not None:
            import dataclasses as _dc

            defaults = GenerateConfig()
            updates = {}
            if self.gen.eos_id == defaults.eos_id and tok_eos != self.gen.eos_id:
                updates["eos_id"] = int(tok_eos)
            if (
                self.gen.pad_id == defaults.pad_id
                and tok_pad is not None
                and tok_pad != self.gen.pad_id
            ):
                updates["pad_id"] = int(tok_pad)
            if updates:
                self.gen = _dc.replace(self.gen, **updates)
        # resolve + VALIDATE the chat template at construction: an unknown
        # alias (typo) or a format string without {prompt} would otherwise
        # silently replace every request with the template text itself
        if cfg.chat_template:
            resolved = CHAT_TEMPLATES.get(
                cfg.chat_template, cfg.chat_template
            )
            if "{prompt}" not in resolved:
                raise ValueError(
                    f"chat_template {cfg.chat_template!r} is neither a "
                    f"known alias ({sorted(CHAT_TEMPLATES)}) nor a format "
                    "string containing '{prompt}'"
                )
            self._chat_template: Optional[str] = resolved
        else:
            self._chat_template = None
        # what the block kind asks of its surroundings (models/serving.py);
        # a configuration it cannot run is refused here, by field, before
        # a tree is drawn for it
        self.block = block_serving(cfg)
        if params is None:
            if cfg.quantize_weights:
                from docqa_tpu.models.quant import (
                    init_quantized_decoder_params,
                )

                params = init_quantized_decoder_params(
                    jax.random.PRNGKey(seed),
                    cfg,
                    host_init=True,
                    bits=cfg.quant_bits,
                    host_seed=seed,
                    mesh=mesh,
                )
            else:
                # host_init: draw on host + device_put per tensor, each
                # straight into its mesh sharding (decoder.param_putter)
                params = init_decoder_params(
                    jax.random.PRNGKey(seed),
                    cfg,
                    param_dtype=param_dtype or jnp.dtype(cfg.dtype),
                    host_init=True,
                    host_seed=seed,
                    mesh=mesh,
                )
        else:
            from docqa_tpu.models.quant import (
                SCALE_SUFFIX,
                is_quantized,
                quantize_decoder_params,
            )

            if cfg.quantize_weights and not is_quantized(params):
                # honor the knob for SUPPLIED weights too (the path real
                # HF checkpoints take) — requires the float tree to fit
                # transiently; the tensor-by-tensor init path covers
                # random-init at scales where it doesn't
                params = quantize_decoder_params(params, bits=cfg.quant_bits)
            if param_dtype is not None:
                # never cast quantized weights or their scales
                params = {
                    k: v
                    if v.dtype in (jnp.int8, jnp.int4)
                    or k.endswith(SCALE_SUFFIX)
                    else v.astype(param_dtype)
                    for k, v in params.items()
                }
        if mesh is not None:
            params = shard_decoder_params(params, cfg, mesh)
        self.params = params
        if use_flash is None:
            use_flash = jax.default_backend() == "tpu" and cfg.head_dim % 64 == 0
        # THE observation every choice of kernel goes by — a TPU whose
        # kernels read this head width, or what the caller said:
        # ``kernel_forms(block_size=)`` is the Pallas forms
        # (``models/serving.KernelForms``) the paged forwards over pools of
        # such pages run for this engine; the batcher hands them to its
        # programs and counts by them
        self.kernel_forms = functools.partial(
            kernel_forms, cfg, on_tpu=bool(use_flash), mesh=mesh)
        # what a round's cold dispatches count where ``ragged`` is chosen
        self.ragged_prefill_counts = functools.partial(
            ragged_prefill_counts, cfg)
        # kept only by a kind one of whose kernels the flag reaches (the
        # warm-up checks kernels against their references by it)
        self.use_flash = bool(use_flash) and self.block.uses_flash
        self._fns = {}

    # ---- device program ------------------------------------------------------

    def _constrain_cache(self, cache: KVCache) -> KVCache:
        if self.mesh is None or self.mesh.n_devices == 1:
            return cache
        from jax.sharding import NamedSharding

        specs = cache_pspecs(self.cfg, self.mesh)
        return {
            k: jax.lax.with_sharding_constraint(
                v, NamedSharding(self.mesh.mesh, specs[k])
            )
            for k, v in cache.items()
        }

    def _generate_fn(
        self,
        params: Params,
        ids: jax.Array,  # [b, prompt_bucket]
        prompt_lengths: jax.Array,  # [b]
        rng: jax.Array,
        temperature: jax.Array,  # traced scalar; greedy handled statically
        *,
        max_new: int,
        greedy: bool,
    ):
        temperature = 0.0 if greedy else temperature
        b, bucket = ids.shape
        cache_len = round_up(bucket + max_new, 128)
        cache = init_kv_cache(self.cfg, b, max_len=cache_len)
        cache = self._constrain_cache(cache)

        # ---- prefill: whole (padded) prompt in one pass; padded tail rows
        # are masked out via attn_lengths=prompt_lengths
        logits, cache = decoder_forward(
            params,
            self.cfg,
            ids,
            cache,
            jnp.zeros((b,), jnp.int32),
            attn_lengths=prompt_lengths,
            use_flash=self.use_flash, mesh=self.mesh,
            last_token_only=True,
        )
        last = logits[:, -1]
        first_tok = sample(last, rng, temperature, self.gen.top_k, self.gen.top_p)

        out = jnp.full((b, max_new), self.gen.pad_id, jnp.int32)
        out = out.at[:, 0].set(first_tok)
        done = first_tok == self.gen.eos_id
        # tokens actually produced per lane (EOS excluded) — the host trims
        # by this count, so a legitimately *sampled* pad_id token mid-stream
        # is preserved
        n_emitted = jnp.where(done, 0, 1).astype(jnp.int32)

        def cond(state):
            step, _, _, _, done, _, _ = state
            return jnp.logical_and(step < max_new, ~jnp.all(done))

        def body(state):
            step, cache, lengths, out, done, n_emitted, rng = state
            tok = out[:, step - 1]
            logits, cache = decoder_forward(
                params,
                self.cfg,
                tok[:, None],
                cache,
                lengths,
                use_flash=self.use_flash, mesh=self.mesh,
            )
            rng, sub = jax.random.split(rng)
            nxt = sample(
                logits[:, 0], sub, temperature, self.gen.top_k, self.gen.top_p
            )
            nxt = jnp.where(done, self.gen.pad_id, nxt)
            out = out.at[:, step].set(nxt)
            is_eos = nxt == self.gen.eos_id
            n_emitted = n_emitted + jnp.where(done | is_eos, 0, 1)
            done = done | is_eos
            return step + 1, cache, lengths + 1, out, done, n_emitted, rng

        state = (jnp.int32(1), cache, prompt_lengths, out, done, n_emitted, rng)
        _, _, _, out, _, n_emitted, _ = jax.lax.while_loop(cond, body, state)
        return out, n_emitted

    # ---- speculative decoding (prompt-lookup / self-lookup drafting) --------

    def _build_bigram(self, ids, lengths):
        """Per-lane bigram table over the prompt: table[lane, prev] = next.
        Misses are -1.  The drafting source for prompt-lookup speculative
        decoding — RAG answers quote retrieved context, so the prompt's own
        bigrams predict long runs of the continuation."""
        b, s = ids.shape
        vocab = self.cfg.vocab_size
        prev = ids[:, :-1]
        nxt = ids[:, 1:]
        valid = (jnp.arange(s - 1)[None, :] + 1) < lengths[:, None]
        prev = jnp.where(valid, prev, vocab)  # out of bounds -> dropped
        lane = jnp.broadcast_to(jnp.arange(b)[:, None], prev.shape)
        table = jnp.full((b, vocab), -1, jnp.int32)
        return table.at[lane, prev].set(nxt, mode="drop")

    def spec_verify_step(self, params, cache, table, cur, lengths, *, K):
        """The draft → verify → accept core shared by the solo speculative
        loop and the batcher's speculative chunk program (the two MUST stay
        output-exact; sharing the subtle part keeps them from diverging —
        the batcher's PAGED variant composes the same :func:`draft_tokens`
        / :func:`accept_drafts` halves around its block-pool forward).

        Drafts K-1 tokens per lane by chained bigram lookup, verifies them
        in one forward of q_len=K, and returns
        ``(cache, g, m, cand, is_eos, eos_pos)``: greedy targets [b, K],
        accepted-draft count [b], emission-candidate mask (g0..gm), EOS
        hits among candidates, and the first-EOS position (K = none).
        Callers apply their own emission masking (budget / live slots) and
        state updates."""
        drafts = draft_tokens(table, cur, K)
        verify_in = jnp.concatenate([cur[:, None], drafts], axis=1)
        logits, cache = decoder_forward(
            params, self.cfg, verify_in, cache, lengths,
            attn_lengths=lengths + K, use_flash=self.use_flash,
            mesh=self.mesh,
        )
        g, m, cand, is_eos, eos_pos = accept_drafts(
            logits, drafts, self.gen.eos_id
        )
        return cache, g, m, cand, is_eos, eos_pos

    def confirm_bigrams(self, table, cur, g, emit_valid):
        """Record confirmed bigrams (cur, g0), (g0, g1), ... in the lookup
        table so the answer's own phrases become draftable (self-lookup)."""
        b, K = g.shape
        lane = jnp.arange(b)
        prev_seq = jnp.concatenate([cur[:, None], g[:, :-1]], axis=1)
        prev_scatter = jnp.where(emit_valid, prev_seq, self.cfg.vocab_size)
        return table.at[
            jnp.broadcast_to(lane[:, None], prev_scatter.shape),
            prev_scatter,
        ].set(g, mode="drop")

    def _generate_spec_fn(
        self,
        params: Params,
        ids: jax.Array,  # [b, prompt_bucket]
        prompt_lengths: jax.Array,  # [b]
        *,
        max_new: int,
        K: int,
    ):
        """Greedy decode with prompt-lookup speculation: each loop step
        drafts K-1 tokens by chained bigram lookup, verifies all of them in
        ONE forward of q_len=K, and emits the matched prefix plus the bonus
        token — so a step costs one weight read (the same as emitting a
        single token, decode being HBM-bound) but can emit up to K tokens.

        Output-exact with plain greedy by construction: every emitted token
        is an argmax of the model's own logits; drafts only decide how many
        of those argmaxes one weight-read yields.  Mis-speculated K/V rows
        are never attended (``attn_lengths`` windows the freshly-written
        region) and are overwritten by the next verify, which always starts
        at or before them.
        """
        b, bucket = ids.shape
        eos, pad = self.gen.eos_id, self.gen.pad_id
        cache_len = round_up(bucket + max_new + K, 128)
        cache = init_kv_cache(self.cfg, b, max_len=cache_len)
        cache = self._constrain_cache(cache)
        lane = jnp.arange(b)
        karange = jnp.arange(K)[None, :]

        logits, cache = decoder_forward(
            params, self.cfg, ids, cache, jnp.zeros((b,), jnp.int32),
            attn_lengths=prompt_lengths, use_flash=self.use_flash,
            mesh=self.mesh, last_token_only=True,
        )
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        table = self._build_bigram(ids, prompt_lengths)
        # the (last prompt token -> first) pair is confirmed; record it
        last_prompt = jnp.take_along_axis(
            ids, jnp.maximum(prompt_lengths - 1, 0)[:, None], 1
        )[:, 0]
        table = table.at[lane, last_prompt].set(first)

        out = jnp.full((b, max_new + K), pad, jnp.int32)
        out = out.at[:, 0].set(first)
        done = first == eos
        n_emit = jnp.where(done, 0, 1).astype(jnp.int32)
        done = done | (n_emit >= max_new)
        cur = first

        def cond(state):
            return ~jnp.all(state[4])

        def body(state):
            cache, lengths, out, n_emit, done, table, cur = state
            cache, g, m, cand, is_eos, eos_pos = self.spec_verify_step(
                params, cache, table, cur, lengths, K=K
            )
            budget = max_new - n_emit
            emit_valid = (
                cand
                & (karange < eos_pos[:, None])
                & (karange < budget[:, None])
                & (~done)[:, None]
            )
            emitted = jnp.where(emit_valid, g, pad)
            out = jax.vmap(
                lambda o, v, off: jax.lax.dynamic_update_slice(o, v, (off,))
            )(out, emitted, n_emit)
            n_valid = jnp.sum(emit_valid.astype(jnp.int32), axis=1)
            n_emit_new = n_emit + n_valid
            done_new = (
                done
                | (jnp.any(is_eos, 1) & (eos_pos < budget))
                | (n_emit_new >= max_new)
            )
            last_tok = jnp.take_along_axis(
                emitted, jnp.maximum(n_valid - 1, 0)[:, None], 1
            )[:, 0]
            cur_new = jnp.where(done_new | (n_valid == 0), cur, last_tok)
            lengths_new = jnp.where(done, lengths, lengths + n_valid)
            table = self.confirm_bigrams(table, cur, g, emit_valid)
            return cache, lengths_new, out, n_emit_new, done_new, table, cur_new

        state = (cache, prompt_lengths, out, n_emit, done, table, cur)
        _, _, out, n_emit, _, _, _ = jax.lax.while_loop(cond, body, state)
        return out, n_emit

    def _get_fn(self, b: int, bucket: int, max_new: int, greedy: bool):
        if self.block.solo is not None:
            raise NotImplementedError(
                "the solo dense-cache engine " + self.block.solo)
        spec_k = self.gen.speculative_k
        if greedy and spec_k >= 2:
            key = (b, bucket, max_new, "spec", spec_k)
            fn = self._fns.get(key)
            if fn is None:
                spec = functools.partial(
                    self._generate_spec_fn, max_new=max_new, K=spec_k
                )
                # same call signature as _generate_fn (rng/temperature
                # ignored: speculation is greedy-only)
                fn = jax.jit(
                    lambda params, ids, lengths, rng, temperature: spec(
                        params, ids, lengths
                    )
                )
                self._fns[key] = fn
            return fn
        key = (b, bucket, max_new, greedy)
        fn = self._fns.get(key)
        if fn is None:
            fn = jax.jit(
                functools.partial(
                    self._generate_fn, max_new=max_new, greedy=greedy
                )
            )
            self._fns[key] = fn
        return fn

    def decode_memory_analysis(
        self,
        prompt_len: int = 3,
        batch: int = 1,
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
    ):
        """AOT ``memory_analysis()`` of the decode program serving the
        given request shape: lower+compile against abstract token inputs
        (the real param arrays ride along, so ``argument_bytes`` is the
        true HBM-resident working set) and return the backend's byte
        accounting, or None when it provides none.

        Shared by the compile audit (``analysis/compile_audit.py`` gates
        per-root ``peak_bytes`` against ``compile_budget.json``) and the
        telemetry sampler's HBM working-set probe — one measurement
        path (``utils.compiled_memory_stats``), no drift."""
        from docqa_tpu.utils import compiled_memory_stats

        max_new = (
            self.gen.max_new_tokens
            if max_new_tokens is None
            else max_new_tokens
        )
        temperature = (
            self.gen.temperature if temperature is None else temperature
        )
        usable = self.cfg.max_seq_len - max_new
        bucket = min(
            pick_bucket(prompt_len, self.gen.prefill_buckets)
            if prompt_len <= self.gen.prefill_buckets[-1]
            else round_up(prompt_len, 128),
            usable,
        )
        b_pad = (
            pick_bucket(batch, BATCH_BUCKETS)
            if batch <= BATCH_BUCKETS[-1]
            else batch
        )
        if self.mesh is not None:
            b_pad = round_up(b_pad, self.mesh.n_data)
        fn = self._get_fn(b_pad, bucket, max_new, greedy=temperature == 0.0)

        def _probe_on_lane():
            """AOT lower+compile as a BACKGROUND spine item: the probe's
            compile must queue behind serving work, never become another
            concurrent client stream (the telemetry sampler fires this
            every hbm_refresh_s)."""
            from docqa_tpu.obs.observatory import DEFAULT_OBSERVATORY

            compiled = fn.lower(
                self.params,
                jax.ShapeDtypeStruct((b_pad, bucket), jnp.int32),
                jax.ShapeDtypeStruct((b_pad,), jnp.int32),
                jax.random.PRNGKey(0),
                jnp.float32(temperature),
            ).compile()
            # the compiled program is in hand: register its cost model
            # so the solo `generate` stage reports MFU too
            key = (b_pad, bucket, max_new, temperature == 0.0)
            DEFAULT_OBSERVATORY.annotate_lowered("generate", compiled, key=key)
            stats = compiled_memory_stats(compiled)
            cost = DEFAULT_OBSERVATORY.cost_of("generate", key)
            if stats is not None and cost is not None:
                # cost columns ride the same probe (compile_audit rows
                # then carry flops next to bytes)
                stats = dict(stats)
                stats["flops"] = cost["flops"]
                stats["bytes_accessed"] = cost["bytes"]
            return stats

        try:
            return spine_run("hbm_probe", _probe_on_lane, stream="probe")
        except Exception:
            # a lowering failure must not take the audit caller
            # down, but it must be VISIBLE — a silent None here would
            # quietly reintroduce the unmeasured-HBM state
            log.exception("decode AOT memory analysis failed")
            return None

    def kernel_selfcheck(self) -> dict:
        """The Pallas kernels against their XLA references ON THE ATTACHED
        DEVICE, at the decode shapes the engines dispatch (q_len 1 and the
        speculative verify width) with this model's head geometry, window
        and mesh — seeded small inputs, lengths and offsets included:

        * ``flash_attention`` (the dense cache of the solo engine)
          against ``attention_reference``;
        * ``paged_flash_decode`` (what the batcher's decode program
          serves) through a SCATTERED block table — ragged lengths, a
          page boundary, a free lane, hole entries past each length —
          against the gather reference, when the kernel reads this
          geometry (``kernel_forms``'s ``paged``);
        * ``ragged_flash_prefill`` (what the batcher's cold prefill
          program attends with) on a packed axis of two segments — one
          longer than a 2,048-row window, one that ends inside a block —
          and a tail of padding, against the XLA form, when it is chosen
          (``kernel_forms``'s ``ragged``).

        Raises when a pair disagrees: a kernel that compiles but computes
        something else must fail the warm-up, not serve.  Tolerance: both
        sides round an O(1) output to bf16 (ulp 2^-7 below 2.0), so two
        roundings apart is the most an agreeing pair can differ."""
        from docqa_tpu.ops.attention import (
            attention_reference,
            flash_attention,
            paged_decode_attention,
            ragged_prefill_attention,
        )

        cfg, tol = self.cfg, 2.0 ** -6
        b = self.mesh.n_data if self.mesh is not None else 1
        skv, dtype = 384, jnp.dtype(cfg.dtype)
        kw = dict(causal=True, sliding_window=cfg.sliding_window)
        kernel = jax.jit(
            functools.partial(flash_attention, mesh=self.mesh, **kw)
        )
        q_lens = sorted({1, max(self.gen.speculative_k, 1)})

        # the paged pair: 4 lanes per data shard over a 64-page pool, 16
        # table entries a lane; lengths end inside a page, on a page
        # boundary, at 0 (free lane: all holes) and at the table's span
        block_size, n_pages, per_lane = self.gen.kv_block_size, 64, 16
        forms = self.kernel_forms(block_size=block_size)
        paged, ragged = forms.paged, forms.ragged
        lane_lens = np.tile(
            np.array([block_size * 5 - 3, block_size * 4, 0,
                      block_size * per_lane], np.int32), b,
        )
        paged_kw = dict(
            block_size=block_size, sliding_window=cfg.sliding_window,
        )
        paged_kernel = jax.jit(functools.partial(
            paged_decode_attention, use_flash=True, mesh=self.mesh, **paged_kw
        ))
        ragged_reference = jax.jit(functools.partial(
            ragged_prefill_attention, sliding_window=cfg.sliding_window))
        ragged_kernel = jax.jit(functools.partial(
            ragged_prefill_attention, sliding_window=cfg.sliding_window,
            use_flash=True))

        def draw(rng, *shape):
            return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

        def err_of(got, want) -> float:
            return float(jnp.max(jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32)
            )))

        def dense_err(rng, sq: int) -> float:
            q = draw(rng, b, sq, cfg.num_heads, cfg.head_dim)
            k = draw(rng, b, skv, cfg.num_kv_heads, cfg.head_dim)
            v = draw(rng, b, skv, cfg.num_kv_heads, cfg.head_dim)
            lengths = jnp.full((b,), skv - 83, jnp.int32)
            got = kernel(q, k, v, lengths=lengths, q_offset=lengths - sq)
            with jax.default_matmul_precision("highest"):
                want = attention_reference(
                    q, k, v, lengths=lengths, q_offset=lengths - sq, **kw
                )
            return err_of(got, want)

        def paged_err(rng, sq: int) -> float:
            # pages handed out in a shuffled order, so every lane's table
            # is scattered and out of order; the rest are holes
            tables = np.full((len(lane_lens), per_lane), n_pages, np.int32)
            pages = iter(rng.permutation(n_pages))
            for lane, n in enumerate(lane_lens):
                for i in range(-(-int(n) // block_size)):
                    tables[lane, i] = next(pages)
            pool = (n_pages * block_size, cfg.num_kv_heads, cfg.head_dim)
            args = (
                draw(rng, len(lane_lens), sq, cfg.num_heads, cfg.head_dim),
                draw(rng, *pool), draw(rng, *pool),
                jnp.asarray(tables), jnp.asarray(lane_lens),
            )
            q_offset = jnp.maximum(jnp.asarray(lane_lens) - sq, 0)
            got = paged_kernel(*args, q_offset=q_offset)
            with jax.default_matmul_precision("highest"):
                want = paged_decode_attention(
                    *args, q_offset=q_offset, **paged_kw
                )
            return err_of(got, want)

        def ragged_err(rng) -> float:
            # segments start on aligned rows; the last 128 rows are padding
            t, seg_lens = 2560, (2118, 130)
            seg, pos, row = np.full(t, -1, np.int32), np.zeros(t, np.int32), 0
            for lane, n in enumerate(seg_lens):
                seg[row: row + n], pos[row: row + n] = lane, np.arange(n)
                row += round_up(n, 128)
            args = (
                draw(rng, t, cfg.num_heads, cfg.head_dim),
                draw(rng, t, cfg.num_kv_heads, cfg.head_dim),
                draw(rng, t, cfg.num_kv_heads, cfg.head_dim),
                jnp.asarray(seg), jnp.asarray(pos),
            )
            got = ragged_kernel(*args)
            with jax.default_matmul_precision("highest"):
                want = ragged_reference(*args)
            return err_of(got, want)

        def _check_on_lane() -> Tuple[float, float, float]:
            rng = np.random.default_rng(0)
            return (
                max(dense_err(rng, sq) for sq in q_lens),
                max(paged_err(rng, sq) for sq in q_lens) if paged else 0.0,
                ragged_err(rng) if ragged else 0.0,
            )

        err, err_paged, err_ragged = spine_run(
            "kernel_check", _check_on_lane, stream="probe"
        )
        report = {"max_abs_err": err, "tolerance": tol, "q_lens": q_lens}
        if paged:
            report["paged_max_abs_err"] = err_paged
        if ragged:
            report["ragged_max_abs_err"] = err_ragged
        # NaN fails too
        if not (err <= tol and err_paged <= tol and err_ragged <= tol):
            raise AssertionError(
                f"a Pallas kernel disagrees with its reference: {report}"
            )
        return report

    # ---- host API ------------------------------------------------------------

    def generate_ids(
        self,
        prompts_ids: Sequence[Sequence[int]],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        seed: int = 0,
    ) -> List[List[int]]:
        """Token-id prompts -> generated token ids (EOS excluded)."""
        max_new = (
            self.gen.max_new_tokens if max_new_tokens is None else max_new_tokens
        )
        temperature = (
            self.gen.temperature if temperature is None else temperature
        )
        b = len(prompts_ids)
        if b == 0 or max_new == 0:
            return [[] for _ in prompts_ids]
        usable = self.cfg.max_seq_len - max_new
        if usable < 1:
            raise ValueError(
                f"max_new_tokens={max_new} leaves no prompt room within "
                f"max_seq_len={self.cfg.max_seq_len}"
            )
        longest = max(len(p) for p in prompts_ids)
        bucket = min(
            pick_bucket(longest, self.gen.prefill_buckets)
            if longest <= self.gen.prefill_buckets[-1]
            else round_up(longest, 128),
            usable,
        )
        # pad the batch to a bucket (stable jit cache) and to a multiple of
        # the data axis (sharding divisibility); dummy lanes get length-1
        # prompts and their outputs are dropped
        b_pad = pick_bucket(b, BATCH_BUCKETS) if b <= BATCH_BUCKETS[-1] else b
        if self.mesh is not None:
            b_pad = round_up(b_pad, self.mesh.n_data)
        ids = np.full((b_pad, bucket), self.gen.pad_id, np.int32)
        lengths = np.ones((b_pad,), np.int32)
        for i, p in enumerate(prompts_ids):
            p = list(p)[-bucket:]  # keep the tail on overflow
            ids[i, : len(p)] = p
            lengths[i] = max(len(p), 1)

        fn = self._get_fn(b_pad, bucket, max_new, greedy=temperature == 0.0)

        def _generate_on_lane():
            """Device phase (spine work item): upload, dispatch, and the
            one fetch — solo generate has no pipeline to overlap, so
            dispatch+fetch ride one item and its duration is the
            program's device time."""
            o, n = fn(
                self.params,
                jnp.asarray(ids),
                jnp.asarray(lengths),
                jax.random.PRNGKey(seed),
                jnp.float32(temperature),
            )
            return np.asarray(o)[:b], np.asarray(n)[:b]

        with span("generate", DEFAULT_REGISTRY):
            out, n_emitted = spine_run(
                "generate", _generate_on_lane,
                cost_key=(b_pad, bucket, max_new, temperature == 0.0),
            )

        return [
            [int(t) for t in row[:count]]
            for row, count in zip(out, n_emitted)
        ]

    def format_prompt(self, prompt: str) -> str:
        """Apply the configured instruction template (``cfg.chat_template``)
        to a text prompt.  The reference's Ollama runtime did this
        internally for Mistral (``llm-qa/main.py:66-69``); serving a real
        instruct checkpoint without its format silently degrades answers.
        ``str.replace`` (not ``str.format``) so braces in clinical text
        can never raise."""
        if self._chat_template is None:
            return prompt
        return self._chat_template.replace("{prompt}", prompt)

    def encode_prompt(self, prompt: str, budget: int) -> List[int]:
        """Tokenize with the chat template applied, TRUNCATION-SAFE.

        Naive wrap-then-tail-truncate would cut the template's opening
        tokens ('[INST]') off a long RAG prompt while keeping the closing
        ones — malformed instruct input in exactly the long-context case
        the template exists for.  Here the RAW prompt is tail-trimmed
        (the question sits at the tail of a RAG prompt) to what the
        budget leaves after the template's own tokens, then wrapped."""
        if self._chat_template is None:
            return self.tokenizer.encode(prompt)
        pre, _, post = self._chat_template.partition("{prompt}")
        pre_ids = list(self.tokenizer.encode(pre))  # carries BOS etc.
        post_ids = (
            list(self.tokenizer.encode(post, add_specials=False))
            if post
            else []
        )
        room = max(1, budget - len(pre_ids) - len(post_ids))
        raw = list(self.tokenizer.encode(prompt, add_specials=False))[-room:]
        return pre_ids + raw + post_ids

    def generate_texts(
        self,
        prompts: Sequence[str],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        seed: int = 0,
    ) -> List[str]:
        """Text prompts -> generated text.

        With real model weights + vocab this is normal detokenization; with
        the hash-fallback tokenizer (zero-egress environment) ids map to
        opaque ``w<id>`` wordpieces — the service contract and the device
        program are identical either way.
        """
        # untemplated prompts: generate_ids keeps the prompt *tail* (where
        # the question sits in a RAG prompt) when it exceeds the bucket;
        # templated prompts truncate template-aware in encode_prompt so the
        # instruct framing survives
        budget = self.gen.prefill_buckets[-1]
        prompt_ids = [self.encode_prompt(p, budget) for p in prompts]
        outs = self.generate_ids(prompt_ids, max_new_tokens, temperature, seed)
        return [self.tokenizer.decode_ids(ids) for ids in outs]
