"""ISSUE 38: the configuration ``minicpm-sala-int8``, its architecture
package ``benchmark/architectures/minicpm_sala/`` and the cell
``record_closed4_sala`` — files and entries only; nothing that was there
is edited."""

import ast
import json
import os
import random
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import arch, child, corpus  # noqa: E402  (standard library)

DATA = os.path.join(HERE, "data")
OVERLAY = os.path.join(DATA, "tiny_overlay_sala.json")
FILE = os.path.join(BENCH_DIR, "configs", "minicpm-sala-int8.json")
PACKAGE_DIR = os.path.join(BENCH_DIR, "architectures", "minicpm_sala")
CELL_NAME = "record_closed4_sala"


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
CONF = load(FILE)
SHAPES = arch.load_shapes(CONF).shapes
KEYS = arch.load_shapes(CONF).keys

# the numbers of https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "model_type": "minicpm_sala", "num_attention_heads": 32,
    "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
}
SPARSE_AT = [0, 9, 16, 17, 22, 29, 30, 31]


# ---- the file and the entries ------------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_each_published_key(key):
    assert CONF[key] == PUBLISHED[key]


def test_the_mixer_order_is_the_published_one():
    kinds = CONF["mixer_types"]
    assert len(kinds) == 32
    assert [i for i, m in enumerate(kinds) if m == "minicpm4"] == SPARSE_AT
    assert set(kinds) == {"minicpm4", "lightning-attn"}


def test_the_entry_names_the_source_and_exactly_one_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "minicpm-sala-int8")
    assert entry["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/minicpm-sala-int8.json"
    assert entry["reduced"] == ["max_position_embeddings"]
    assert CONF["max_position_embeddings"] == 9728
    # appended behind what was there; whatever comes later comes behind it
    assert [c["name"] for c in BENCH["configs"]][:3] == [
        "mistral-7b-int8", "deepseek-v2-ep4-bf16", "minicpm-sala-int8"]


def test_the_cell_is_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL_NAME)
    assert [w["name"] for w in BENCH["workloads"]][:3] == [
        "rag_closed", "rag_closed8_dsv2", CELL_NAME]
    assert cell["chips"] == 1
    assert cell["config"] == "minicpm-sala-int8"
    assert cell["traffic"] == "record_closed4"
    for said in ("4 clients", "9.0k-9.3k", "128 new tokens", "select"):
        assert said in cell["why"], said
    mix = load(os.path.join(BENCH_DIR, "traffic", "record_closed4.json"))
    assert (mix["loop"], mix["clients"], mix["lockstep"]) == ("closed", 4, True)
    assert mix["endpoint"] == "/ask/stream" and mix["warm_requests"] == 0
    assert mix["questions"] == [{"kind": "generative", "weight": 1}]
    assert mix["warm_bursts"] == [1, 2, 3, 4, 1] and mix["ramp_requests"] == 1
    assert (mix["timeout_s"], mix["trace_s"]) == (120, 6)
    serving = CONF["serving"]
    assert serving["generate.max_concurrent"] == mix["clients"]
    assert serving["generate.kv_pool_tokens"] == 4 * 9728
    assert serving["generate.max_new_tokens"] == 128
    assert serving["generate.admit_hold_ms"] == 50
    assert serving["generate.prefix_cache"] is False
    assert serving["generate.speculative_k"] == 0
    assert serving["generate.prefill_token_buckets"] == [9728]
    assert serving["store.default_k"] == 112
    assert serving["resilience.request_deadline_s"] == 30
    assert serving["decoder.quantize_weights"] is True


OLDER = ["rag_closed", "rag_closed8_dsv2"]
NEW_METRICS = ["sparse_blocks_read_share", "lane_state_share_of_step_bytes",
               "prefill_mfu"]


def test_the_lists_the_cell_joined_and_the_ones_it_did_not():
    """ISSUE 38's lists, but for ``decode_step_ms`` and
    ``decode_step_roofline``: the 6 s slice the issue fixed (``trace_s``)
    lies inside a round's prefills and may hold no decode program, so their
    reader may find nothing to read here (PERF.md 6, 7)."""
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    joined = {n for n, m in metrics.items()
              if CELL_NAME in m.get("workloads", [])}
    assert joined == {
        "ttft_p50_ms", "tpot_p50_ms", "window_tok_s", "retrieve_mean_ms.gen",
        "admit_wait_p50_ms", "decode_batch_mean", "kv_pool_used_share",
        "spine_wait_mean_ms", "device_idle_share.gen",
        "first_token_wait_p50_ms", "admit_drain_mean_ms", "admit_batch_mean",
        "prefill_pad_share", "decode_tokens_per_chunk",
        "decode_stale_chunk_share", *NEW_METRICS}
    for name in joined - set(NEW_METRICS):  # behind the cells that were there
        assert metrics[name]["workloads"][:3] == OLDER + [CELL_NAME], name
    for name in NEW_METRICS:
        assert metrics[name]["workloads"][0] == CELL_NAME
    assert metrics["prefill_mfu"]["moves"] == "ttft_p50_ms"
    assert metrics["prefill_mfu"]["source"] == "device_trace"


def test_what_pr35s_pin_held_still_holds():
    """``test_benchmark_deepseek_v2.py::test_the_cell_is_the_issues_table``
    cannot pass once a cell or a metric is appended (tests/conftest.py marks
    it, strictly).  Every assertion of it that is still true, as it stands
    there; and the two that are not — the per-layer list ENDS with PR 35's
    three metrics, every list of cells EQUALS the two older cells — as what
    they meant: nothing moved, nothing taken away, later entries behind."""
    cell = {w["name"]: w for w in BENCH["workloads"]}["rag_closed8_dsv2"]
    assert cell == {**cell, "config": "deepseek-v2-ep4-bf16",
                    "traffic": "rag_closed8", "chips": 1}
    mix = load(os.path.join(BENCH_DIR, "traffic", "rag_closed8.json"))
    assert {k: mix[k] for k in (
        "loop", "clients", "lockstep", "endpoint", "questions",
        "ramp_requests", "timeout_s", "trace_s", "warm_requests")} == {
        "loop": "closed", "clients": 8, "lockstep": True,
        "endpoint": "/ask/stream",
        "questions": [{"kind": "generative", "weight": 1}],
        "ramp_requests": 1, "timeout_s": 60, "trace_s": 6, "warm_requests": 0}
    dsv2 = load(os.path.join(BENCH_DIR, "configs", "deepseek-v2-ep4-bf16.json"))
    assert mix["clients"] == dsv2["serving"]["generate.max_concurrent"] == 8
    assert mix["warm_bursts"] == [1, 2, 3, 4, 5, 6, 7, 8, 1]
    assert "1 + 3 + 4" in mix["note"]
    assert dsv2["serving"]["generate.admit_hold_ms"] == 50.0
    assert "generate.admit_hold_ms" in mix["note"]
    assert "generate.admit_hold_ms" in dsv2["assumed"]
    pinned = {"prefill_ahead_share", "prefix_hit_share",
              "prefill_dispatches_per_round", "decode_kv_read_amplification"}
    moe = ["moe_local_pick_share", "moe_experts_touched_per_layer_step",
           "decode_touched_roofline"]
    reports = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
               if "rag_closed8_dsv2" in m.get("workloads", [])}
    old = {m["name"] for m in BENCH["per_layer"]
           if "rag_closed" in m.get("workloads", [])}
    assert reports == (old - pinned) | {"ttft_p50_ms", "tpot_p50_ms", *moe}
    # PR 35's three: together, in their order, behind everything older ...
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(moe[0])
    assert names[at:at + 3] == moe
    assert all("rag_closed" in m["workloads"] for m in BENCH["per_layer"][:at])
    new = BENCH["per_layer"][at:at + 3]
    assert all(m["workloads"][0] == "rag_closed8_dsv2"
               and m["moves"] == "tpot_p50_ms" for m in new)
    assert [m["layer"] for m in new] == ["Model step", "Model step", "Kernels"]
    # ... and PR 38's three behind them, whatever comes after
    assert names[at + 3:at + 6] == NEW_METRICS
    # the old cell is on every list it was on, first; the second behind it
    for m in BENCH["end_to_end"] + BENCH["per_layer"][:at]:
        if "workloads" in m:
            lead = ["rag_closed"] + (
                [] if m["name"] in pinned else ["rag_closed8_dsv2"])
            assert m["workloads"][:len(lead)] == lead, m["name"]
    assert [m["bound"] for m in BENCH["end_to_end"]][:3] == [0.01, 0.01, 0.1]
    assert [m["name"] for m in BENCH["end_to_end"]][:3] == [
        "ttft_p50_ms", "tpot_p50_ms", "setup_s"]
    assert BENCH["run_seconds"] == 30


def test_the_file_states_what_it_assumed_and_four_limits():
    for key in ("sparse_config", "lightning decay", "lightning details",
                "mup", "weights", "tokenizer", "kernels", "store.default_k",
                "resilience.request_deadline_s", "dense_sparse_switch"):
        assert len(CONF["assumed"][key]) > 40, key
    assert CONF["sparse_config"] == KEYS.SPARSE_DEFAULTS
    assert set(CONF["correct"]) == {
        "decoder_logit_rel_err", "kv_cache_bits_missing",
        "retrieval_score_err", "router_choice_gap"}
    for name, limit in CONF["correct"].items():
        assert isinstance(limit, (int, float)), name  # from calibrate.py
    assert CONF["check"] == {
        "prompt_lengths": [9000, 9050, 9100, 9150], "lane_rows": 9472}
    assert CONF["check"]["lane_rows"] % 128 == 0
    assert min(CONF["check"]["prompt_lengths"]) >= 8192  # selection runs


# ---- keys ---------------------------------------------------------------------

def test_every_published_key_is_mapped_fixed_or_ignored_by_name():
    known = (set(KEYS.TO_DECODER) | set(KEYS.FIXED) | set(KEYS.IGNORED)
             | {"mixer_types", "lightning_nkv"})
    assert set(PUBLISHED) <= known
    out = KEYS.program_overrides(CONF)
    assert out["decoder.block"] == "sparse_linear"
    assert out["decoder.mixer_types"].count("sparse") == 8
    assert [i for i, m in enumerate(out["decoder.mixer_types"])
            if m == "sparse"] == SPARSE_AT
    assert out["decoder.max_seq_len"] == 9728
    assert out["decoder.sparse_topk"] == 64
    assert out["decoder.sparse_dense_len"] == 8192
    assert (out["decoder.linear_heads"], out["decoder.num_kv_heads"]) == (32, 2)
    whole = child.program_overrides(CONF)
    assert whole["decoder.quantize_weights"] is True


@pytest.mark.parametrize("change, said", [
    ({"sliding_window": 4096}, '"sliding_window"'),
    ({"attn_use_rope": True}, '"attn_use_rope"'),
    ({"qk_norm": False}, '"qk_norm"'),
    ({"lightning_nkv": 8}, '"lightning_nkv"'),
    ({"mixer_types": ["minicpm4"] * 31 + ["mamba"]}, '"mixer_types"'),
    ({"sparse_config": {"top_k": 64}}, '"sparse_config"'),
    ({"weight_quantization": "int4"}, '"weight_quantization"'),
])
def test_a_key_the_block_does_not_know_is_a_config_error(change, said):
    with pytest.raises(arch.ConfigError, match=said):
        KEYS.program_overrides({**CONF, **change})


def test_a_missing_key_is_named():
    conf = {k: v for k, v in CONF.items() if k != "lightning_nh"}
    with pytest.raises(arch.ConfigError, match='"lightning_nh"'):
        KEYS.program_overrides(conf)


# ---- what the package imports ---------------------------------------------------

def _imports(path):
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            out.add("." if node.level else node.module.split(".")[0])
    return out


@pytest.mark.parametrize("module", ["keys", "shapes", "weights", "reference"])
def test_what_a_module_of_the_package_imports(module):
    found = _imports(os.path.join(PACKAGE_DIR, module + ".py"))
    if module in ("keys", "shapes"):
        assert found <= {"__future__", "typing", "harness", "."}
    if module == "reference":
        assert "docqa_tpu" not in found
    assert found <= {"__future__", "typing", "harness", ".", "functools",
                     "math", "jax", "numpy", "docqa_tpu"}
    assert _imports(os.path.join(PACKAGE_DIR, "__init__.py")) == set()


# ---- the bytes and the operations, by hand --------------------------------------

def test_the_bytes_a_step_is_charged_with_by_hand():
    lightning = 5 * 4096 * 4096 + 3 * 4096 * 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    head = 73448 * 4096
    assert SHAPES.matrix_params(CONF) == {
        "layers": 24 * lightning + 8 * sparse, "head": head,
        "embedding": head}
    scales = 4 * (73448 + 24 * (5 * 4096 + 2 * 16384 + 4096)
                  + 8 * (3 * 4096 + 2 * 256 + 2 * 16384 + 4096))
    gains = 2 * (4096 + 32 * 2 * 4096 + 24 * (256 + 4096) + 8 * 256)
    weights = SHAPES.decoder_weight_bytes(CONF)
    assert weights["streamed"] == (
        24 * lightning + 8 * sparse + head + scales + gains)
    assert weights["embedding"] == 2 * head
    assert SHAPES.kv_row_bytes(CONF) == 1024
    assert SHAPES.kv_bytes_per_token(CONF) == 8448
    assert SHAPES.lane_state_bytes(CONF) == 50331648
    # four full lanes: every state read and written, 4096 rows a lane and
    # sparse layer, one compressed key per 16 tokens
    live = 4 * 9728
    assert SHAPES.least_lanes(CONF, live) == 4
    want = (weights["streamed"] + 4 * 2 * 50331648
            + 4 * 4096 * 1024 * 8 + live * 256)
    assert SHAPES.decode_step_min_bytes(CONF, live, 1) == want
    assert SHAPES.decode_step_min_bytes(CONF, live, 4) == want / 4
    # a short lane is charged its state all the same, rows by its share
    assert SHAPES.state_step_bytes(CONF, 9728) == 2 * 50331648


def test_the_operations_of_a_prefill_by_hand():
    layers = SHAPES.matrix_params(CONF)["layers"]
    n = 9100.0
    keys = 4096 * 4097 / 2 + (n - 4096) * 4096
    windows = n * n / 2 / 16
    want = (n * (2 * layers + 24 * 32 * 4 * 128 * 128)
            + 8 * 32 * (4 * 128 * keys + 2 * 128 * windows)
            + 2 * 73448 * 4096)
    assert SHAPES.prefill_flops(CONF, n, n) == pytest.approx(want, rel=1e-12)
    # the file's check block gives the default prompt length (9075)
    assert SHAPES.prefill_flops(CONF, 9075.0) == pytest.approx(
        SHAPES.prefill_flops(CONF, 9075.0, 9075.0))
    # ISSUE 38's reckoning: at least 1.62e14 a prompt
    assert 1.62e14 < SHAPES.prefill_flops(CONF, 9150.0, 9150.0) < 1.75e14
    # a prompt under topk x block attends causally to everything
    short = SHAPES.prefill_flops(CONF, 1000.0, 1000.0)
    assert short == pytest.approx(
        1000 * (2 * layers + 24 * 32 * 4 * 128 * 128)
        + 8 * 32 * (4 * 128 * 1000 * 1001 / 2 + 2 * 128 * 1000 * 500 / 16)
        + 2 * 73448 * 4096)


# ---- the traffic: what the seed may not draw --------------------------------------

@pytest.mark.parametrize("seed", [1, 7, 99, 4295604013, 4295606029, 2**31 + 5])
def test_every_prompt_of_the_mix_is_past_dense_len_and_takes_one_budget(seed):
    """Template + question + 112 notes of one size (whichever 112 the
    seeded encoder retrieves): 8,9xx-9,3xx tokens, more than dense_len
    8192, 128 new tokens inside 9,728 positions, one 9,728-row budget."""
    from docqa_tpu.ops.attention import RAGGED_ALIGN
    from docqa_tpu.service.qa import QA_TEMPLATE
    from docqa_tpu.text.tokenizer import default_tokenizer

    tok = default_tokenizer(CONF["vocab_size"], vocab_path=None)
    templates = [t["text"] for t in load(os.path.join(
        BENCH_DIR, "questions", "generative.json"))["templates"]]
    chunks = [row["text_content"] for i in range(0, 2048, 3)
              for row in corpus.patient_chunks(seed, i)]
    rng = random.Random(seed)
    k = CONF["serving"]["store.default_k"]
    budgets = set()
    for trial in range(24):
        prompt = QA_TEMPLATE.format(
            context="\n\n".join(rng.sample(chunks, k)),
            question=corpus.question(
                seed, templates[trial % len(templates)], rng.randrange(2048)))
        n = len(tok.encode(prompt))
        assert 8900 <= n <= 9399 and n > CONF["sparse_config"]["dense_len"]
        assert n + 128 + 2 <= CONF["max_position_embeddings"]
        budgets.add(-(-n // RAGGED_ALIGN) * RAGGED_ALIGN <= 9728)
    assert budgets == {True}


# ---- the metrics -----------------------------------------------------------------

def counters(**gained):
    return {"before": {"metrics": {"counters": dict.fromkeys(gained, 10)}},
            "after": {"metrics": {"counters": {
                k: 10 + v for k, v in gained.items()}}}}


def test_the_three_new_metrics_on_hand_made_counters():
    import run

    ctx = counters(
        serve_sparse_blocks_selected=64 * 16 * 1000,
        serve_sparse_blocks_live=145 * 16 * 1000,
        serve_state_bytes_rw=2 * 50331648 * 4 * 16 * 10,
        serve_decode_chunks=10, serve_prefill_tokens=4 * 9100,
        serve_prefill_dispatches=4, serve_admitted=4)
    ctx.update(
        conf=CONF, cell={"chips": 1}, device={"kind": "TPU v5 lite"},
        polled=[{"kv_tokens": 4 * 9200}],
        trace={"programs": {
            "jit__prefill_program": {"count": 4, "median_s": 1.25},
            "jit__decode_program": {"count": 9, "median_s": 0.24}}})
    assert run.read_metric("sparse_blocks_read_share", ctx) == pytest.approx(
        100 * 64 / 145)
    least = SHAPES.decode_step_min_bytes(CONF, 4 * 9200, 1)
    assert run.read_metric(
        "lane_state_share_of_step_bytes", ctx) == pytest.approx(
        100 * 2 * 50331648 * 4 / least)
    assert run.read_metric("prefill_mfu", ctx) == pytest.approx(
        100 * SHAPES.prefill_flops(CONF, 9100.0, 9100.0) / (197e12 * 1.25))
    assert 60 < run.read_metric("prefill_mfu", ctx) < 75


def test_under_a_program_without_the_counters_the_metrics_are_left_out():
    """The parent commit has none of the counters (and cannot run the
    configuration): each reader finds nothing and returns None; so does
    ``prefill_mfu`` under a package without ``prefill_flops``."""
    import run

    ctx = counters(serve_admitted=8, serve_decode_chunks=9)
    ctx.update(
        conf=CONF, cell={"chips": 1}, device={"kind": "TPU v5 lite"},
        polled=[{"kv_tokens": 3000}],
        trace={"programs": {
            "jit__prefill_program": {"count": 3, "median_s": 0.05}}})
    for name in ("sparse_blocks_read_share", "lane_state_share_of_step_bytes",
                 "prefill_mfu"):
        assert run.read_metric(name, ctx) is None
    full = counters(serve_prefill_tokens=900, serve_prefill_dispatches=3,
                    serve_admitted=3)
    full.update(ctx, before=full["before"], after=full["after"], conf=load(
        os.path.join(BENCH_DIR, "configs", "mistral-7b-int8.json")))
    assert run.read_metric("prefill_mfu", full) is None


# ---- the cell, rehearsed on the CPU at tiny widths ---------------------------------

def test_the_cell_runs_end_to_end_at_tiny_widths():
    """``/ask/stream`` -> QAService -> EnginePool -> batcher -> the paged
    forwards of the two-mixer block: one admission of four a round, four
    compared numbers, the block's counters in the result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", CELL_NAME, "--seed", "4295604013", "--seconds", "4",
         "--trace", "1", "--rehearsal", OVERLAY],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(ln for ln in reversed(proc.stdout.strip().splitlines())
                if ln.startswith("{"))
    out = json.loads(line)
    assert out["correct"] is True and out["failed"] == 0, out
    assert list(out["compared"]) == [
        "decoder_logit_rel_err", "kv_cache_bits_missing",
        "retrieval_score_err", "router_choice_gap"]
    assert out["compared"]["router_choice_gap"]["value"] > 0
    metrics = out["metrics"]
    assert metrics["admit_batch_mean"]["value"] == 4.0
    assert 0 < metrics["sparse_blocks_read_share"]["value"] < 100
    assert 0 < metrics["lane_state_share_of_step_bytes"]["value"] < 100
    assert "prefill_mfu" not in metrics  # a device metric: no CPU number
