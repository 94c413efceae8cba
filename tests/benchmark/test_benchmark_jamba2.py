"""ISSUE 42: the configuration ``jamba2-3b-bf16``, its architecture package
``benchmark/architectures/jamba/`` and the cell ``record_closed4_jamba2``
— files and entries only; nothing that was there is edited."""

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
OVERLAY = os.path.join(DATA, "tiny_overlay_jamba2.json")
FILE = os.path.join(BENCH_DIR, "configs", "jamba2-3b-bf16.json")
PACKAGE_DIR = os.path.join(BENCH_DIR, "architectures", "jamba")
CELL_NAME = "record_closed4_jamba2"
SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
CONF = load(FILE)
SHAPES = arch.load_shapes(CONF).shapes
KEYS = arch.load_shapes(CONF).keys

# the catalog row's ``config`` (the numbers of SOURCE), every key
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}
REDUCED = {"max_position_embeddings": 9728}


# ---- the file and the entries ------------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_each_published_key(key):
    assert key in CONF
    assert CONF[key] == REDUCED.get(key, PUBLISHED[key])


def test_layers_7_and_21_are_attention_and_the_rest_mamba():
    kinds = KEYS.mixer_types(CONF)
    assert len(kinds) == 28
    assert [i for i, m in enumerate(kinds) if m == "attention"] == [7, 21]
    assert set(kinds) == {"attention", "mamba"}
    assert kinds == KEYS.program_overrides(CONF)["decoder.mixer_types"]


def test_the_entry_names_the_source_and_exactly_one_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "jamba2-3b-bf16")
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/jamba2-3b-bf16.json"
    assert entry["reduced"] == ["max_position_embeddings"] == sorted(REDUCED)
    assert len(entry["why"]) <= 200
    # appended behind what was there; whatever comes later comes behind it
    assert [c["name"] for c in BENCH["configs"]][:4] == [
        "mistral-7b-int8", "deepseek-v2-ep4-bf16", "minicpm-sala-int8",
        "jamba2-3b-bf16"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}


def test_the_cell_is_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL_NAME)
    assert [w["name"] for w in BENCH["workloads"]][:4] == [
        "rag_closed", "rag_closed8_dsv2", "record_closed4_sala", CELL_NAME]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] == 1
    assert cell["config"] == "jamba2-3b-bf16"
    assert cell["traffic"] == "record_closed4"
    assert len(cell["why"]) <= 200
    for said in ("4 clients", "9.0k-9.3k", "128 new tokens", "scan"):
        assert said in cell["why"], said
    # the traffic file is SALA's, letter for letter
    mix = load(os.path.join(BENCH_DIR, "traffic", "record_closed4.json"))
    assert (mix["loop"], mix["clients"], mix["lockstep"]) == ("closed", 4, True)
    assert mix["endpoint"] == "/ask/stream"
    assert mix["warm_bursts"] == [1, 2, 3, 4, 1] and mix["trace_s"] == 6
    serving = CONF["serving"]
    assert serving["generate.max_concurrent"] == mix["clients"] == 4
    assert serving["generate.kv_pool_tokens"] == 4 * 9728 == 38912
    assert serving["generate.max_new_tokens"] == 128
    assert serving["generate.decode_chunk"] == 16
    assert serving["generate.prefill_token_buckets"] == [9728]
    assert serving["generate.prefix_cache"] is False
    assert serving["generate.speculative_k"] == 0
    assert serving["store.default_k"] == 112
    assert serving["resilience.request_deadline_s"] == 30
    # the program's own 0: PR 39's count makes a round one admission
    assert "generate.admit_hold_ms" not in serving
    assert "decoder.quantize_weights" not in serving  # bf16 weights
    assert CONF["torch_dtype"] == "bfloat16" and CONF["kv_cache_bits"] == 16
    # corpus, store, canary, retrieval and tagger settings of SALA's file
    sala = load(os.path.join(BENCH_DIR, "configs", "minicpm-sala-int8.json"))
    assert CONF["corpus"] == sala["corpus"] and CONF["chips"] == 1
    for key, value in sala["serving"].items():
        if key.split(".")[0] in ("store", "chunk", "dispatch", "pool",
                                 "retrieval_quality"):
            assert serving[key] == value, key


OLDER = ["rag_closed", "rag_closed8_dsv2", "record_closed4_sala"]
NEW_METRICS = ["prefill_scan_ms", "prefill_scan_roofline"]
THIRTEEN = [
    "window_tok_s", "retrieve_mean_ms.gen", "admit_wait_p50_ms",
    "decode_batch_mean", "kv_pool_used_share", "spine_wait_mean_ms",
    "device_idle_share.gen", "first_token_wait_p50_ms",
    "admit_drain_mean_ms", "admit_batch_mean", "prefill_pad_share",
    "decode_tokens_per_chunk", "decode_stale_chunk_share"]
PR40S_EIGHT = [
    "decode_attention_ms", "decode_projection_ms", "decode_mlp_ms",
    "decode_head_ms", "decode_other_ms", "prefill_attention_ms",
    "prefill_mlp_ms", "ask_lane_wait_p50_ms"]
PINNED_TO_RAG_CLOSED = [
    "prefill_ahead_share", "prefix_hit_share",
    "prefill_dispatches_per_round", "decode_kv_read_amplification"]


def test_the_lists_the_cell_joined_and_the_ones_it_did_not():
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    joined = {n for n, m in metrics.items()
              if CELL_NAME in m.get("workloads", [])}
    assert joined == {
        "ttft_p50_ms", "tpot_p50_ms", *THIRTEEN,
        "lane_state_share_of_step_bytes", "prefill_mfu", "decode_step_ms",
        "decode_step_roofline", *NEW_METRICS}
    for name in ["ttft_p50_ms", "tpot_p50_ms", *THIRTEEN]:
        assert metrics[name]["workloads"] == OLDER + [CELL_NAME], name
    for name in ("lane_state_share_of_step_bytes", "prefill_mfu"):
        assert metrics[name]["workloads"] == [
            "record_closed4_sala", CELL_NAME]
    for name in ("decode_step_ms", "decode_step_roofline"):
        assert metrics[name]["workloads"] == OLDER[:2] + [CELL_NAME]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL_NAME]
    # nothing selects here; PR 40's eight and the four stay pinned
    for name in ["sparse_blocks_read_share", *PR40S_EIGHT,
                 *PINNED_TO_RAG_CLOSED]:
        assert CELL_NAME not in metrics[name]["workloads"], name
    assert "workloads" not in metrics["setup_s"]  # every cell reports it


def test_the_two_entries_are_the_last_two_behind_pr40s_eight():
    """The driver takes an entry put in the middle of ``per_layer`` as a
    change to the one that stood at that place (its first check of this PR
    refused "between ``prefill_mfu`` and ``decode_attention_ms``", which
    ISSUE 42 had asked for): new entries go at the END."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("prefill_mfu")
    assert names[at + 1:at + 9] == PR40S_EIGHT  # nothing of theirs moved
    assert names[at + 9:] == NEW_METRICS
    ms, share = BENCH["per_layer"][-2:]
    assert ms == {
        "name": "prefill_scan_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels",
        "moves": "ttft_p50_ms", "workloads": [CELL_NAME]}
    assert share == {
        "name": "prefill_scan_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels",
        "moves": "ttft_p50_ms", "workloads": [CELL_NAME]}
    assert load(os.path.join(BENCH_DIR, "metrics", "prefill_scan_ms.json")) == {
        "reader": "scope_time",
        "params": {"program": "prefill", "per": 1, "scopes": ["state"]}}
    assert load(os.path.join(
        BENCH_DIR, "metrics", "prefill_scan_roofline.json"))["reader"] == (
        "scan_roofline")
    # no bound, no count of runs and no older entry moved
    assert [m["bound"] for m in BENCH["end_to_end"]] == [0.01, 0.01, 0.1]
    assert BENCH["run_seconds"] == 30 and len(BENCH["workloads"]) == 4
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0


def test_what_pr40s_pin_held_still_holds():
    """``test_benchmark_scopes.py::test_the_eight_entries_sit_at_the_end_
    with_the_two_cells`` asserts that ``per_layer`` ENDS with PR 40's eight:
    false once any later PR appends a metric, as the driver makes it do
    (tests/conftest.py marks that test, strictly).  Every other assertion
    of it as it stands there, and that one as what it meant: the eight
    together, in their order, behind everything older, later entries behind
    them."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(PR40S_EIGHT[0])
    assert names[at:at + 8] == PR40S_EIGHT
    assert names[at - 1] == "prefill_mfu"  # PR 38's last, as before PR 42
    tail = BENCH["per_layer"][at:at + 8]
    for m in tail:
        assert m["workloads"] == ["rag_closed", "rag_closed8_dsv2"]
        assert (m["unit"], m["better"]) == ("ms", "lower")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["source"] for m in tail] == ["device_trace"] * 7 + [
        "program_span"]
    assert [m["layer"] for m in tail] == [
        "Kernels", "Model step", "Model step", "Model step", "Model step",
        "Kernels", "Model step", "HTTP surface"]
    assert [m["moves"] for m in tail] == ["tpot_p50_ms"] * 5 + [
        "ttft_p50_ms"] * 3
    assert len(set(names)) == len(names)


def test_the_file_states_what_it_assumed_and_three_limits():
    for key in ("weights", "tokenizer", "mamba initialisation",
                "layer order", "lm_head", "conv window", "kernels",
                "max_position_embeddings", "store.default_k",
                "generate.admit_hold_ms", "resilience.request_deadline_s",
                "lane state"):
        assert len(CONF["assumed"][key]) > 40, key
    assert len(CONF["deployment"]) > 100
    # this block does not route: three limits, from calibrate.py
    assert set(CONF["correct"]) == {
        "decoder_logit_rel_err", "kv_cache_bits_missing",
        "retrieval_score_err"}
    for name, limit in CONF["correct"].items():
        assert isinstance(limit, (int, float)), name
    assert CONF["correct"]["kv_cache_bits_missing"] == 0
    assert CONF["check"] == {
        "prompt_lengths": [9000, 9050, 9100, 9150], "lane_rows": 9472}
    assert CONF["check"]["lane_rows"] % 128 == 0


# ---- keys ---------------------------------------------------------------------

def test_every_published_key_is_mapped_fixed_or_ignored_by_name():
    mapped = set(KEYS.TO_DECODER) | set(KEYS.ORDER)
    assert set(PUBLISHED) == mapped | set(KEYS.FIXED) | set(KEYS.IGNORED)
    assert not mapped & set(KEYS.FIXED) and not mapped & set(KEYS.IGNORED)
    assert set(KEYS.IGNORED) == {
        "use_mamba_kernels", "num_logits_to_keep", "expert_layer_offset",
        "expert_layer_period", "num_experts_per_tok"}
    assert KEYS.FIXED["sliding_window"] is None
    out = KEYS.program_overrides(CONF)
    assert out["decoder.block"] == "sparse_linear"
    assert out["decoder.mixer_types"].count("mamba") == 26
    assert (out["decoder.hidden_dim"], out["decoder.mlp_dim"]) == (2560, 8192)
    assert (out["decoder.num_heads"], out["decoder.num_kv_heads"],
            out["decoder.head_dim"]) == (20, 1, 128)
    assert (out["decoder.ssm_state_dim"], out["decoder.ssm_conv_width"],
            out["decoder.ssm_dt_rank"], out["decoder.ssm_expand"]) == (
        16, 4, 160, 2)
    assert out["decoder.ssm_conv_bias"] is True
    assert out["decoder.ssm_proj_bias"] is False
    assert out["decoder.tie_embeddings"] is True
    assert out["decoder.max_seq_len"] == 9728
    assert out["decoder.vocab_size"] == 65536
    assert out["decoder.norm_eps"] == 1e-06
    # what SALA's stack does around its softmax, off
    assert (out["decoder.qk_norm"], out["decoder.use_output_gate"],
            out["decoder.use_output_norm"]) == (False, False, False)
    whole = child.program_overrides(CONF)
    assert whole["generate.max_concurrent"] == 4
    assert "decoder.quantize_weights" not in whole


@pytest.mark.parametrize("change, said", [
    ({"sliding_window": 4096}, '"sliding_window"'),
    ({"num_experts": 16}, '"num_experts"'),
    ({"hidden_act": "gelu"}, '"hidden_act"'),
    ({"model_type": "mamba"}, '"model_type"'),
    ({"attn_layer_offset": 14}, '"attn_layer_offset"'),
    ({"num_attention_heads": 24}, '"num_attention_heads"'),
    ({"mamba_chunk_size": 256}, '"mamba_chunk_size"'),
    ({"rope_theta": 10000.0}, '"rope_theta"'),
])
def test_a_key_the_block_does_not_know_is_a_config_error(change, said):
    with pytest.raises(arch.ConfigError, match=said):
        KEYS.program_overrides({**CONF, **change})


@pytest.mark.parametrize("key", [
    "mamba_d_state", "attn_layer_period", "num_experts", "sliding_window",
    "tie_word_embeddings"])
def test_a_missing_key_is_named(key):
    conf = {k: v for k, v in CONF.items() if k != key}
    with pytest.raises(arch.ConfigError, match=f'"{key}"'):
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
                     "math", "jax", "docqa_tpu"}
    assert _imports(os.path.join(PACKAGE_DIR, "__init__.py")) == set()


def test_the_new_reader_is_standard_library_and_used():
    found = _imports(os.path.join(BENCH_DIR, "readers", "scan_roofline.py"))
    assert found <= {"harness", "readers"}


# ---- the bytes and the operations, by hand --------------------------------------

MAMBA_MIXER = (2560 * 10240 + 5120 * 2560 + 5120 * 192 + 160 * 5120 + 5120
               + 4 * 5120 + 5120 + 16 * 5120 + 5120 + 192)
MLP = 3 * 2560 * 8192
ATTN_MIXER = 2 * 2560 * 2560 + 2 * 2560 * 128


def test_the_parameters_by_hand():
    assert MAMBA_MIXER == 41241792 and MLP == 62914560
    assert sum(SHAPES.mixer_params(CONF, "mamba").values()) == MAMBA_MIXER
    assert SHAPES.mixer_params(CONF, "attention")["matrices"] == ATTN_MIXER
    assert SHAPES.layer_params(CONF, "mamba") == 104161472
    assert SHAPES.layer_params(CONF, "attention") == 76682240
    assert SHAPES.parameters(CONF) == (
        26 * 104161472 + 2 * 76682240 + 65536 * 2560 + 2560) == 3029337472
    assert SHAPES.scan_layers(CONF) == 26


def test_the_bytes_a_step_is_charged_with_by_hand():
    a_log = 26 * 16 * 5120
    streamed = 2 * 3029337472 + 2 * a_log  # A_log is float32
    assert SHAPES.decoder_weight_bytes(CONF) == {"streamed": streamed}
    assert 6.06e9 < streamed < 6.07e9
    assert SHAPES.kv_row_bytes(CONF) == 512
    assert SHAPES.kv_bytes_per_token(CONF) == 1024
    assert SHAPES.layer_state_bytes(CONF) == 327680 + 30720
    assert SHAPES.lane_state_bytes(CONF) == 26 * 358400 == 9318400
    live = 4 * 9728
    assert SHAPES.least_lanes(CONF, live) == 4
    want = streamed + 4 * 2 * 9318400 + live * 1024
    assert SHAPES.decode_step_min_bytes(CONF, live, 1) == want
    assert SHAPES.decode_step_min_bytes(CONF, live, 4) == want / 4
    # 7.4-7.6 ms at 819 GB/s: ISSUE 42's weight stream plus state and rows
    assert 7.4e-3 < want / 819e9 < 7.6e-3


def test_the_operations_of_a_prefill_by_hand():
    n = 9100.0
    mamba_matrices = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    a_token = (26 * (2 * (mamba_matrices + 4 * 5120 + MLP) + 9 * 5120 * 16)
               + 2 * 2 * (ATTN_MIXER + MLP))
    a_prompt = 2 * 20 * 4 * 128 * n * (n + 1) / 2 + 2 * 65536 * 2560
    assert SHAPES.prefill_flops(CONF, n, n) == pytest.approx(
        n * a_token + a_prompt, rel=1e-12)
    # ISSUE 42's 5.7 GFLOP a token, the attention's and the head's share in
    assert 5.6e9 < a_token < 5.8e9
    assert 5.7e9 < SHAPES.prefill_flops(CONF, n, n) / n < 6.0e9
    # the file's check block gives the default prompt length (9075)
    assert SHAPES.prefill_flops(CONF, 9075.0) == pytest.approx(
        SHAPES.prefill_flops(CONF, 9075.0, 9075.0))
    # four prompts in one dispatch pay four heads and four triangles
    assert SHAPES.prefill_flops(CONF, 4 * n, n) == pytest.approx(
        4 * SHAPES.prefill_flops(CONF, n, n))


def test_the_bytes_a_scan_is_charged_with_by_hand():
    n = 9100.0
    a_token = (3 * 5120 + 2 * 16) * 2  # c, D_t in, g out, B and C: bf16
    want = 26 * (n * a_token + 2 * (327680 + 30720))
    assert SHAPES.prefill_scan_min_bytes(CONF, n, 1) == want
    assert SHAPES.prefill_scan_min_bytes(CONF, 4 * n, 4) == 4 * want
    assert 7.2e9 < want < 7.4e9  # 8.9 ms of a dispatch at 819 GB/s


# ---- the traffic: what the seed may not draw --------------------------------------

@pytest.mark.parametrize("seed", [1, 7, 99, 4295604013, 4295606029, 2**31 + 5])
def test_every_prompt_of_the_mix_takes_one_budget(seed):
    """Template + question + 112 notes of one size (whichever 112 the
    seeded encoder retrieves), hashed over THIS vocabulary: 8,9xx-9,3xx
    tokens, 128 new tokens inside 9,728 positions, one 9,728-row budget."""
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
    for trial in range(24):
        prompt = QA_TEMPLATE.format(
            context="\n\n".join(rng.sample(chunks, k)),
            question=corpus.question(
                seed, templates[trial % len(templates)], rng.randrange(2048)))
        n = len(tok.encode(prompt))
        assert 8900 <= n <= 9399
        assert n + 128 + 2 <= CONF["max_position_embeddings"]
        assert -(-n // RAGGED_ALIGN) * RAGGED_ALIGN <= 9728
        assert n + 2 * 1 <= CONF["check"]["lane_rows"]


# ---- the metrics -----------------------------------------------------------------

def counters(**gained):
    return {"before": {"metrics": {"counters": dict.fromkeys(gained, 10)}},
            "after": {"metrics": {"counters": {
                k: 10 + v for k, v in gained.items()}}}}


SCOPES = {"jit__prefill_program": {
    "executions": 4, "median_s": 0.9,
    "scopes": {"state": 0.36, "proj": 0.25, "mlp": 0.2, "attend": 0.05,
               "-": 0.01}}}


def test_the_two_new_metrics_on_hand_made_counters_and_scopes():
    import run

    ctx = counters(
        serve_scan_tokens=26 * 4 * 9100, serve_prefill_tokens=4 * 9100,
        serve_prefill_dispatches=4, serve_admitted=4,
        serve_state_bytes_rw=2 * 9318400 * 4 * 16 * 10,
        serve_decode_chunks=10)
    ctx.update(
        conf=CONF, cell={"chips": 1, "name": CELL_NAME},
        device={"kind": "TPU v5 lite"}, polled=[{"kv_tokens": 4 * 9200}],
        scope_times=SCOPES,
        trace={"programs": {
            "jit__prefill_program": {"count": 4, "median_s": 0.9},
            "jit__decode_program": {"count": 9, "median_s": 0.14}}})
    assert run.read_metric("prefill_scan_ms", ctx) == pytest.approx(360.0)
    least = SHAPES.prefill_scan_min_bytes(CONF, 9100.0, 1.0)
    share = run.read_metric("prefill_scan_roofline", ctx)
    assert share == pytest.approx(100 * (least / 819e9) / 0.36)
    assert 2.0 < share < 3.0
    # the older ones the cell joined read this package's shapes
    assert run.read_metric("prefill_mfu", ctx) == pytest.approx(
        100 * SHAPES.prefill_flops(CONF, 9100.0, 9100.0) / (197e12 * 0.9))
    step = SHAPES.decode_step_min_bytes(CONF, 4 * 9200, 1)
    assert run.read_metric(
        "lane_state_share_of_step_bytes", ctx) == pytest.approx(
        100 * 2 * 9318400 * 4 / step)
    assert run.read_metric("decode_step_ms", ctx) == pytest.approx(140 / 16)
    assert run.read_metric("decode_step_roofline", ctx) == pytest.approx(
        100 * (step / 819e9) / (0.14 / 16))
    # two prompts a dispatch: the same share at twice the time
    two = dict(ctx, **counters(
        serve_scan_tokens=26 * 4 * 9100, serve_prefill_dispatches=2,
        serve_admitted=4))
    two["scope_times"] = {"jit__prefill_program": {
        **SCOPES["jit__prefill_program"],
        "scopes": {"state": 0.72}}}
    assert run.read_metric("prefill_scan_roofline", two) == pytest.approx(
        share)


def test_under_a_program_without_the_counter_the_metrics_are_left_out():
    """The parent commit has no ``serve_scan_tokens`` (and cannot run the
    configuration), a compile cache filled before the scopes no scoped op,
    another package no such function: each reader finds nothing and
    returns None, and nothing raises."""
    import run

    base = dict(
        conf=CONF, cell={"chips": 1, "name": CELL_NAME},
        device={"kind": "TPU v5 lite"}, polled=[{"kv_tokens": 3000}])
    parent = dict(base, scope_times=SCOPES, **counters(
        serve_prefill_tokens=900, serve_prefill_dispatches=3,
        serve_admitted=3))
    assert run.read_metric("prefill_scan_roofline", parent) is None
    unscoped = dict(base, **counters(
        serve_scan_tokens=26 * 900, serve_prefill_dispatches=3,
        serve_admitted=3))
    for table in ({}, {"jit__prefill_program": {
            "executions": 3, "median_s": 0.5, "scopes": {"-": 0.5}}}):
        unscoped["scope_times"] = table
        assert run.read_metric("prefill_scan_ms", unscoped) is None
        assert run.read_metric("prefill_scan_roofline", unscoped) is None
    other = dict(parent, conf=load(
        os.path.join(BENCH_DIR, "configs", "minicpm-sala-int8.json")),
        **counters(serve_scan_tokens=24 * 900, serve_prefill_dispatches=3,
                   serve_admitted=3))
    assert run.read_metric("prefill_scan_roofline", other) is None
    no_counters = dict(base, scope_times=SCOPES, before={}, after={})
    assert run.read_metric("prefill_scan_roofline", no_counters) is None


# ---- the cell, rehearsed on the CPU at tiny widths ---------------------------------

def test_the_cell_runs_end_to_end_at_tiny_widths():
    """``/ask/stream`` -> QAService -> EnginePool -> batcher -> the paged
    forwards of the stack of mixer kinds (a Mamba, an attention, two Mamba
    layers): rounds admitted together, three compared numbers, the stack's
    counters in the result line."""
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
        "retrieval_score_err"]
    assert 0 < out["compared"]["decoder_logit_rel_err"]["value"] < 0.08
    metrics = out["metrics"]
    # 4.0 on the chip and on an idle host; under the suite's six workers
    # a starved round may go without an arrival it was told to expect
    assert 1.0 < metrics["admit_batch_mean"]["value"] <= 4.0
    assert 0 < metrics["lane_state_share_of_step_bytes"]["value"] < 100
    # device metrics: no CPU number under their names
    for name in ("prefill_scan_ms", "prefill_scan_roofline", "prefill_mfu",
                 "decode_step_ms", "decode_step_roofline"):
        assert name not in metrics
