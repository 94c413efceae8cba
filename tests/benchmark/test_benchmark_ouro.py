"""ISSUE 44: the configuration ``ouro-2.6b-bf16``, its architecture package
``benchmark/architectures/ouro/`` and the cell ``rag_closed_ouro`` — files
and entries only; nothing that was there is edited.

This file pins BENCHMARK.json by prefix and membership only (``[:n]``,
``in``, ``names.index``; never ``==`` on a whole list, a tail or a length),
so that the next cell or metric appended needs no mark in
``tests/conftest.py``."""

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
OVERLAY = os.path.join(DATA, "tiny_overlay_ouro.json")
FILE = os.path.join(BENCH_DIR, "configs", "ouro-2.6b-bf16.json")
PACKAGE_DIR = os.path.join(BENCH_DIR, "architectures", "ouro")
CELL_NAME = "rag_closed_ouro"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
CONF = load(FILE)
SHAPES = arch.load_shapes(CONF).shapes
KEYS = arch.load_shapes(CONF).keys

# the catalog row's ``config`` (the numbers of SOURCE), every key
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
REDUCED = {"max_position_embeddings": 512}


# ---- the file and the entries ------------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_each_published_key(key):
    assert key in CONF
    assert CONF[key] == REDUCED.get(key, PUBLISHED[key])


def test_the_entry_names_the_source_and_exactly_one_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "ouro-2.6b-bf16")
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/ouro-2.6b-bf16.json"
    assert entry["reduced"] == sorted(REDUCED)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200
    # the four older configurations stand where they stood
    assert [c["name"] for c in BENCH["configs"]][:5] == [
        "mistral-7b-int8", "deepseek-v2-ep4-bf16", "minicpm-sala-int8",
        "jamba2-3b-bf16", "ouro-2.6b-bf16"]
    assert CONF["architecture"] == "ouro" and CONF["chips"] == 1
    assert CONF["torch_dtype"] == "bfloat16" and CONF["kv_cache_bits"] == 16


def test_the_cell_is_the_issues():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[:5] == [
        "rag_closed", "rag_closed8_dsv2", "record_closed4_sala",
        "record_closed4_jamba2", CELL_NAME]
    cell = BENCH["workloads"][cells.index(CELL_NAME)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b-bf16", "rag_closed_short_slice", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200
    # rag_closed's mix, parameter for parameter, under a SHORTER traced
    # slice: 192 layer passes a step leave more device events a traced
    # second than run.py's 300 s wait for a 6 s slice can take; 2.5 s is
    # a round (2.35 s) and one prefill dispatch (0.096 s) more, so four
    # whole dispatches lie in it wherever it falls (PERF.md 6)
    traffic = load(os.path.join(
        BENCH_DIR, "traffic", "rag_closed_short_slice.json"))
    older = load(os.path.join(BENCH_DIR, "traffic", "rag_closed.json"))
    assert {k: v for k, v in traffic.items()
            if k not in ("trace_s", "note")} == {
        k: v for k, v in older.items() if k not in ("trace_s", "note")}
    assert (traffic["clients"], traffic["endpoint"], traffic["lockstep"],
            traffic["warm_bursts"]) == (
        4, "/ask/stream", True, [1, 2, 3, 4, 1])
    assert (older["trace_s"], traffic["trace_s"]) == (6, 2.5)
    assert len(traffic["note"]) > 100
    serving, mistral = CONF["serving"], load(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-int8.json"))["serving"]
    assert serving["generate.max_concurrent"] == traffic["clients"]
    assert serving["generate.kv_pool_tokens"] == 4 * 512 == (
        traffic["clients"] * CONF["max_position_embeddings"])
    assert "generate.admit_hold_ms" not in serving
    assert "decoder.quantize_weights" not in serving
    # everything else is mistral-7b-int8's, setting for setting
    for key, value in mistral.items():
        if key not in ("decoder.quantize_weights", "decoder.quant_bits",
                       "generate.kv_pool_tokens"):
            assert serving[key] == value, key
    assert set(serving) <= set(mistral)
    assert CONF["corpus"] == load(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-int8.json"))["corpus"]
    # the lengths this traffic sends (311-349) in the 384 packed rows
    # they take: four lanes are one 1,536-row dispatch, whose temporaries
    # fit beside the THREE pools the comparison holds (the file's assumed)
    assert CONF["check"] == {
        "prompt_lengths": [150, 290, 320, 340], "lane_rows": 384}


OLDER = ["rag_closed", "rag_closed8_dsv2", "record_closed4_sala",
         "record_closed4_jamba2"]
SEVENTEEN = [
    "ttft_p50_ms", "tpot_p50_ms", "window_tok_s", "retrieve_mean_ms.gen",
    "admit_wait_p50_ms", "decode_batch_mean", "kv_pool_used_share",
    "spine_wait_mean_ms", "device_idle_share.gen",
    "first_token_wait_p50_ms", "admit_drain_mean_ms", "admit_batch_mean",
    "prefill_pad_share", "decode_tokens_per_chunk",
    "decode_stale_chunk_share", "decode_step_ms", "decode_step_roofline"]
PR40S_EIGHT = [
    "decode_attention_ms", "decode_projection_ms", "decode_mlp_ms",
    "decode_head_ms", "decode_other_ms", "prefill_attention_ms",
    "prefill_mlp_ms", "ask_lane_wait_p50_ms"]
PINNED_TO_RAG_CLOSED = [
    "prefill_ahead_share", "prefix_hit_share",
    "prefill_dispatches_per_round", "decode_kv_read_amplification"]
PR42S_TWO = ["prefill_scan_ms", "prefill_scan_roofline"]


def _metrics():
    return {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}


@pytest.mark.parametrize("name", [*SEVENTEEN, "prefill_mfu"])
def test_the_cell_joined_the_list_behind_the_cells_that_were_there(name):
    cells = _metrics()[name]["workloads"]
    at = cells.index(CELL_NAME)
    assert cells[:at] == [c for c in OLDER if c in cells[:at]]  # their order
    assert set(cells[:at]) <= set(OLDER) and at >= 2


@pytest.mark.parametrize("name", [
    "sparse_blocks_read_share", "lane_state_share_of_step_bytes",
    "moe_local_pick_share", "moe_experts_touched_per_layer_step",
    "decode_touched_roofline", *PR42S_TWO, *PR40S_EIGHT,
    *PINNED_TO_RAG_CLOSED])
def test_the_lists_the_cell_did_not_join(name):
    """Nothing routes, selects, scans or keeps a lane state here; PR 40's
    eight and the four pinned to ``["rag_closed"]`` wait for the
    `benchmark` PR that loosens their pins (PERF.md section 7)."""
    assert CELL_NAME not in _metrics()[name]["workloads"]


def test_the_one_new_metric_stands_behind_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("loop_passes_per_token")
    assert names.index(PR42S_TWO[1]) < at  # behind PR 42's two
    assert BENCH["per_layer"][at] == {
        "name": "loop_passes_per_token", "unit": "passes", "better": "lower",
        "source": "program_counter", "layer": "Model step",
        "moves": "tpot_p50_ms", "workloads": [CELL_NAME]}
    assert load(os.path.join(
        BENCH_DIR, "metrics", "loop_passes_per_token.json")) == {
        "reader": "counter_ratio", "params": {
            "numerator": ["serve_loop_passes"],
            "denominator": ["serve_loop_lane_steps"]}}
    assert "workloads" not in _metrics()["setup_s"]  # every cell reports it
    # no bound, no count of runs moved, no four-chip cell
    assert [m["bound"] for m in BENCH["end_to_end"]][:3] == [0.01, 0.01, 0.1]
    assert BENCH["run_seconds"] == 30
    assert all(w["chips"] == 1 for w in BENCH["workloads"][:5])
    assert len(set(names)) == len(names)


def test_what_pr42s_two_pins_held_still_holds():
    """``test_benchmark_jamba2.py::test_the_lists_the_cell_joined_and_the_
    ones_it_did_not`` holds every list the Jamba cell joined EQUAL to
    ``older + [its name]`` and ``::test_the_two_entries_are_the_last_two_
    behind_pr40s_eight`` the per-layer list's END and the count of cells:
    false once this PR appends (tests/conftest.py marks both, strictly).
    Every other line of them, and those as what they meant."""
    jamba, metrics = "record_closed4_jamba2", _metrics()
    joined = {n for n, m in metrics.items()
              if jamba in m.get("workloads", [])}
    assert joined == {
        *SEVENTEEN, "prefill_mfu", "lane_state_share_of_step_bytes",
        *PR42S_TWO}
    for name in SEVENTEEN[:15]:
        assert metrics[name]["workloads"][:4] == OLDER, name
    for name in ("lane_state_share_of_step_bytes", "prefill_mfu"):
        assert metrics[name]["workloads"][:2] == [
            "record_closed4_sala", jamba]
    for name in ("decode_step_ms", "decode_step_roofline"):
        assert metrics[name]["workloads"][:3] == OLDER[:2] + [jamba]
    for name in PR42S_TWO:
        assert metrics[name]["workloads"][:1] == [jamba]
    for name in ["sparse_blocks_read_share", *PR40S_EIGHT,
                 *PINNED_TO_RAG_CLOSED]:
        assert jamba not in metrics[name]["workloads"], name
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("prefill_mfu")
    assert names[at + 1:at + 9] == PR40S_EIGHT  # nothing of theirs moved
    assert names[at + 9:at + 11] == PR42S_TWO  # PR 42's two behind them
    ms, share = BENCH["per_layer"][at + 9:at + 11]
    assert ms == {
        "name": "prefill_scan_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels",
        "moves": "ttft_p50_ms", "workloads": [jamba]}
    assert share == {
        "name": "prefill_scan_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels",
        "moves": "ttft_p50_ms", "workloads": [jamba]}
    assert load(os.path.join(BENCH_DIR, "metrics", "prefill_scan_ms.json")) == {
        "reader": "scope_time",
        "params": {"program": "prefill", "per": 1, "scopes": ["state"]}}
    assert load(os.path.join(
        BENCH_DIR, "metrics", "prefill_scan_roofline.json"))["reader"] == (
        "scan_roofline")
    assert [w["name"] for w in BENCH["workloads"]][:4] == OLDER
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:4]) == 0


ASSUMED = (
    "check", "weights", "tokenizer", "four norms a layer", "closing norm a step",
    "cache entry a (step, layer)", "exit gate", "rotation", "attention",
    "step loop", "generate.kv_pool_tokens", "generate.max_concurrent",
    "max_position_embeddings", "generate.prefix_cache",
    "generate.speculative_k", "kv_cache_bits")


@pytest.mark.parametrize("key", ASSUMED)
def test_the_file_states_what_it_assumed(key):
    assert len(CONF["assumed"][key]) > 40, key


def test_the_file_states_three_limits():
    assert len(CONF["deployment"]) > 100
    assert "not evaluated" in CONF["assumed"]["exit gate"].lower()
    # this block does not route: three limits, from calibrate.py
    assert set(CONF["correct"]) == {
        "decoder_logit_rel_err", "kv_cache_bits_missing",
        "retrieval_score_err"}
    for name, limit in CONF["correct"].items():
        assert isinstance(limit, (int, float)), name
    assert CONF["correct"]["kv_cache_bits_missing"] == 0
    assert CONF["check"]["lane_rows"] % 128 == 0
    # the compared prompts and their two decode steps fit a lane's rows
    assert max(CONF["check"]["prompt_lengths"]) + 39 + 2 <= min(
        CONF["check"]["lane_rows"], CONF["max_position_embeddings"])


# ---- keys ---------------------------------------------------------------------

def test_every_published_key_is_mapped_fixed_or_ignored_by_name():
    mapped = set(KEYS.TO_DECODER) | {"layer_types"}
    assert set(PUBLISHED) == mapped | set(KEYS.FIXED) | set(KEYS.IGNORED)
    assert not mapped & set(KEYS.FIXED) and not mapped & set(KEYS.IGNORED)
    assert set(KEYS.IGNORED) == {"max_window_layers"}
    assert KEYS.FIXED["sliding_window"] is None
    out = KEYS.program_overrides(CONF)
    assert "decoder.block" not in out  # the GQA block, with three fields
    assert (out["decoder.loop_steps"], out["decoder.sandwich_norm"],
            out["decoder.loop_exit_threshold"]) == (4, True, 1.0)
    assert (out["decoder.hidden_dim"], out["decoder.mlp_dim"]) == (2048, 5632)
    assert (out["decoder.num_heads"], out["decoder.num_kv_heads"],
            out["decoder.head_dim"], out["decoder.num_layers"]) == (
        16, 16, 128, 48)
    assert out["decoder.max_seq_len"] == 512
    assert out["decoder.vocab_size"] == 49152
    assert out["decoder.norm_eps"] == 1e-06
    assert out["decoder.rope_theta"] == 1e6
    assert out["decoder.sliding_window"] is None
    assert out["decoder.dtype"] == "bfloat16"
    whole = child.program_overrides(CONF)
    assert whole["generate.max_concurrent"] == 4
    assert "decoder.quantize_weights" not in whole


@pytest.mark.parametrize("change, said", [
    ({"sliding_window": 4096}, '"sliding_window"'),
    ({"use_sliding_window": True}, '"use_sliding_window"'),
    ({"rope_scaling": {"type": "yarn"}}, '"rope_scaling"'),
    ({"hidden_act": "gelu"}, '"hidden_act"'),
    ({"model_type": "llama"}, '"model_type"'),
    ({"tie_word_embeddings": True}, '"tie_word_embeddings"'),
    ({"early_exit_threshold": 0.5}, '"early_exit_threshold"'),
    ({"layer_types": ["full_attention"] * 47 + ["sliding_attention"]},
     '"layer_types"'),
    ({"layer_types": ["full_attention"] * 47}, '"layer_types"'),
    ({"num_experts": 8}, '"num_experts"'),
    ({"exit_gate_bias": True}, '"exit_gate_bias"'),
])
def test_a_key_the_block_does_not_know_is_a_config_error(change, said):
    with pytest.raises(arch.ConfigError, match=said):
        KEYS.program_overrides({**CONF, **change})


@pytest.mark.parametrize("key", [
    "total_ut_steps", "early_exit_threshold", "head_dim", "rope_theta",
    "num_key_value_heads"])
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
        assert found <= {"__future__", "typing", "harness"}
    if module == "reference":
        assert "docqa_tpu" not in found
    assert found <= {"__future__", "typing", "harness", "functools", "jax",
                     "docqa_tpu"}
    assert _imports(os.path.join(PACKAGE_DIR, "__init__.py")) == set()


def test_the_cell_brings_no_reader():
    """``loop_passes_per_token`` is a data file over the reader that was
    there; the package has the harness's surface and nothing is missing."""
    assert sorted(f for f in os.listdir(os.path.join(BENCH_DIR, "readers"))
                  if "loop" in f) == []
    package = arch.load_shapes(CONF)
    assert callable(package.shapes.decode_step_min_bytes)
    assert callable(package.shapes.prefill_flops)


# ---- the bytes and the operations, by hand --------------------------------------

LAYER_MATS = 4 * 2048 * 2048 + 3 * 2048 * 5632


def test_the_parameters_by_hand():
    assert SHAPES.layer_matrices(CONF) == LAYER_MATS == 51_380_224
    assert SHAPES.layer_params(CONF) == LAYER_MATS + 4 * 2048 == 51_388_416
    assert 48 * SHAPES.layer_params(CONF) == 2_466_643_968
    assert SHAPES.parameters(CONF) == (
        2_466_643_968 + 2 * 100_663_296 + 2_048 + 2_049) == 2_667_974_657
    assert SHAPES.steps(CONF) == 4 and SHAPES.kv_entries(CONF) == 192
    assert SHAPES.kv_bytes_per_token(CONF) == 2 * 16 * 128 * 2 * 192 == (
        1_572_864)
    # the pool the file states: 4 slots x 512 positions
    assert CONF["serving"]["generate.kv_pool_tokens"] * 1_572_864 == (
        3_221_225_472)


def test_the_bytes_a_step_is_charged_with_by_hand():
    w = SHAPES.decoder_weight_bytes(CONF)
    assert w["layers"] == 2 * 2_466_643_968 == 4_933_287_936
    assert w["head"] == 2 * (49152 * 2048 + 2048)
    assert w["embedding"] == 2 * 49152 * 2048
    live = 4 * 356
    least = SHAPES.decode_step_min_bytes(CONF, live, 1)
    # the layers ONCE A PASS, the head once, every entry's live rows
    assert least == 4 * w["layers"] + w["head"] + live * 1_572_864
    assert 26.0 < 1e3 * least / 819e9 < 28.0  # the issue's ">= 27 ms"
    assert SHAPES.decode_step_min_bytes(CONF, live, 4) == least / 4
    # a plain model of these layers would be charged a quarter of it
    plain = dict(CONF, total_ut_steps=1)
    assert SHAPES.decode_step_min_bytes(plain, 0, 1) == (
        w["layers"] + w["head"])


def test_the_operations_of_a_prefill_by_hand():
    n = 330.0
    keys = n * (n + 1) / 2
    want = 4 * n * 2 * 48 * LAYER_MATS + (
        4 * 48 * 16 * 4 * 128 * keys + 2 * 49152 * 2048)
    assert SHAPES.prefill_flops(CONF, n, n) == pytest.approx(want)
    # 19.7 GFLOP a token in the matrices alone
    assert 4 * 2 * 48 * LAYER_MATS == pytest.approx(19.73e9, rel=1e-3)
    # twice the tokens in prompts of the same length: twice the work
    assert SHAPES.prefill_flops(CONF, 2 * n, n) == pytest.approx(2 * want)
    # the default length is the mean of the file's check block
    mean = sum(CONF["check"]["prompt_lengths"]) / 4
    assert SHAPES.prefill_flops(CONF, 300.0) == pytest.approx(
        SHAPES.prefill_flops(CONF, 300.0, mean))


# ---- the traffic: what the seed may not draw --------------------------------------

@pytest.mark.parametrize("seed", [1, 7, 99, 4295604013, 4295606029, 2**31 + 5])
def test_every_prompt_of_the_mix_fits_a_lane(seed):
    """Template + question + ANY three chunks of one size, hashed over
    THIS vocabulary: 3xx tokens in 384 packed rows, 64 new tokens inside
    512 positions."""
    from docqa_tpu.ops.attention import RAGGED_ALIGN
    from docqa_tpu.service.qa import QA_TEMPLATE
    from docqa_tpu.text.tokenizer import default_tokenizer

    tok = default_tokenizer(CONF["vocab_size"], vocab_path=None)
    templates = [t["text"] for t in load(os.path.join(
        BENCH_DIR, "questions", "generative.json"))["templates"]]
    chunks = [row["text_content"] for i in range(0, 2048, 7)
              for row in corpus.patient_chunks(seed, i)]
    rng = random.Random(seed)
    k = CONF["serving"]["store.default_k"]
    for trial in range(24):
        prompt = QA_TEMPLATE.format(
            context="\n\n".join(rng.sample(chunks, k)),
            question=corpus.question(
                seed, templates[trial % len(templates)], rng.randrange(2048)))
        n = len(tok.encode(prompt))
        assert 300 <= n <= 360
        assert n + CONF["serving"]["generate.max_new_tokens"] + 2 <= (
            CONF["max_position_embeddings"])
        assert -(-n // RAGGED_ALIGN) * RAGGED_ALIGN == 384


# ---- the metrics -----------------------------------------------------------------

def counters(**gained):
    return {"before": {"metrics": {"counters": dict.fromkeys(gained, 10)}},
            "after": {"metrics": {"counters": {
                k: 10 + v for k, v in gained.items()}}}}


def test_the_metrics_on_hand_made_counters():
    import run

    ctx = counters(
        serve_loop_passes=4 * 4 * 16 * 10, serve_loop_lane_steps=4 * 16 * 10,
        serve_prefill_tokens=4 * 330, serve_prefill_dispatches=4,
        serve_admitted=4)
    ctx.update(
        conf=CONF, cell={"chips": 1, "name": CELL_NAME},
        device={"kind": "TPU v5 lite"}, polled=[{"kv_tokens": 4 * 356}],
        trace={"programs": {
            "jit__prefill_program": {"count": 4, "median_s": 0.08},
            "jit__decode_program": {"count": 9, "median_s": 0.48}}})
    assert run.read_metric("loop_passes_per_token", ctx) == 4.0
    # the older ones the cell joined read this package's shapes
    assert run.read_metric("prefill_mfu", ctx) == pytest.approx(
        100 * SHAPES.prefill_flops(CONF, 330.0, 330.0) / (197e12 * 0.08))
    step = SHAPES.decode_step_min_bytes(CONF, 4 * 356, 1)
    assert run.read_metric("decode_step_ms", ctx) == pytest.approx(30.0)
    share = run.read_metric("decode_step_roofline", ctx)
    assert share == pytest.approx(100 * (step / 819e9) / 0.030)
    assert 85 < share < 95  # a 30 ms step of 27 ms of bytes


def test_under_a_program_without_the_counters_the_metric_is_left_out():
    """The parent commit has no ``serve_loop_passes`` (and cannot run the
    configuration); a plain trunk counts neither: the reader finds nothing
    to divide by, returns None, and nothing raises."""
    import run

    base = dict(conf=CONF, cell={"chips": 1, "name": CELL_NAME},
                device={"kind": "TPU v5 lite"})
    parent = dict(base, **counters(serve_prefill_tokens=900))
    assert run.read_metric("loop_passes_per_token", parent) is None
    assert run.read_metric(
        "loop_passes_per_token", dict(base, before={}, after={})) is None
    idle = dict(base, **counters(serve_loop_passes=0, serve_loop_lane_steps=0))
    assert run.read_metric("loop_passes_per_token", idle) is None


# ---- the cell, rehearsed on the CPU at tiny widths ---------------------------------

def test_the_cell_runs_end_to_end_at_tiny_widths():
    """``/ask/stream`` -> QAService -> EnginePool -> batcher -> the paged
    forwards of the looped trunk (two layers, four passes): rounds
    admitted together, three compared numbers, four passes a token in the
    result line."""
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
    assert 0 < out["compared"]["decoder_logit_rel_err"]["value"] < 0.05
    metrics = out["metrics"]
    assert metrics["loop_passes_per_token"]["value"] == 4.0
    # 4.0 on the chip and on an idle host; under the suite's six workers
    # a starved round may go without an arrival it was told to expect
    assert 1.0 < metrics["admit_batch_mean"]["value"] <= 4.0
    # device metrics: no CPU number under their names
    for name in ("prefill_mfu", "decode_step_ms", "decode_step_roofline",
                 "device_idle_share.gen"):
        assert name not in metrics
