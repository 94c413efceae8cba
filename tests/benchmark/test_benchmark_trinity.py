"""ISSUE 48: the configuration ``trinity-mini-ep8-bf16``, its architecture
package ``benchmark/architectures/afmoe/`` and the cell
``record_closed4_trinity`` — files and entries only; nothing that was
there is edited.

This file pins BENCHMARK.json by prefix and membership only (``[:n]``,
``in``, ``names.index``; never ``==`` on a whole list, a tail or a length),
the form ``test_benchmark_ouro.py`` uses, so that the next cell or metric
appended needs no mark in ``tests/conftest.py``."""

import ast
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import arch, child  # noqa: E402  (standard library)

DATA = os.path.join(HERE, "data")
OVERLAY = os.path.join(DATA, "tiny_overlay_trinity.json")
FILE = os.path.join(BENCH_DIR, "configs", "trinity-mini-ep8-bf16.json")
PACKAGE_DIR = os.path.join(BENCH_DIR, "architectures", "afmoe")
CONFIG_NAME = "trinity-mini-ep8-bf16"
CELL_NAME = "record_closed4_trinity"
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
CONF = load(FILE)
SHAPES = arch.load_shapes(CONF).shapes
KEYS = arch.load_shapes(CONF).keys

# the catalog row's ``config`` (the numbers of SOURCE), every key
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}
PUBLISHED["layer_types"] = PUBLISHED["layer_types"] * 8
REDUCED = {"num_experts": 16, "vocab_size": 25024,
           "max_position_embeddings": 9728}
OLDER = ["rag_closed", "rag_closed8_dsv2", "record_closed4_sala",
         "record_closed4_jamba2", "rag_closed_ouro"]


# ---- the file and the entries ------------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_each_published_key(key):
    assert key in CONF
    assert CONF[key] == REDUCED.get(key, PUBLISHED[key])


def test_the_entry_names_the_source_and_its_three_cuts():
    names = [c["name"] for c in BENCH["configs"]]
    assert names[:6] == [
        "mistral-7b-int8", "deepseek-v2-ep4-bf16", "minicpm-sala-int8",
        "jamba2-3b-bf16", "ouro-2.6b-bf16", CONFIG_NAME]
    entry = BENCH["configs"][names.index(CONFIG_NAME)]
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/trinity-mini-ep8-bf16.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200
    # no width and not the depth
    assert not {"num_hidden_layers", "hidden_size", "head_dim",
                "moe_intermediate_size", "intermediate_size",
                "num_experts_per_tok", "sliding_window"} & set(
        entry["reduced"])
    assert CONF["architecture"] == "afmoe" and CONF["chips"] == 1
    assert CONF["torch_dtype"] == "bfloat16" and CONF["kv_cache_bits"] == 16
    assert (CONF["router_experts"], CONF["experts_held_start"]) == (128, 0)
    assert "one of eight" in CONF["deployment"].lower() or (
        "EIGHT CHIPS SHARE EACH LAYER" in CONF["deployment"])


def test_the_cell_is_the_issues():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[:6] == OLDER + [CELL_NAME]
    cell = BENCH["workloads"][cells.index(CELL_NAME)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "record_closed4", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200
    # the traffic file that was there, as record_closed4_jamba2 uses it
    jamba = BENCH["workloads"][cells.index("record_closed4_jamba2")]
    assert jamba["traffic"] == cell["traffic"]
    traffic = load(os.path.join(BENCH_DIR, "traffic", "record_closed4.json"))
    assert (traffic["clients"], traffic["endpoint"], traffic["lockstep"],
            traffic["warm_bursts"], traffic["trace_s"]) == (
        4, "/ask/stream", True, [1, 2, 3, 4, 1], 6)
    serving = CONF["serving"]
    older = load(os.path.join(
        BENCH_DIR, "configs", "jamba2-3b-bf16.json"))
    assert serving["generate.max_concurrent"] == traffic["clients"] == 4
    assert serving["generate.kv_pool_tokens"] == 38912 == (
        4 * CONF["max_position_embeddings"])
    assert serving["generate.admit_hold_ms"] == 0.0
    assert (serving["generate.max_new_tokens"],
            serving["generate.decode_chunk"],
            serving["generate.prefill_token_buckets"],
            serving["store.default_k"],
            serving["resilience.request_deadline_s"],
            serving["generate.speculative_k"],
            serving["generate.prefix_cache"]) == (
        128, 16, [9728], 112, 30.0, 0, False)
    # the rest as jamba2-3b-bf16, setting for setting
    for key, value in older["serving"].items():
        assert serving[key] == value, key
    assert set(serving) == set(older["serving"]) | {"generate.admit_hold_ms"}
    assert CONF["corpus"] == older["corpus"]
    assert CONF["check"] == {
        "prompt_lengths": [9000, 9050, 9100, 9150], "lane_rows": 9472}


def _metrics():
    return {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}


ON_ALL_FIVE = [
    "ttft_p50_ms", "tpot_p50_ms", "window_tok_s", "retrieve_mean_ms.gen",
    "admit_wait_p50_ms", "decode_batch_mean", "kv_pool_used_share",
    "spine_wait_mean_ms", "device_idle_share.gen",
    "first_token_wait_p50_ms", "admit_drain_mean_ms", "admit_batch_mean",
    "prefill_pad_share", "decode_tokens_per_chunk",
    "decode_stale_chunk_share"]
ALSO_JOINED = [
    "decode_step_ms", "decode_step_roofline", "prefill_mfu",
    "moe_local_pick_share", "moe_experts_touched_per_layer_step",
    "decode_touched_roofline"]
PR40S_EIGHT = [
    "decode_attention_ms", "decode_projection_ms", "decode_mlp_ms",
    "decode_head_ms", "decode_other_ms", "prefill_attention_ms",
    "prefill_mlp_ms", "ask_lane_wait_p50_ms"]
NOT_JOINED = [
    *PR40S_EIGHT, "prefill_ahead_share", "prefix_hit_share",
    "prefill_dispatches_per_round", "decode_kv_read_amplification",
    "sparse_blocks_read_share", "lane_state_share_of_step_bytes",
    "prefill_scan_ms", "prefill_scan_roofline", "loop_passes_per_token"]


@pytest.mark.parametrize("name", ON_ALL_FIVE + ALSO_JOINED)
def test_the_cell_joined_the_list_behind_the_cells_that_were_there(name):
    cells = _metrics()[name]["workloads"]
    at = cells.index(CELL_NAME)
    assert cells[:at] == [c for c in OLDER if c in cells[:at]]  # their order
    assert at >= 1 and set(cells[:at]) <= set(OLDER)
    if name in ON_ALL_FIVE:
        assert cells[:5] == OLDER
    if name.startswith("moe_") or name == "decode_touched_roofline":
        assert cells[:1] == ["rag_closed8_dsv2"]  # the first place held


@pytest.mark.parametrize("name", NOT_JOINED)
def test_the_lists_the_cell_did_not_join(name):
    """Nothing selects, scans, loops or keeps a float32 lane state here;
    PR 40's eight and the four pinned to ``["rag_closed"]`` wait for the
    `benchmark` PR that loosens their pins (PERF.md section 7)."""
    assert CELL_NAME not in _metrics()[name]["workloads"]


def test_the_two_new_metrics_stand_behind_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    read, held = (names.index("window_rows_read_share"),
                  names.index("window_kv_held_share"))
    assert names.index("loop_passes_per_token") < read < held
    assert BENCH["per_layer"][read] == {
        "name": "window_rows_read_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Kernels",
        "moves": "tpot_p50_ms", "workloads": [CELL_NAME]}
    assert BENCH["per_layer"][held] == {
        "name": "window_kv_held_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Paged KV",
        "moves": "tpot_p50_ms", "workloads": [CELL_NAME]}
    for name, numerator in (
            ("window_rows_read_share", "serve_window_kv_rows_read"),
            ("window_kv_held_share", "serve_window_kv_rows_held")):
        assert load(os.path.join(BENCH_DIR, "metrics", name + ".json")) == {
            "reader": "counter_ratio", "params": {
                "numerator": [numerator],
                "denominator": ["serve_window_kv_rows_live"],
                "scale": 100.0}}
    assert "workloads" not in _metrics()["setup_s"]  # every cell reports it
    # no bound, no count of runs moved, no four-chip cell
    assert [m["bound"] for m in BENCH["end_to_end"]][:3] == [0.01, 0.01, 0.1]
    assert BENCH["run_seconds"] == 30
    assert all(w["chips"] == 1 for w in BENCH["workloads"][:6])
    assert len(set(names)) == len(names)


def test_the_cell_brings_data_only():
    """Two metric files over the reader that was there, one configuration,
    one package; no reader, no traffic file, no harness file."""
    assert sorted(f for f in os.listdir(os.path.join(BENCH_DIR, "readers"))
                  if "window" in f) == []
    assert "record_closed4_trinity.json" not in os.listdir(
        os.path.join(BENCH_DIR, "traffic"))
    package = arch.load_shapes(CONF)
    for fn in ("decode_step_min_bytes", "decode_step_touched_bytes",
               "prefill_flops", "kv_bytes_per_token"):
        assert callable(getattr(package.shapes, fn)), fn


ASSUMED = (
    "weights", "routers", "expert_bias", "four norms a layer",
    "q and k norms", "output gate", "rotation", "window", "router",
    "embedding scale", "num_experts", "vocab_size",
    "max_position_embeddings", "num_hidden_layers", "tokenizer", "kernels",
    "generate.kv_pool_tokens", "generate.max_concurrent",
    "generate.prefix_cache", "generate.speculative_k", "kv_cache_bits",
    "check")


@pytest.mark.parametrize("key", ASSUMED)
def test_the_file_states_what_it_assumed(key):
    assert len(CONF["assumed"][key]) > 40, key


def test_the_file_states_four_limits():
    assert len(CONF["deployment"]) > 100
    # what the row's config does not spell names ISSUE 48's sentence
    for key in ("four norms a layer", "q and k norms", "output gate",
                "rotation", "router", "expert_bias"):
        assert "ISSUE 48" in CONF["assumed"][key], key
    assert "0.25 (weights.EXPERT_BIAS_STD)" in CONF["assumed"]["expert_bias"]
    assert set(CONF["correct"]) == {
        "decoder_logit_rel_err", "kv_cache_bits_missing",
        "retrieval_score_err", "router_choice_gap"}
    for name, limit in CONF["correct"].items():
        assert isinstance(limit, (int, float)), name
    assert CONF["correct"]["kv_cache_bits_missing"] == 0
    assert CONF["check"]["lane_rows"] % 128 == 0
    assert max(CONF["check"]["prompt_lengths"]) + 39 + 2 <= min(
        CONF["check"]["lane_rows"], CONF["max_position_embeddings"])
    # every compared row is past the window
    assert min(CONF["check"]["prompt_lengths"]) > 4 * CONF["sliding_window"]


# ---- keys ---------------------------------------------------------------------

def test_every_published_key_is_mapped_fixed_or_ignored_by_name():
    mapped = (set(KEYS.TO_DECODER) | {"layer_types"}) - {
        "router_experts", "experts_held_start"}
    assert set(PUBLISHED) == mapped | set(KEYS.FIXED) | set(KEYS.IGNORED)
    assert not mapped & set(KEYS.FIXED) and not mapped & set(KEYS.IGNORED)
    out = KEYS.program_overrides(CONF)
    assert out["decoder.block"] == "sparse_linear"
    assert out["decoder.mixer_types"] == (
        "window", "window", "window", "attention") * 8
    assert (out["decoder.hidden_dim"], out["decoder.mlp_dim"],
            out["decoder.expert_dim"]) == (2048, 6144, 1024)
    assert (out["decoder.num_heads"], out["decoder.num_kv_heads"],
            out["decoder.head_dim"], out["decoder.num_layers"]) == (
        32, 4, 128, 32)
    assert (out["decoder.num_experts"], out["decoder.experts_held"],
            out["decoder.experts_held_start"],
            out["decoder.experts_per_token"],
            out["decoder.num_shared_experts"],
            out["decoder.first_dense_layers"]) == (128, 16, 0, 8, 1, 2)
    assert (out["decoder.router_score"], out["decoder.router_norm"],
            out["decoder.router_bias"], out["decoder.routed_scale"]) == (
        "sigmoid", True, True, 2.826)
    assert (out["decoder.expert_groups"],
            out["decoder.expert_groups_per_token"]) == (1, 1)
    assert out["decoder.sliding_window"] == 2048
    assert out["decoder.max_seq_len"] == 9728
    assert out["decoder.vocab_size"] == 25024
    assert out["decoder.norm_eps"] == 1e-05
    assert (out["decoder.qk_norm"], out["decoder.use_output_gate"],
            out["decoder.use_output_norm"], out["decoder.sandwich_norm"]) == (
        True, True, False, True)
    assert out["decoder.scale_emb"] == 2048 ** 0.5
    whole = child.program_overrides(CONF)
    assert whole["generate.max_concurrent"] == 4


@pytest.mark.parametrize("change, said", [
    ({"rope_scaling": {"type": "yarn"}}, '"rope_scaling"'),
    ({"hidden_act": "gelu"}, '"hidden_act"'),
    ({"model_type": "llama"}, '"model_type"'),
    ({"tie_word_embeddings": True}, '"tie_word_embeddings"'),
    ({"mup_enabled": False}, '"mup_enabled"'),
    ({"num_expert_groups": 4}, '"num_expert_groups"'),
    ({"layer_types": ["full_attention"] * 31 + ["chunked_attention"]},
     '"layer_types"'),
    ({"layer_types": ["full_attention"] * 31}, '"layer_types"'),
    ({"attention_bias": True}, '"attention_bias"'),
])
def test_a_key_the_block_does_not_know_is_a_config_error(change, said):
    with pytest.raises(arch.ConfigError, match=said):
        KEYS.program_overrides({**CONF, **change})


def test_a_program_without_the_fields_is_refused_by_the_architectures_name(
        monkeypatch):
    """The parent commit's ``DecoderConfig`` has no ``router_score``: the
    cell fails there at once, with an error that names the architecture."""
    monkeypatch.setattr(
        KEYS, "_program_fields",
        lambda: set(KEYS.TO_DECODER.values()) - {"router_score",
                                                 "router_norm"})
    with pytest.raises(arch.ConfigError, match='architecture "afmoe"'):
        KEYS.program_overrides(CONF)
    monkeypatch.undo()
    assert set(KEYS.TO_DECODER.values()) <= KEYS._program_fields()


@pytest.mark.parametrize("key", [
    "router_experts", "experts_held_start", "sliding_window", "score_func",
    "route_scale", "num_dense_layers", "layer_types", "head_dim"])
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
    if module == "shapes":
        assert found <= {"__future__", "typing", "."}
    if module == "keys":
        # the program's config module (standard library alone, imported
        # inside a function, tolerated absent): the check that a program
        # without this PR's fields is refused BY THE ARCHITECTURE'S NAME
        assert found <= {"__future__", "dataclasses", "math", "harness",
                         "docqa_tpu"}
    if module == "reference":
        assert "docqa_tpu" not in found
    assert found <= {"__future__", "typing", "harness", "functools", "jax",
                     "numpy", "math", "dataclasses", ".", "docqa_tpu"}
    assert _imports(os.path.join(PACKAGE_DIR, "__init__.py")) == set()


# ---- the bytes and the operations, by hand --------------------------------------

ATTENTION = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 4 * 2048
EXPERT = 3 * 2048 * 1024


def test_the_parameters_by_hand():
    assert SHAPES.attention_params(CONF) == ATTENTION == 27_271_424
    assert SHAPES.dense_mlp_params(CONF) == 37_748_736
    assert SHAPES.expert_params(CONF) == EXPERT == 6_291_456
    assert SHAPES.router_params(CONF) == 262_272
    assert SHAPES.routed_layer_params(CONF) == 134_488_448
    assert SHAPES.routed_layer_params(CONF, 128) == 839_131_520
    assert ATTENTION + 37_748_736 == 65_020_160
    assert SHAPES.parameters(CONF) == 4_267_194_112
    assert SHAPES.parameters(CONF, experts=128, vocab=200_192) == (
        26_123_974_400)
    assert SHAPES.kv_row_bytes(CONF) == 2048
    assert SHAPES.kv_bytes_per_token(CONF) == 8 * 2048


def test_the_bytes_a_step_is_charged_with_by_hand():
    weights = SHAPES.non_expert_weight_bytes(CONF)
    by_hand = 2 * (
        32 * ATTENTION + 2 * 37_748_736 + 30 * (262_272 + EXPERT)
        + 2048 + 2048 * 25024) + 2 * 30 * 128  # the bias is float32
    assert weights == by_hand
    assert 2.38e9 < weights < 2.40e9  # the issue's 2.39 GB
    live = 4 * 9200
    least = SHAPES.decode_step_min_bytes(CONF, live, 1)
    # global layers the live rows, window layers min(live, lanes x window),
    # and NO expert
    assert least == weights + (8 * live + 24 * 4 * 2048) * 2048
    assert SHAPES.window_rows(CONF, 100) == 100  # a lane under its window
    assert SHAPES.decode_step_min_bytes(CONF, live, 4) == least / 4
    touched = SHAPES.decode_step_touched_bytes(CONF, live, 3.6, 1)
    assert touched == pytest.approx(least + 30 * 3.6 * 2 * EXPERT)
    assert 1.3e9 < touched - least < 1.4e9  # the issue's ~1.37 GB
    # unwindowed, the K/V read would be 2.4 GB of the step
    assert 32 * live * 2048 == pytest.approx(2.41e9, rel=1e-2)
    assert (8 * live + 24 * 4 * 2048) * 2048 == pytest.approx(1.0e9, rel=2e-2)


def test_the_operations_of_a_prefill_by_hand():
    n = 9100.0
    mats = ATTENTION - 2 * 128 - 4 * 2048
    a_token = 2 * (32 * mats + 2 * 37_748_736
                   + 30 * (2048 * 128 + (1 + 8 * 16 / 128) * EXPERT))
    w = 2048
    a_prompt = 4 * 128 * 32 * (
        8 * n * (n + 1) / 2 + 24 * (w * n - w * (w - 1) / 2)
    ) + 2 * 25024 * 2048
    assert SHAPES.prefill_flops(CONF, n, n) == pytest.approx(
        n * a_token + a_prompt)
    # ~4 GFLOP a prompt token, the window layers at ~40 % of causal
    assert SHAPES.prefill_flops(CONF, n, n) / n == pytest.approx(
        3.98e9, rel=2e-2)
    assert (w * n - w * (w - 1) / 2) / (n * (n + 1) / 2) == pytest.approx(
        0.40, abs=0.01)
    assert SHAPES.prefill_flops(CONF, 2 * n, n) == pytest.approx(
        2 * (n * a_token + a_prompt))
    # a prompt under the window is charged causal attention in every layer
    short = SHAPES.prefill_flops(CONF, 300.0, 300.0)
    assert short == pytest.approx(
        300 * a_token + 4 * 128 * 32 * 32 * 300 * 301 / 2
        + 2 * 25024 * 2048)
    mean = sum(CONF["check"]["prompt_lengths"]) / 4
    assert SHAPES.prefill_flops(CONF, 300.0) == pytest.approx(
        SHAPES.prefill_flops(CONF, 300.0, mean))


# ---- the metrics -----------------------------------------------------------------

def counters(**gained):
    return {"before": {"metrics": {"counters": dict.fromkeys(gained, 10)}},
            "after": {"metrics": {"counters": {
                k: 10 + v for k, v in gained.items()}}}}


def test_the_metrics_on_hand_made_counters():
    import run

    ctx = counters(
        serve_window_kv_rows_read=2340, serve_window_kv_rows_held=2064,
        serve_window_kv_rows_live=9200,
        serve_moe_picks=32 * 30 * 16, serve_moe_picks_local=4 * 30 * 16,
        serve_moe_experts_touched=36 * 30 * 16 // 10,
        serve_moe_layer_steps=30 * 16,
        serve_prefill_tokens=4 * 9100, serve_prefill_dispatches=4,
        serve_admitted=4)
    ctx.update(
        conf=CONF, cell={"chips": 1, "name": CELL_NAME},
        device={"kind": "TPU v5 lite"}, polled=[{"kv_tokens": 4 * 9200}],
        trace={"programs": {
            "jit__prefill_program": {"count": 4, "median_s": 0.5},
            "jit__decode_program": {"count": 9, "median_s": 0.16}}})
    assert run.read_metric("window_rows_read_share", ctx) == pytest.approx(
        100 * 2340 / 9200)
    assert run.read_metric("window_kv_held_share", ctx) == pytest.approx(
        100 * 2064 / 9200)
    assert run.read_metric("moe_local_pick_share", ctx) == 12.5
    assert run.read_metric(
        "moe_experts_touched_per_layer_step", ctx) == pytest.approx(3.6)
    # the older ones the cell joined read this package's shapes
    assert run.read_metric("prefill_mfu", ctx) == pytest.approx(
        100 * SHAPES.prefill_flops(CONF, 9100.0, 9100.0) / (197e12 * 0.5))
    assert run.read_metric("decode_step_ms", ctx) == pytest.approx(10.0)
    step = SHAPES.decode_step_min_bytes(CONF, 4 * 9200, 1)
    assert run.read_metric("decode_step_roofline", ctx) == pytest.approx(
        100 * (step / 819e9) / 0.010)
    touched = SHAPES.decode_step_touched_bytes(CONF, 4 * 9200, 3.6, 1)
    assert run.read_metric("decode_touched_roofline", ctx) == pytest.approx(
        100 * (touched / 819e9) / 0.010)


def test_under_a_program_without_the_counters_the_metrics_are_left_out():
    """The parent commit has no ``serve_window_kv_rows_*`` (and cannot run
    the configuration): the reader finds nothing to divide by, returns
    None, and nothing raises."""
    import run

    base = dict(conf=CONF, cell={"chips": 1, "name": CELL_NAME},
                device={"kind": "TPU v5 lite"})
    parent = dict(base, **counters(serve_prefill_tokens=900))
    for name in ("window_rows_read_share", "window_kv_held_share"):
        assert run.read_metric(name, parent) is None
        assert run.read_metric(name, dict(base, before={}, after={})) is None
    idle = dict(base, **counters(
        serve_window_kv_rows_read=0, serve_window_kv_rows_held=0,
        serve_window_kv_rows_live=0))
    assert run.read_metric("window_rows_read_share", idle) is None


# ---- the cell, rehearsed on the CPU at tiny widths ---------------------------------

def test_the_cell_runs_end_to_end_at_tiny_widths():
    """``/ask/stream`` -> QAService -> EnginePool -> batcher -> the paged
    forwards of the stack (window and global layers, a leading dense layer
    and five routed): rounds admitted together, four compared numbers, the
    two window shares and the routed layer's in the result line."""
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
    assert 0 < out["compared"]["decoder_logit_rel_err"]["value"] < 0.05
    assert 0 <= out["compared"]["router_choice_gap"]["value"] < 0.05
    metrics = out["metrics"]
    # ~550-token prompts under a 96-row window: a ring of 7 pages of 16
    assert 15 < metrics["window_kv_held_share"]["value"] < 30
    # a CPU: the XLA form gathers every table's span (1,024 positions)
    assert metrics["window_rows_read_share"]["value"] > 100
    # 4 of 16 experts held: a quarter of the picks, the routers level
    assert 15 < metrics["moe_local_pick_share"]["value"] < 35
    assert 0 < metrics["moe_experts_touched_per_layer_step"]["value"] <= 4
    assert 1.0 < metrics["admit_batch_mean"]["value"] <= 4.0
    # device metrics: no CPU number under their names
    for name in ("prefill_mfu", "decode_step_ms", "decode_step_roofline",
                 "decode_touched_roofline", "device_idle_share.gen"):
        assert name not in metrics
