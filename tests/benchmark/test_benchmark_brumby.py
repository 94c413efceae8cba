"""ISSUE 51: the configuration ``brumby-14b-l12-int8``, its architecture
package ``benchmark/architectures/brumby/``, two readers, three metric
files and the cell ``record_closed4_brumby`` — files and entries only;
nothing that was there is edited.  Lists and tails are pinned by prefix
and membership, so that the next cell needs no mark in ``conftest.py``."""

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
OVERLAY = os.path.join(DATA, "tiny_overlay_brumby.json")
FILE = os.path.join(BENCH_DIR, "configs", "brumby-14b-l12-int8.json")
PACKAGE_DIR = os.path.join(BENCH_DIR, "architectures", "brumby")
CONFIG_NAME = "brumby-14b-l12-int8"
CELL_NAME = "record_closed4_brumby"
SOURCE = ("https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
          "config.json")


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
CONF = load(FILE)
SHAPES = arch.load_shapes(CONF).shapes
KEYS = arch.load_shapes(CONF).keys

# the catalog row's ``config`` (the numbers of SOURCE), every key
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 12, "max_position_embeddings": 9728}
OLDER = ["rag_closed", "rag_closed8_dsv2", "record_closed4_sala",
         "record_closed4_jamba2", "rag_closed_ouro", "record_closed4_trinity"]


# ---- the file and the entries -----------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_each_published_key(key):
    assert key in CONF
    assert CONF[key] == REDUCED.get(key, PUBLISHED[key])


def test_the_entry_names_the_source_and_exactly_two_cuts():
    names = [c["name"] for c in BENCH["configs"]]
    assert names[:7] == [
        "mistral-7b-int8", "deepseek-v2-ep4-bf16", "minicpm-sala-int8",
        "jamba2-3b-bf16", "ouro-2.6b-bf16", "trinity-mini-ep8-bf16",
        CONFIG_NAME]
    entry = BENCH["configs"][names.index(CONFIG_NAME)]
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/brumby-14b-l12-int8.json"
    assert entry["reduced"] == ["num_hidden_layers",
                                "max_position_embeddings"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200
    # every width, all 40 / 8 heads and the whole vocabulary as published
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "vocab_size"):
        assert CONF[key] == PUBLISHED[key] and key not in entry["reduced"]
    assert CONF["architecture"] == "brumby" and CONF["chips"] == 1
    assert CONF["torch_dtype"] == "bfloat16"
    assert CONF["weight_quantization"] == "int8"
    assert CONF["kv_cache_bits"] == 32  # the state IS the narrowest array
    assert "12 of its 40 layers" in CONF["deployment"]
    assert "pipeline stages" in CONF["deployment"]


def test_the_cell_is_the_issues():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[:7] == OLDER + [CELL_NAME]
    cell = BENCH["workloads"][cells.index(CELL_NAME)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "record_closed4", 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200
    traffic = load(os.path.join(BENCH_DIR, "traffic", "record_closed4.json"))
    assert (traffic["clients"], traffic["endpoint"], traffic["lockstep"],
            traffic["warm_bursts"], traffic["trace_s"]) == (
        4, "/ask/stream", True, [1, 2, 3, 4, 1], 6)
    serving = CONF["serving"]
    jamba = load(os.path.join(BENCH_DIR, "configs", "jamba2-3b-bf16.json"))
    sala = load(os.path.join(BENCH_DIR, "configs", "minicpm-sala-int8.json"))
    assert serving["generate.max_concurrent"] == traffic["clients"] == 4
    assert serving["generate.kv_pool_tokens"] == 38912 == (
        4 * CONF["max_position_embeddings"])
    assert "generate.admit_hold_ms" not in serving
    # as jamba2-3b-bf16's, setting for setting, plus the int8 weights
    for key, value in jamba["serving"].items():
        assert serving[key] == value, key
    assert set(serving) == set(jamba["serving"]) | {
        "decoder.quantize_weights", "decoder.quant_bits"}
    assert (serving["decoder.quantize_weights"],
            serving["decoder.quant_bits"]) == (True, 8)
    assert CONF["corpus"] == sala["corpus"] == jamba["corpus"]
    assert CONF["check"] == {
        "prompt_lengths": [9000, 9050, 9100, 9150], "lane_rows": 9472}


def _metrics():
    return {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}


ON_ALL_SIX = [
    "ttft_p50_ms", "tpot_p50_ms", "window_tok_s", "retrieve_mean_ms.gen",
    "admit_wait_p50_ms", "decode_batch_mean", "kv_pool_used_share",
    "spine_wait_mean_ms", "device_idle_share.gen",
    "first_token_wait_p50_ms", "admit_drain_mean_ms", "admit_batch_mean",
    "prefill_pad_share", "decode_tokens_per_chunk",
    "decode_stale_chunk_share"]
ALSO_JOINED = [
    "lane_state_share_of_step_bytes", "prefill_mfu", "decode_step_ms",
    "decode_step_roofline"]
NEW_METRICS = ["decode_retention_ms", "decode_retention_roofline",
               "prefill_retention_roofline"]
PR40S_EIGHT = [
    "decode_attention_ms", "decode_projection_ms", "decode_mlp_ms",
    "decode_head_ms", "decode_other_ms", "prefill_attention_ms",
    "prefill_mlp_ms", "ask_lane_wait_p50_ms"]
NOT_JOINED = [
    *PR40S_EIGHT, "prefill_ahead_share", "prefix_hit_share",
    "prefill_dispatches_per_round", "decode_kv_read_amplification",
    "sparse_blocks_read_share", "prefill_scan_ms", "prefill_scan_roofline",
    "loop_passes_per_token", "moe_local_pick_share",
    "moe_experts_touched_per_layer_step", "decode_touched_roofline",
    "window_rows_read_share", "window_kv_held_share"]


@pytest.mark.parametrize("name", ON_ALL_SIX + ALSO_JOINED)
def test_the_cell_joined_the_list_behind_the_cells_that_were_there(name):
    cells = _metrics()[name]["workloads"]
    at = cells.index(CELL_NAME)
    assert cells[:at] == [c for c in OLDER if c in cells[:at]]  # their order
    assert at >= 1 and set(cells[:at]) <= set(OLDER)
    if name in ON_ALL_SIX:
        assert cells[:6] == OLDER
    if name in ("lane_state_share_of_step_bytes", "prefill_mfu"):
        assert cells[:1] == ["record_closed4_sala"]  # the first place held


@pytest.mark.parametrize("name", NOT_JOINED)
def test_the_lists_the_cell_did_not_join(name):
    """Nothing attends, selects, routes, loops or keeps a window here; the
    scan is bound by arithmetic, not by the bytes ``prefill_scan_roofline``
    charges; ``prefill_scan_ms`` (ISSUE 51 asked for it) is held to
    ``["record_closed4_jamba2"]`` by EQUALITY in ``test_benchmark_ouro.py``,
    as PR 40's eight and the four pinned to ``["rag_closed"]`` are in
    theirs: all wait for the `benchmark` PR that loosens those pins
    (PERF.md section 7)."""
    assert CELL_NAME not in _metrics()[name]["workloads"]


def test_the_three_new_metrics_stand_behind_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = [names.index(n) for n in NEW_METRICS]
    assert names.index("window_kv_held_share") < at[0]
    assert at == [at[0], at[0] + 1, at[0] + 2]
    want = [("ms", "lower", "tpot_p50_ms"), ("%", "higher", "tpot_p50_ms"),
            ("%", "higher", "ttft_p50_ms")]
    for i, (unit, better, moves) in zip(at, want):
        assert BENCH["per_layer"][i] == {
            "name": names[i], "unit": unit, "better": better,
            "source": "device_trace", "layer": "Kernels", "moves": moves,
            "workloads": [CELL_NAME]}
    decode = {"program": "decode", "exclude": "prefill",
              "per": "generate.decode_chunk", "scopes": ["state"]}
    assert load(os.path.join(
        BENCH_DIR, "metrics", "decode_retention_ms.json")) == {
        "reader": "scope_time", "params": decode}
    assert load(os.path.join(
        BENCH_DIR, "metrics", "decode_retention_roofline.json")) == {
        "reader": "state_step_roofline", "params": decode}
    assert load(os.path.join(
        BENCH_DIR, "metrics", "prefill_retention_roofline.json")) == {
        "reader": "scan_flops_roofline",
        "params": {"program": "prefill", "scopes": ["state"]}}
    assert "workloads" not in _metrics()["setup_s"]  # every cell reports it
    # no bound, no count of runs moved, no four-chip cell
    assert [m["bound"] for m in BENCH["end_to_end"]][:3] == [0.01, 0.01, 0.1]
    assert BENCH["run_seconds"] == 30
    assert all(w["chips"] == 1 for w in BENCH["workloads"][:7])
    assert len(set(names)) == len(names)


def test_what_the_schemas_pin_held_still_holds():
    """``test_benchmark_schema.py::test_configuration`` holds every file to
    ``kv_cache_bits == 16``; this one states 32, as ISSUE 51 asked
    (tests/conftest.py marks that one id, strictly).  Every other line of
    it, for this configuration."""
    config = {c["name"]: c for c in BENCH["configs"]}[CONFIG_NAME]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["source"].startswith("https://")
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == CONFIG_NAME for w in BENCH["workloads"])
    for key in ("architecture", "serving", "corpus", "assumed", "deployment",
                "check", "correct", "chips"):
        assert key in CONF, key
    block = arch.load_shapes(CONF)
    assert set(block.keys.program_overrides(CONF)) >= {"decoder.dtype"}
    assert set(CONF["check"]) == {"prompt_lengths", "lane_rows"}
    assert CONF["check"]["lane_rows"] % 128 == 0
    assert max(CONF["check"]["prompt_lengths"]) < CONF["check"]["lane_rows"]
    assert set(CONF["correct"]) == {
        "decoder_logit_rel_err", "kv_cache_bits_missing",
        "retrieval_score_err"}
    assert CONF["correct"]["kv_cache_bits_missing"] == 0
    assert (CONF["corpus"]["patients"] * CONF["corpus"]["chunks_per_patient"]
            <= CONF["corpus"]["rows"])
    assert CONF["corpus"]["patients"] <= 4096


ASSUMED = (
    "weights", "tokenizer", "weight_quantization", "degree", "gate",
    "normaliser", "scale", "q and k norms", "output", "lane state",
    "retention precision", "kernels", "num_hidden_layers",
    "max_position_embeddings", "generate.max_concurrent",
    "generate.kv_pool_tokens", "generate.prefix_cache",
    "generate.speculative_k", "kv_cache_bits")


@pytest.mark.parametrize("key", ASSUMED)
def test_the_file_states_what_it_assumed(key):
    assert len(CONF["assumed"][key]) > 20


def test_the_file_states_three_limits():
    correct = CONF["correct"]
    assert 0.01 < correct["decoder_logit_rel_err"] < 0.5
    assert correct["retrieval_score_err"] == 1e-05
    assert correct["kv_cache_bits_missing"] == 0
    tiny = load(OVERLAY)
    assert set(tiny["correct"]) <= set(correct)


# ---- keys -------------------------------------------------------------------

def test_every_published_key_is_mapped_fixed_or_ignored_by_name():
    mapped = set(KEYS.TO_DECODER)
    assert set(PUBLISHED) == mapped | set(KEYS.FIXED) | set(KEYS.IGNORED)
    assert not mapped & set(KEYS.FIXED) and not mapped & set(KEYS.IGNORED)
    assert set(KEYS.IGNORED) == {"max_window_layers"}
    assert KEYS.FIXED["sliding_window"] is None
    assert KEYS.FIXED["use_sliding_window"] is False
    out = KEYS.program_overrides(CONF)
    assert out["decoder.block"] == "sparse_linear"
    assert out["decoder.mixer_types"] == ("retention",) * 12
    assert (out["decoder.hidden_dim"], out["decoder.mlp_dim"]) == (5120, 17408)
    assert (out["decoder.num_heads"], out["decoder.num_kv_heads"],
            out["decoder.head_dim"]) == (40, 8, 128)
    assert out["decoder.rope_theta"] == 1e6
    assert out["decoder.tie_embeddings"] is False
    assert out["decoder.max_seq_len"] == 9728
    assert out["decoder.vocab_size"] == 151936
    assert (out["decoder.qk_norm"], out["decoder.use_output_gate"],
            out["decoder.use_output_norm"]) == (True, False, False)
    whole = child.program_overrides(CONF)
    assert whole["generate.max_concurrent"] == 4
    assert whole["decoder.quantize_weights"] is True
    # no field that picks an implementation: every override is a published
    # key's, the kind's name, or one of the trunk's three switches
    assert set(out) == {f"decoder.{f}" for f in KEYS.TO_DECODER.values()} | {
        "decoder.mixer_types", "decoder.block", "decoder.qk_norm",
        "decoder.use_output_gate", "decoder.use_output_norm",
        "decoder.dtype"}


@pytest.mark.parametrize("change, said", [
    ({"sliding_window": 4096}, '"sliding_window"'),
    ({"use_sliding_window": True}, '"use_sliding_window"'),
    ({"attention_bias": True}, '"attention_bias"'),
    ({"hidden_act": "gelu"}, '"hidden_act"'),
    ({"model_type": "qwen3"}, '"model_type"'),
    ({"rope_scaling": {"type": "yarn"}}, '"rope_scaling"'),
    ({"num_attention_heads": 36}, '"num_attention_heads"'),
    ({"head_dim": 127}, '"head_dim"'),
    ({"retention_degree": 4}, '"retention_degree"'),
    ({"weight_quantization": "int4"}, '"weight_quantization"'),
])
def test_a_key_the_block_does_not_know_is_a_config_error(change, said):
    with pytest.raises(arch.ConfigError, match=said):
        KEYS.program_overrides({**CONF, **change})


def test_a_program_without_the_kind_refuses_the_name_the_package_asks_for():
    """The package asks the program for its mixer BY NAME and checks
    nothing of the program itself: a stack that lacks the kind (the parent
    commit's, with this PR's benchmark files laid over it) refuses the name
    by field in its own configuration check, and the run fails cleanly —
    tried on the chip (PERF.md section 6, PR 51)."""
    import dataclasses

    from docqa_tpu.config import load_config
    from docqa_tpu.models.hybrid import check_hybrid_config

    conf = arch.load_cell_config(FILE, OVERLAY)
    overrides = KEYS.program_overrides(conf)
    assert set(overrides["decoder.mixer_types"]) == {KEYS.RETENTION}
    cfg = load_config(env={}, overrides=child.program_overrides(conf)).decoder
    check_hybrid_config(cfg)
    unknown = dataclasses.replace(
        cfg, mixer_types=("retention2",) * cfg.num_layers)
    with pytest.raises(ValueError, match="mixer_types names"):
        check_hybrid_config(unknown)


@pytest.mark.parametrize("key", [
    "head_dim", "num_key_value_heads", "sliding_window",
    "use_sliding_window", "rope_theta", "tie_word_embeddings"])
def test_a_missing_key_is_named(key):
    conf = {k: v for k, v in CONF.items() if k != key}
    with pytest.raises(arch.ConfigError, match=f'"{key}"'):
        KEYS.program_overrides(conf)


# ---- what the package imports -----------------------------------------------

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
        assert found <= {"__future__", "typing", "harness", ".", "ast",
                         "os"}
    if module == "reference":
        assert "docqa_tpu" not in found
    assert found <= {"__future__", "typing", "harness", ".", "functools",
                     "math", "jax", "docqa_tpu", "ast", "os"}
    assert _imports(os.path.join(PACKAGE_DIR, "__init__.py")) == set()


@pytest.mark.parametrize("reader", ["state_step_roofline",
                                    "scan_flops_roofline"])
def test_the_new_readers_are_standard_library(reader):
    found = _imports(os.path.join(BENCH_DIR, "readers", reader + ".py"))
    assert found <= {"harness", "readers"}


# ---- the bytes and the operations, by hand ----------------------------------

LAYER = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
ENDS = 151936 * 5120
A_HEAD = 8256 * 129 * 4
FULL = 4 * 9728


def test_the_parameters_by_hand():
    assert LAYER == 330_301_440 and ENDS == 777_912_320
    assert SHAPES.layer_matrix_params(CONF) == LAYER
    assert SHAPES.matrix_params(CONF) == {
        "layers": 12 * LAYER, "head": ENDS, "embedding": ENDS}
    assert SHAPES.features(CONF) == 8256 and SHAPES.scan_layers(CONF) == 12
    w = SHAPES.decoder_weight_bytes(CONF)
    channels = 5120 + 2 * 1024 + 5120 + 2 * 17408 + 5120
    small = 5120 * 8 + 2 * 5120 + 2 * 128
    assert w["streamed"] == (
        12 * LAYER + ENDS + 4 * (151936 + 12 * channels)
        + 2 * (5120 + 12 * small))
    assert 4.74e9 < w["streamed"] < 4.75e9  # 3.96 of layers + 0.78 of head
    assert w["embedding"] == 2 * ENDS  # 1.56 GB, gathered, not streamed
    # the same tree in bfloat16: what 12 layers would weigh unquantized
    bf16 = {k: v for k, v in CONF.items() if k != "weight_quantization"}
    assert SHAPES.decoder_weight_bytes(bf16)["streamed"] == (
        2 * (12 * LAYER + ENDS + 5120 + 12 * small))


def test_the_state_a_step_is_charged_with_by_hand():
    assert SHAPES.head_state_bytes(CONF) == A_HEAD == 4_260_096  # 4.26 MB
    assert SHAPES.lane_state_bytes(CONF) == 12 * 8 * A_HEAD == 408_969_216
    assert SHAPES.kv_bytes_per_token(CONF) == 0
    assert SHAPES.least_lanes(CONF, FULL) == 4.0
    state = SHAPES.state_step_min_bytes(CONF, FULL)
    assert state == 2 * 4 * 408_969_216 == 3_271_753_728  # 3.27 GB
    step = SHAPES.decode_step_min_bytes(CONF, FULL, 1)
    assert step == SHAPES.decoder_weight_bytes(CONF)["streamed"] + state
    assert 8.0e9 < step < 8.04e9  # 9.8 ms at 819 GB/s
    assert 0.40 < state / step < 0.42  # 41 % of a step's least bytes
    # the symmetric layout is what is charged, whatever is built
    assert 2 * 12 * 8 * 8256 * 129 * 4 == 2 * 408_969_216
    assert SHAPES.state_step_min_bytes(CONF, FULL / 2) == state / 2
    assert SHAPES.decode_step_min_bytes(CONF, FULL, 4) == step / 4


def test_the_operations_of_a_prefill_by_hand():
    n = 9075.0  # the mean base length of the check block
    r = SHAPES.retention_flops(CONF, n)
    one = 2 * 8256 * 129
    assert r == {"attention_a_token": 40 * 4 * 128 * (n + 1) / 2,
                 "state_a_token": 40 * one, "build_a_prompt": 8 * one * n}
    assert r["state_a_token"] < r["attention_a_token"]  # past ~8.3k tokens
    short = SHAPES.retention_flops(CONF, 2000.0)
    assert short["attention_a_token"] < short["state_a_token"]
    scan = SHAPES.prefill_scan_min_flops(CONF, 4 * n, 4.0)
    assert scan == 12 * (4 * n * 40 * one + 4 * 8 * one * n)
    # the lesser form a token: under the switch-over the attention form
    assert SHAPES.prefill_scan_min_flops(CONF, 2000.0, 1.0) == 12 * (
        2000 * short["attention_a_token"] + 8 * one * 2000.0)
    flops = SHAPES.prefill_flops(CONF, 4 * n)
    assert flops == (4 * n * 2 * (12 * LAYER + 12 * 5120 * 8) + scan
                     + 4 * 2 * ENDS)
    assert 83e12 < SHAPES.prefill_flops(CONF, 9200.0, 9200.0) < 85e12
    assert SHAPES.prefill_flops(CONF, 2 * n, n) == pytest.approx(flops / 2)


# ---- the traffic: what the seed may not draw --------------------------------

@pytest.mark.parametrize("seed", [1, 99, 4295604013, 2**31 + 5])
def test_every_prompt_of_the_mix_takes_one_budget(seed):
    """Template + question + 112 notes of one size, hashed over THIS
    vocabulary (151,936 ids): 128 new tokens inside 9,728 positions, one
    9,728-row budget."""
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


# ---- the metrics ------------------------------------------------------------

def counters(**gained):
    return {"before": {"metrics": {"counters": dict.fromkeys(gained, 10)}},
            "after": {"metrics": {"counters": {
                k: 10 + v for k, v in gained.items()}}}}


SCOPES = {
    "jit__prefill_program": {
        "executions": 4, "median_s": 1.2,
        "scopes": {"state": 0.5, "proj": 0.25, "mlp": 0.4, "-": 0.01}},
    "jit__decode_program": {
        "executions": 9, "median_s": 0.32,
        "scopes": {"state": 0.2, "proj": 0.05, "mlp": 0.05, "head": 0.01}},
}


def _ctx():
    ctx = counters(
        serve_scan_tokens=12 * 4 * 9100, serve_prefill_tokens=4 * 9100,
        serve_prefill_dispatches=4, serve_admitted=4,
        serve_state_bytes_rw=2 * 408_969_216 * 4 * 16 * 10,
        serve_decode_chunks=10)
    ctx.update(
        conf=CONF, cell={"chips": 1, "name": CELL_NAME},
        device={"kind": "TPU v5 lite"}, polled=[{"kv_tokens": 4 * 9200}],
        scope_times=SCOPES,
        trace={"programs": {
            "jit__prefill_program": {"count": 4, "median_s": 1.2},
            "jit__decode_program": {"count": 9, "median_s": 0.32}}})
    return ctx


def test_the_new_metrics_on_hand_made_counters_and_scopes():
    import run

    ctx = _ctx()
    assert run.read_metric("decode_retention_ms", ctx) == pytest.approx(
        200 / 16)
    state = SHAPES.state_step_min_bytes(CONF, 4 * 9200)
    share = run.read_metric("decode_retention_roofline", ctx)
    assert share == pytest.approx(100 * (state / 819e9) / (0.2 / 16))
    assert 25 < share < 35
    least = SHAPES.prefill_scan_min_flops(CONF, 9100.0, 1.0)
    scan = run.read_metric("prefill_retention_roofline", ctx)
    assert scan == pytest.approx(100 * (least / 197e12) / 0.5)
    assert 8 < scan < 12
    # the older ones the cell joined read this package's shapes (and
    # ``prefill_scan_ms`` would: its list is pinned by equality elsewhere)
    assert run.read_metric("prefill_scan_ms", ctx) == pytest.approx(500.0)
    assert run.read_metric("prefill_mfu", ctx) == pytest.approx(
        100 * SHAPES.prefill_flops(CONF, 9100.0, 9100.0) / (197e12 * 1.2))
    step = SHAPES.decode_step_min_bytes(CONF, 4 * 9200, 1)
    assert run.read_metric(
        "lane_state_share_of_step_bytes", ctx) == pytest.approx(
        100 * 2 * 408_969_216 * 4 / step)
    assert 41 < run.read_metric("lane_state_share_of_step_bytes", ctx) < 43
    assert run.read_metric("decode_step_ms", ctx) == pytest.approx(320 / 16)
    assert run.read_metric("decode_step_roofline", ctx) == pytest.approx(
        100 * (step / 819e9) / (0.32 / 16))
    # two prompts a dispatch: the same share at twice the time
    two = dict(ctx, **counters(
        serve_scan_tokens=12 * 4 * 9100, serve_prefill_dispatches=2,
        serve_admitted=4))
    two["scope_times"] = {"jit__prefill_program": {
        **SCOPES["jit__prefill_program"], "scopes": {"state": 1.0}}}
    assert run.read_metric("prefill_retention_roofline", two) == (
        pytest.approx(scan))


@pytest.mark.parametrize("case", [
    "no_counter", "no_table", "unscoped", "other_package", "no_counters",
    "nothing_polled"])
def test_where_there_is_nothing_to_read_the_readers_return_none(case):
    """A parent without the counter, a compile cache filled before the
    scopes, another package without the functions, a window in which
    nothing was polled: each reader returns None and nothing raises."""
    import run

    ctx = _ctx()
    both = ("decode_retention_roofline", "prefill_retention_roofline")
    if case == "no_counter":
        ctx.update(counters(serve_prefill_tokens=900,
                            serve_prefill_dispatches=3, serve_admitted=3))
        names = both[1:]
    elif case == "no_table":
        ctx["scope_times"] = {}
        names = both + ("decode_retention_ms",)
    elif case == "unscoped":
        ctx["scope_times"] = {
            name: {"executions": 3, "median_s": 0.5, "scopes": {"-": 0.5}}
            for name in SCOPES}
        names = both + ("decode_retention_ms",)
    elif case == "other_package":
        ctx["conf"] = load(
            os.path.join(BENCH_DIR, "configs", "jamba2-3b-bf16.json"))
        names = both
    elif case == "no_counters":
        ctx.update(before={}, after={})
        names = both[1:]
    else:
        ctx["polled"] = []
        names = both[:1]
    for name in names:
        assert run.read_metric(name, ctx) is None, name


# ---- the package through the harness at tiny widths -------------------------

def test_the_comparison_at_tiny_widths_with_every_control():
    """``harness/check.decoder_check`` over the package at the tiny overlay:
    the program's paged forwards against the attention-form reference, the
    state pool's 32 bits read, every control above the limit."""
    import types

    import jax

    from docqa_tpu.config import load_config
    from harness import check

    conf = arch.load_cell_config(FILE, OVERLAY)
    cfg = load_config(env={}, overrides=child.program_overrides(conf))
    package = arch.load(conf)
    assert not arch.routes(package)
    params = package.weights.make_decoder_params(cfg.decoder, 7)
    engine = types.SimpleNamespace(
        cfg=cfg.decoder, params=params, use_flash=False)
    gen = cfg.generate
    block = int(gen.kv_block_size)
    row = check.decoder_check(
        package, conf["check"], engine, 7,
        n_blocks=int(gen.kv_pool_tokens) // block, block_size=block,
        seq_capacity=cfg.decoder.max_seq_len, n_lanes=4, step_width=1,
        control=True)
    limit = conf["correct"]["decoder_logit_rel_err"]
    assert 0 < row["program"]["worst_row"] < limit
    assert row["kv_bits"] == 32 == conf["kv_cache_bits"]
    assert check.kv_bits_missing(conf["kv_cache_bits"], row["kv_bits"]) == 0
    assert check.kv_bits_missing(conf["kv_cache_bits"], 16) == 16  # bf16
    assert set(row["controls"]) == {"w_int4", "a_int8", "a_fp8", "carry_zero"}
    for name, reading in row["controls"].items():
        assert reading["worst_row"] > limit, name
    # what a lane carried is most of what a compared row reads: forgetting
    # it is wrong by more than any rounding
    assert row["controls"]["carry_zero"]["worst_row"] > 0.5
    assert set(row["kv_only"]) == {"state_bf16", "read_bf16"}
    for reading in row["kv_only"].values():
        assert 0 < reading["worst_row"] < limit
    del params, engine
    jax.clear_caches()


# ---- the cell, rehearsed on the CPU at tiny widths --------------------------

def test_the_cell_runs_end_to_end_at_tiny_widths():
    """``/ask/stream`` -> QAService -> EnginePool -> batcher -> the paged
    forwards of a stack of four retention layers whose pools hold no row:
    rounds admitted together, three compared numbers (the state pool's 32
    bits held exactly), the stack's counters in the result line."""
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
    assert 0 < out["compared"]["decoder_logit_rel_err"]["value"] < 0.02
    assert out["compared"]["kv_cache_bits_missing"] == {
        "value": 0, "limit": 0}
    metrics = out["metrics"]
    assert 1.0 < metrics["admit_batch_mean"]["value"] <= 4.0
    # lanes still take pages: the unit of admission, though none holds a row
    assert 0 < metrics["kv_pool_used_share"]["value"] <= 100
    # at tiny widths the weights are nothing and the ~550-token lanes count
    # as half lanes of 1,024: the share passes 100 here, 41-43 on the chip
    assert metrics["lane_state_share_of_step_bytes"]["value"] > 0
    # device metrics: no CPU number under their names
    for name in ("prefill_scan_ms", "prefill_mfu", "decode_step_ms",
                 "decode_step_roofline", *NEW_METRICS):
        assert name not in metrics
