"""ISSUE 35: the ``deepseek_v2`` architecture package, its configuration
``deepseek-v2-ep4-bf16`` and its cell ``rag_closed8_dsv2``.

* the file keeps every published width: each key of DeepSeek-V2's
  ``config.json`` (the catalog's row, copied below) that is not in
  ``reduced`` stands in the file at its published value;
* ``keys.program_overrides`` maps every published key or names it in
  ``FIXED``; an unknown key is a ``ConfigError``;
* ``shapes``: the bytes a step is charged with, by hand;
* the new metrics' reader on hand-made counters, and ``None`` under a
  program that has no such counters (the parent);
* the package through ``child.run_check`` with the PROGRAM's own paged
  forwards at tiny widths (an overlay), every limit beside its number.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
DATA = os.path.join(HERE, "data")
for _p in (BENCH_DIR, DATA):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import arch  # noqa: E402

FILE = os.path.join(BENCH_DIR, "configs", "deepseek-v2-ep4-bf16.json")
OVERLAY = os.path.join(DATA, "tiny_overlay_dsv2.json")
SOURCE = "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json"
# the language model's settings as the source publishes them
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 3, "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400,
}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 40,
           "vocab_size": 25600, "max_position_embeddings": 4096}


# four accepted metrics whose entries tests of PRs 26 and 33 hold to
# `"workloads": ["rag_closed"]` verbatim, in files this PR may not edit
# (test_benchmark_prefill_ahead.py, test_benchmark_prefix_cache_off.py): the
# new cell stays off their lists until a `benchmark` PR loosens those tests
PINNED = {"prefill_ahead_share", "prefix_hit_share",
          "prefill_dispatches_per_round", "decode_kv_read_amplification"}


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


CONF = load(FILE)
BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
ENTRY = {c["name"]: c for c in BENCH["configs"]}["deepseek-v2-ep4-bf16"]
PACKAGE = arch.load_shapes(CONF)


# ---- the file against the source -------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_each_published_key(key):
    if key in REDUCED:
        assert key in ENTRY["reduced"]
        assert CONF[key] == REDUCED[key] < PUBLISHED[key]
    else:
        assert key not in ENTRY["reduced"]
        assert CONF[key] == PUBLISHED[key], key


def test_the_entry_names_the_source_and_exactly_the_four_cuts():
    assert ENTRY["source"] == SOURCE
    assert sorted(ENTRY["reduced"]) == sorted(REDUCED)
    assert ENTRY["file"] == "benchmark/configs/deepseek-v2-ep4-bf16.json"
    # the floors of a model_config PR: four routed layers behind the dense
    # one, at least 8 experts held, at least an eighth of the vocabulary
    assert CONF["num_hidden_layers"] - CONF["first_k_dense_replace"] >= 4
    assert CONF["n_routed_experts"] >= 8
    assert CONF["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_file_states_the_deployment_and_the_published_counts():
    # the router keeps its published width; the range held is two whole
    # groups of the eight
    assert CONF["router_experts"] == PUBLISHED["n_routed_experts"] == 160
    per_group = CONF["router_experts"] // CONF["n_group"]
    assert CONF["experts_held_start"] % per_group == 0
    assert CONF["n_routed_experts"] == 2 * per_group == 160 // 4
    said = CONF["deployment"]
    for part in ("four chips", "60", "160", "102400", "163840",
                 "experts 0-39", "pipeline"):
        assert part in said, part
    for key in ("weights", "tokenizer", "rope_pairing", "seq_aux",
                "generate.decode_chunk", "num_hidden_layers",
                "n_routed_experts", "vocab_size", "max_position_embeddings"):
        assert key in CONF["assumed"], key
    assert CONF["serving"]["decoder.quantize_weights"] is False
    assert CONF["serving"]["generate.prefix_cache"] is False
    assert CONF["serving"]["generate.speculative_k"] == 0
    assert CONF["serving"]["generate.max_concurrent"] == 8
    assert CONF["serving"]["generate.max_new_tokens"] == 128
    assert CONF["kv_cache_bits"] == 16 and CONF["torch_dtype"] == "bfloat16"
    mistral = load(os.path.join(BENCH_DIR, "configs", "mistral-7b-int8.json"))
    assert CONF["corpus"] == mistral["corpus"]
    for key, value in mistral["serving"].items():
        if key.startswith(("store.", "chunk.", "retrieval_quality.", "pool.",
                           "dispatch.")):
            assert CONF["serving"][key] == value, key


def test_the_cell_is_the_issues_table():
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
    # eight clients = the eight decode slots, as ISSUE 35's table has it;
    # warm bursts of every count admitted or retired together, closed by
    # a burst of one (the traffic file's note)
    assert mix["clients"] == CONF["serving"]["generate.max_concurrent"] == 8
    assert mix["warm_bursts"] == [1, 2, 3, 4, 5, 6, 7, 8, 1]
    assert "1 + 3 + 4" in mix["note"]
    # ... which the configuration's hold turns into one admission of eight
    assert CONF["serving"]["generate.admit_hold_ms"] == 50.0
    assert "generate.admit_hold_ms" in mix["note"]
    assert "generate.admit_hold_ms" in CONF["assumed"]
    reports = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
               if "rag_closed8_dsv2" in m.get("workloads", [])}
    old = {m["name"] for m in BENCH["per_layer"]
           if "rag_closed" in m.get("workloads", [])}
    assert reports == (old - PINNED) | {
        "ttft_p50_ms", "tpot_p50_ms", "moe_local_pick_share",
        "moe_experts_touched_per_layer_step", "decode_touched_roofline"}
    new = [m for m in BENCH["per_layer"] if m["name"] in (
        "moe_local_pick_share", "moe_experts_touched_per_layer_step",
        "decode_touched_roofline")]
    assert BENCH["per_layer"][-3:] == new, "appended, at the end"
    assert all(m["workloads"] == ["rag_closed8_dsv2"]
               and m["moves"] == "tpot_p50_ms" for m in new)
    assert [m["layer"] for m in new] == ["Model step", "Model step", "Kernels"]
    # nothing the benchmark had was taken away: the old cell is on every
    # list it was on, first
    for m in BENCH["end_to_end"] + BENCH["per_layer"][:-3]:
        if "workloads" in m:
            assert m["workloads"] == ["rag_closed"] + (
                [] if m["name"] in PINNED else ["rag_closed8_dsv2"])
    assert [m["bound"] for m in BENCH["end_to_end"]] == [0.01, 0.01, 0.1]


# ---- keys -------------------------------------------------------------------

def test_every_published_key_is_mapped_or_fixed():
    keys = PACKAGE.keys
    known = set(keys.TO_DECODER) | set(keys.FIXED) | {
        "rope_scaling", "num_key_value_heads", "torch_dtype"}
    assert set(PUBLISHED) <= known
    assert set(PUBLISHED["rope_scaling"]) - {"type"} == set(keys.ROPE_SCALING)
    out = keys.program_overrides(CONF)
    assert out["decoder.block"] == "mla_moe"
    assert out["decoder.head_dim"] == 192 and out["decoder.num_kv_heads"] == 1
    assert out["decoder.num_experts"] == 160
    assert out["decoder.experts_held"] == 40
    assert out["decoder.experts_held_start"] == 0
    assert out["decoder.rope_scaling_factor"] == 40.0
    assert out["decoder.rope_original_max_len"] == 4096
    assert out["decoder.rope_mscale_all_dim"] == 0.707
    assert out["decoder.num_layers"] == 5 and out["decoder.vocab_size"] == 25600
    from docqa_tpu.config import DecoderConfig

    fields = {f.name for f in DecoderConfig.__dataclass_fields__.values()}
    assert {k.split(".", 1)[1] for k in out} <= fields


@pytest.mark.parametrize("change, said", [
    ({"sliding_window": 4096}, '"sliding_window"'),
    ({"topk_method": "noaux_tc"}, '"topk_method"'),
    ({"scoring_func": "sigmoid"}, '"scoring_func"'),
    ({"norm_topk_prob": True}, '"norm_topk_prob"'),
    ({"num_key_value_heads": 8}, '"num_key_value_heads"'),
    ({"rope_scaling": {"type": "linear", "factor": 4}}, '"rope_scaling"'),
    ({"rope_scaling": {"type": "yarn", "attn_factor": 1}}, '"rope_scaling"'),
])
def test_a_key_the_block_does_not_know_is_a_config_error(change, said):
    with pytest.raises(arch.ConfigError, match=said):
        PACKAGE.keys.program_overrides({**CONF, **change})


def test_the_router_width_is_owed():
    conf = {k: v for k, v in CONF.items() if k != "router_experts"}
    with pytest.raises(arch.ConfigError, match='"router_experts"'):
        PACKAGE.keys.program_overrides(conf)


# ---- shapes -----------------------------------------------------------------

def test_the_bytes_a_step_is_charged_with_by_hand():
    shapes = PACKAGE.shapes
    assert shapes.latent_row_bytes(CONF) == 1152
    assert shapes.kv_bytes_per_token(CONF) == 5760
    assert shapes.expert_bytes(CONF) == 3 * 5120 * 1536 * 2 == 47185920
    attention = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576
                 + 512 * 128 * 256 + 128 * 128 * 5120)
    assert attention == 149225472  # ISSUE 35: 149.23 M a layer
    non_expert = (
        5 * (attention + 2 * 5120 + 1536 + 512) + 3 * 5120 * 12288
        + 4 * (5120 * 160 + 3 * 5120 * 3072) + 5120 + 5120 * 25600)
    assert shapes.non_expert_weight_bytes(CONF) == 2 * non_expert
    least = shapes.decode_step_min_bytes(CONF, 3600, 1)
    assert least == 2 * non_expert + 3600 * 5760
    # never all 40 experts of a layer — not even one: a step whose tokens
    # keep groups held elsewhere reads no expert here
    assert least < 2 * non_expert + 3600 * 5760 + shapes.expert_bytes(CONF)
    # 10.5 experts a routed layer-step, four routed layers: 42 a step
    touched = shapes.decode_step_touched_bytes(CONF, 3600, 10.5, 1)
    assert shapes.routed_layers(CONF) == 4
    assert touched == least + 42 * 47185920
    assert shapes.decode_step_touched_bytes(CONF, 3600, 0.0, 1) == least
    assert 2.4e9 < least < 2.7e9 and 4.3e9 < touched < 4.7e9


def counters(**gained):
    base = {"serve_moe_picks": 10, "serve_moe_picks_local": 3,
            "serve_moe_experts_touched": 2, "serve_moe_layer_steps": 1}
    after = {k: base[k] + gained.get(k, 0) for k in base}
    return {"before": {"metrics": {"counters": base}},
            "after": {"metrics": {"counters": after}}}


def test_the_three_new_metrics_on_hand_made_counters():
    import run
    from harness import peaks

    ctx = counters(serve_moe_picks=4 * 16 * 8 * 6,
                   serve_moe_picks_local=4 * 16 * 12,
                   serve_moe_experts_touched=4 * 16 * 10,
                   serve_moe_layer_steps=4 * 16)
    assert run.read_metric("moe_local_pick_share", ctx) == pytest.approx(25.0)
    assert run.read_metric(
        "moe_experts_touched_per_layer_step", ctx) == pytest.approx(10.0)
    ctx.update(
        conf=CONF, cell={"chips": 1}, device={"kind": "TPU v5 lite"},
        polled=[{"kv_tokens": 3000}, {"kv_tokens": 4200}, {"kv_tokens": None}],
        trace={"programs": {
            "jit__decode_program(1)": {"count": 30, "median_s": 0.128},
            "jit__prefill_program(2)": {"count": 40, "median_s": 0.03}}},
    )
    want_bytes = PACKAGE.shapes.decode_step_touched_bytes(CONF, 3600, 10.0, 1)
    bandwidth = peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    share = run.read_metric("decode_touched_roofline", ctx)
    assert share == pytest.approx(
        100 * (want_bytes / bandwidth) / (0.128 / 16), rel=1e-9)
    assert share > run.read_metric("decode_step_roofline", ctx) > 0


def test_under_a_program_without_the_counters_the_metrics_are_left_out():
    """The parent commit has no routing counters (and cannot run the
    configuration): each reader finds nothing and returns None."""
    import run

    ctx = {"before": {"metrics": {"counters": {"serve_admitted": 1}}},
           "after": {"metrics": {"counters": {"serve_admitted": 9}}},
           "conf": CONF, "cell": {"chips": 1},
           "device": {"kind": "TPU v5 lite"},
           "polled": [{"kv_tokens": 3000}],
           "trace": {"programs": {
               "jit__decode_program": {"count": 3, "median_s": 0.1}}}}
    for name in ("moe_local_pick_share", "moe_experts_touched_per_layer_step",
                 "decode_touched_roofline"):
        assert run.read_metric(name, ctx) is None
    # and under the Mistral package, which has no such function
    mistral = load(os.path.join(BENCH_DIR, "configs", "mistral-7b-int8.json"))
    full = counters(serve_moe_experts_touched=5, serve_moe_layer_steps=5)
    full.update(ctx, conf=mistral, before=full["before"], after=full["after"])
    assert run.read_metric("decode_touched_roofline", full) is None


# ---- the package through run_check, with the program's own forwards --------

REHEARSAL = """
import json, sys, types
sys.path[:0] = ["benchmark", %(data)r]
import routed_standin as standin
from docqa_tpu.config import load_config
from harness import arch, child

conf = arch.load_cell_config(%(file)r, %(overlay)r)
package = arch.load(conf)
cfg = load_config(env={}, overrides=child.program_overrides(conf)).decoder
assert cfg.block == "mla_moe" and cfg.num_experts == 32 and cfg.experts_held == 8
seed = int(sys.argv[1])
engine = types.SimpleNamespace(
    cfg=cfg, use_flash=False,
    params=package.weights.make_decoder_params(cfg, seed %% 2**31))
store, stored = standin.small_store(seed)
out = child.run_check(
    standin.runtime(engine, store, n_blocks=256, block_size=16,
                    seq_capacity=1024, n_slots=4, step_width=0),
    conf, package, seed, stored)
print(json.dumps({"correct": out["correct"], "numbers": out["numbers"]}))
"""


@pytest.mark.parametrize("seed", [7, 4294967327])
def test_the_package_goes_through_run_check_with_the_programs_forwards(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c",
         REHEARSAL % {"data": DATA, "file": FILE, "overlay": OVERLAY},
         str(seed)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out
    assert [n["name"] for n in out["numbers"]] == [
        "decoder_logit_rel_err", "kv_cache_bits_missing",
        "retrieval_score_err", "router_choice_gap"]
    limits = {**CONF["correct"], **load(OVERLAY)["correct"]}
    for n in out["numbers"]:
        assert n["limit"] == limits[n["name"]] and 0 <= n["value"] <= n["limit"]
    assert out["numbers"][0]["value"] > 0 < out["numbers"][3]["value"]
