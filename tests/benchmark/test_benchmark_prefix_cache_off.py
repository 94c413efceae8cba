"""ISSUE 33: ``rag_closed`` serves with the prefix cache OFF, stated in the
configuration file, and the regime shows on every traced line — three
metrics that are data files on the ``counter_ratio`` reader
(``prefix_hit_share``, ``prefill_dispatches_per_round``,
``decode_kv_read_amplification``; the last one's cases came with its file
from ``tests/test_kv_read_amplification_metric.py``, ISSUE 29)."""

import dataclasses
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import arch, child, traffic  # noqa: E402  (standard library)

OVERLAY = os.path.join(HERE, "data", "tiny_overlay.json")
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)
CELL = next(w for w in BENCH["workloads"] if w["name"] == "rag_closed")
CONFIG = next(c for c in BENCH["configs"] if c["name"] == CELL["config"])
with open(os.path.join(ROOT, CONFIG["file"]), encoding="utf-8") as _f:
    CONF = json.load(_f)

HITS, ADMITTED = "serve_prefix_hits", "serve_admitted"
GROUPS, ROUNDS = "serve_prefill_dispatches", "serve_admit_rounds"
READ, LIVE = "serve_decode_kv_rows_read", "serve_decode_kv_rows_live"


def tiny_conf():
    """The cell's configuration file at the overlay's widths."""
    return arch.load_cell_config(os.path.join(ROOT, CONFIG["file"]), OVERLAY)


# ---- the configuration ----------------------------------------------------

def test_the_cell_states_that_the_prefix_cache_is_off_and_why():
    assert CONF["serving"]["generate.prefix_cache"] is False
    why = CONF["assumed"]["generate.prefix_cache"]
    # in numbers, and with the cell that does measure the cache
    assert "rag_shared_docs" in why and any(c.isdigit() for c in why)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_every_serving_key_is_a_field_of_the_programs_config(config):
    """``serving`` holds dotted overrides of the program's ``Config``: a
    key that names no field there would change nothing, in silence."""
    from docqa_tpu.config import Config

    with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as f:
        serving = json.load(f)["serving"]
    cfg = Config()
    for key in serving:
        section, _, field = key.partition(".")
        assert hasattr(cfg, section), key
        names = {f.name for f in dataclasses.fields(getattr(cfg, section))}
        assert field in names, key


@pytest.mark.parametrize("key", ["generate.prefix_cach", "generat.prefix_cache"],
                         ids=["misspelt_field", "misspelt_section"])
def test_a_misspelt_override_is_an_error_not_silence(key):
    """What the child does with the file's ``serving`` block
    (``child.program_overrides`` into the program's ``load_config``)."""
    from docqa_tpu.config import load_config

    conf = tiny_conf()
    conf["serving"][key] = False
    with pytest.raises((TypeError, KeyError)):
        load_config(env={}, overrides=child.program_overrides(conf))


def test_the_override_reaches_the_programs_config():
    from docqa_tpu.config import Config, load_config

    cfg = load_config(env={}, overrides=child.program_overrides(tiny_conf()))
    assert Config().generate.prefix_cache is True  # the default it departs from
    assert cfg.generate.prefix_cache is False


# ---- the traffic ----------------------------------------------------------

def test_warm_bursts_cover_every_count_admitted_together():
    """The batcher builds a few small programs per number of requests of a
    round; a window may hold rounds of 1 to ``clients``."""
    mix = traffic.load(os.path.join(BENCH_DIR, "traffic", CELL["traffic"] + ".json"))
    assert set(range(1, int(mix["clients"]) + 1)) <= set(mix["warm_bursts"])


# ---- the three metric files -----------------------------------------------

def read(name, before, after):
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
        decl = json.load(f)
    ctx = {"before": {"metrics": {"counters": before}},
           "after": {"metrics": {"counters": after}}}
    # as run.py finds it: the module the metric's file names
    reader = importlib.import_module("readers." + decl["reader"])
    return reader.read(ctx, **decl.get("params", {}))


@pytest.mark.parametrize("name, before, after, expected", [
    # rag_closed with the cache off: requests admitted, none of them warm
    ("prefix_hit_share", {ADMITTED: 12, HITS: 0}, {ADMITTED: 100}, 0.0),
    # the parent of ISSUE 33 on a seed that repeats chunk sets: 23 of 88
    ("prefix_hit_share", {ADMITTED: 12, HITS: 3},
     {ADMITTED: 100, HITS: 26}, 100.0 * 23 / 88),
    # nothing admitted in the window: nothing to read, not a zero
    ("prefix_hit_share", {ADMITTED: 12, HITS: 3}, {ADMITTED: 12, HITS: 3}, None),
    # a round of one and a round of three cold groups, eleven times over
    ("prefill_dispatches_per_round", {ROUNDS: 6, GROUPS: 12},
     {ROUNDS: 28, GROUPS: 56}, 2.0),
    # the program before PR 31 has no such counter: every round 0 groups
    ("prefill_dispatches_per_round", {ROUNDS: 6}, {ROUNDS: 28}, 0.0),
    ("prefill_dispatches_per_round", {ROUNDS: 6, GROUPS: 12},
     {ROUNDS: 6, GROUPS: 12}, None),
    # the parent of ISSUE 29 has neither counter: nothing to read
    ("decode_kv_read_amplification", {"serve_decode_chunks": 4},
     {"serve_decode_chunks": 40}, None),
    # the kernel: 4 lanes of 357 live rows read 23 pages of 16 each step
    ("decode_kv_read_amplification", {READ: 1000, LIVE: 900},
     {READ: 1000 + 64 * 4 * 368, LIVE: 900 + 64 * 4 * 357}, 368 / 357),
    # the gather reference: every slot's 4096-position table each step
    ("decode_kv_read_amplification", {READ: 0, LIVE: 0},
     {READ: 16 * 4 * 4096, LIVE: 16 * 3 * 357}, 4 * 4096 / (3 * 357)),
    # no chunk in the window
    ("decode_kv_read_amplification", {READ: 5000, LIVE: 4000},
     {READ: 5000, LIVE: 4000}, None),
], ids=["hits_none", "hits_some", "hits_no_admission",
        "groups_one_and_three", "groups_counter_absent", "groups_no_round",
        "kv_counters_absent", "kv_live_pages", "kv_whole_tables",
        "kv_no_chunk"])
def test_metric_file_over_counter_snapshots(name, before, after, expected):
    got = read(name, before, after)
    assert got == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("entry", [
    {"name": "prefix_hit_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "Paged KV",
     "moves": "ttft_p50_ms", "workloads": ["rag_closed"]},
    {"name": "prefill_dispatches_per_round", "unit": "dispatches",
     "better": "lower", "source": "program_counter",
     "layer": "Admission and batching", "moves": "ttft_p50_ms",
     "workloads": ["rag_closed"]},
    {"name": "decode_kv_read_amplification", "unit": "x", "better": "lower",
     "source": "program_counter", "layer": "Kernels",
     "moves": "tpot_p50_ms", "workloads": ["rag_closed"]},
], ids=lambda e: e["name"])
def test_declared_after_the_parents_entries(entry):
    at = [m["name"] for m in BENCH["per_layer"]].index(entry["name"])
    assert BENCH["per_layer"][at] == entry
    assert at >= 16  # appended: the parent's sixteen stay a prefix


# ---- the program under the cell's overrides, on a tiny model ---------------

@pytest.fixture(scope="module")
def tiny():
    """The program's engine at the overlay's widths under the file's
    ``serving`` block, and prompts that share their first 256 tokens (a
    template and the same three chunks) under one prefix key."""
    from docqa_tpu.config import load_config
    from docqa_tpu.engines.generate import GenerateEngine

    cfg = load_config(env={}, overrides=child.program_overrides(tiny_conf()))
    engine = GenerateEngine(cfg.decoder, cfg.generate, seed=7)
    shared = [(3 + i * 7) % 2000 + 3 for i in range(300)]
    return cfg, engine, [shared + [11, 12, 13], shared + [21, 22]]


def serve(engine, prompts, **kw):
    from docqa_tpu.engines.serve import ContinuousBatcher
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    hits = DEFAULT_REGISTRY.counter(HITS)
    before = hits.value
    b = ContinuousBatcher(engine, n_slots=2, cache_len=512, **kw)
    try:
        enabled = b.prefix_cache_enabled
        out = [b.submit_ids(p, max_new_tokens=8, prefix_key="same-chunks")
               .result(timeout=300) for p in prompts]
    finally:
        b.stop()
    return enabled, out, hits.value - before


def test_the_same_chunk_set_twice_prefills_cold_both_times(tiny):
    cfg, engine, prompts = tiny
    assert cfg.generate.prefix_cache is False
    # the batcher as the pool builds it: the cache's switch from the config
    enabled, cold, hits = serve(engine, prompts)
    assert enabled is False and hits == 0
    # the cache on (the program's default): the second request is warm,
    # and says the same tokens
    enabled, warm, hits = serve(engine, prompts, prefix_cache=True)
    assert enabled is True and hits == 1
    assert warm == cold and all(len(t) > 0 for t in cold)
