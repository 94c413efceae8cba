"""Every file of the benchmark against the rules a later PR relies on:
names and units in the allowed characters, every metric declared with a
reader that exists, every ``moves`` an end-to-end metric of each cell that
reports it, at most a quarter (and at least one allowed) four-chip cells."""

import importlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import arch, traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
PUBLISHED = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/config.json": {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "head_dim": 128, "num_hidden_layers": 32, "vocab_size": 32000,
        "sliding_window": 4096, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-05},
}


def cells_of(metric):
    return metric.get("workloads") or list(CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16 and len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    # a full check of 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], ALL_METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["why"]) <= 200
    assert config["source"].startswith("https://")
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as f:
        conf = json.load(f)
    # the published widths, by the source the entry claims: every file of
    # Mistral-7B-v0.1 is held to them, and none may ever be reduced (a
    # configuration of another source brings a test of its widths with it)
    widths = PUBLISHED.get(config["source"], {})
    for key, value in widths.items():
        assert conf[key] == value, key
        assert key not in config["reduced"]
    for key in config["reduced"]:
        assert NAME.match(key) and not re.search(r"(_dim|_rank|_size)$", key)
    for key in ("architecture", "serving", "corpus", "assumed", "deployment",
                "check", "correct", "chips"):
        assert key in conf, key
    # the package the file names has the whole surface, and knows every
    # published key of the file
    block = arch.load(conf)
    assert set(block.keys.program_overrides(conf)) >= {"decoder.dtype"}
    assert set(conf["check"]) == {"prompt_lengths", "lane_rows"}
    assert conf["check"]["lane_rows"] % 128 == 0
    assert max(conf["check"]["prompt_lengths"]) < conf["check"]["lane_rows"]
    assert set(conf["correct"]) == {"decoder_logit_rel_err", "kv_cache_bits_missing",
                                    "retrieval_score_err"}
    assert conf["kv_cache_bits"] == 16 and conf["correct"]["kv_cache_bits_missing"] == 0
    assert conf["corpus"]["patients"] * conf["corpus"]["chunks_per_patient"] <= conf["corpus"]["rows"]
    assert conf["corpus"]["patients"] <= 4096


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = traffic.load(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    assert mix["endpoint"] in ("/ask/", "/ask/stream")
    with open(os.path.join(
        ROOT, {c["name"]: c for c in BENCH["configs"]}[cell["config"]]["file"]
    ), encoding="utf-8") as f:
        assert json.load(f)["chips"] == cell["chips"]
    reported = [m for m in BENCH["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    assert any(cell["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    per_layer = metric["name"] not in E2E
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"}
    )
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in cells_of(metric):
        assert cell in CELLS
    if per_layer:
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        moved = E2E[metric["moves"]]
        for cell in cells_of(metric):  # each has to report what it moves
            assert cell in cells_of(moved), (metric["name"], cell)
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
    with open(os.path.join(BENCH_DIR, "metrics", metric["name"] + ".json"),
              encoding="utf-8") as f:
        decl = json.load(f)
    assert set(decl) <= {"reader", "params"}
    reader = importlib.import_module("readers." + decl["reader"])
    assert callable(reader.read)


def test_metrics_of_one_layer_spell_it_the_same():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({x.lower() for x in layers}) == len(layers)


def test_every_file_under_paths_is_named_from_allowed_characters():
    seen = 0
    for base in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert PATH.match(rel), rel
                seen += 1
    assert seen > 20


def test_every_declared_metric_file_belongs_to_a_metric():
    declared = {m["name"] for m in ALL_METRICS}
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "metrics"))}
    assert on_disk == declared


def test_nothing_ships_that_no_cell_uses():
    """Every reader is named by a metric file, every kind of question by a
    traffic file and every traffic file by a cell: what a later cell needs
    arrives with it, as a file."""
    import json

    def names(folder, ext):
        return {f[:-len(ext)] for f in os.listdir(os.path.join(BENCH_DIR, folder))
                if f.endswith(ext) and not f.startswith("__")}

    def load(folder, name):
        with open(os.path.join(BENCH_DIR, folder, name + ".json"),
                  encoding="utf-8") as f:
            return json.load(f)

    used_readers = {load("metrics", m)["reader"] for m in names("metrics", ".json")}
    assert names("readers", ".py") == used_readers
    used_traffic = {w["traffic"] for w in BENCH["workloads"]}
    assert names("traffic", ".json") == used_traffic
    used_kinds = {q["kind"] for t in used_traffic
                  for q in load("traffic", t)["questions"]}
    assert names("questions", ".json") == used_kinds


def test_the_parent_never_imports_jax():
    import subprocess

    code = (
        "import sys; sys.argv=['run.py','--help']\n"
        "import runpy\n"
        "try:\n runpy.run_path(%r, run_name='__main__')\n"
        "except SystemExit: pass\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
    ) % os.path.join(BENCH_DIR, "run.py")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60,
                   stdout=subprocess.DEVNULL)
