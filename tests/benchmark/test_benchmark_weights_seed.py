"""ISSUE 58: a configuration STATES the seed its decoder weights are drawn
from (``"weights_seed"``: the CRC-32 of its name), and ``--seed`` draws
what a deployment sees anew every hour — the questions, the patients, the
encoder, the corpus, ``check``'s prompts — and never a model.  A
deployment serves one set of weights for months: what the weights' draw
sets (how many experts a routed step reads) is a cell's LEVEL, not its
run-to-run spread (PERF.md §2, §6).

* two ``--seed``s give the same decoder tree through the child's own
  seeded engine, another encoder and another order of questions;
* a file without the key, or with one that is not the CRC of its name, is
  refused by file and key;
* ``calibrate.py`` goes on drawing its weights from each ``--seeds`` entry
  (the limits stay what many draws read).
"""

import json
import os
import sys
import zlib

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import arch, child, corpus, traffic  # noqa: E402  (standard library)

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
MISTRAL_FILE = os.path.join(ROOT, CONFIGS["mistral-7b-int8"]["file"])

# (configuration, its tiny overlay): one block that does not route and the
# two that do — a router and a selection bias are leaves of the tree too
TINY = [
    ("mistral-7b-int8", "tiny_overlay.json"),
    ("deepseek-v2-ep4-bf16", "tiny_overlay_dsv2.json"),
    ("trinity-mini-ep8-bf16", "tiny_overlay_trinity.json"),
]
SEEDS = (11, 4295000012)  # the second is past 32 signed bits, as the driver's are


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


# ---- the files -------------------------------------------------------------

@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_states_the_crc_of_its_name(config):
    conf = load(os.path.join(ROOT, config["file"]))
    assert os.path.basename(config["file"]) == config["name"] + ".json"
    assert conf["weights_seed"] == zlib.crc32(config["name"].encode())
    assert conf["weights_seed"] == arch.stated_weights_seed(config["name"])
    # the harness's own key, beside `serving` and `check`: no package's
    # keys.py has to map it
    assert "weights_seed" in arch.HARNESS_KEYS
    assert "weights_seed" not in arch.model_keys(conf)
    arch.load_cell_config(os.path.join(ROOT, config["file"]))


@pytest.mark.parametrize("name, change, said", [
    ("mistral-7b-int8", {"weights_seed": None}, "None"),
    ("mistral-7b-int8", {"weights_seed": 7}, "7 is not"),
    ("mistral-7b-int8", {"weights_seed": 1573579549}, "1573579549 is not"),
    # the right number of ANOTHER name: a copied file draws its own model
    ("mistral-7b-int4", {}, "1573579548 is not"),
], ids=["missing", "chosen", "off_by_one", "copied_under_another_name"])
def test_a_file_whose_weights_seed_is_not_its_names_is_refused(
        tmp_path, name, change, said):
    conf = {**load(MISTRAL_FILE), **change}
    if conf["weights_seed"] is None:
        del conf["weights_seed"]
    path = tmp_path / (name + ".json")
    path.write_text(json.dumps(conf))
    with pytest.raises(arch.ConfigError) as e:
        arch.load_cell_config(str(path))
    message = str(e.value)
    assert str(path) in message and '"weights_seed"' in message
    assert said in message and str(zlib.crc32(name.encode())) in message
    # and with the stated number the same file loads
    conf["weights_seed"] = zlib.crc32(name.encode())
    path.write_text(json.dumps(conf))
    assert arch.load_cell_config(str(path))["weights_seed"] == conf["weights_seed"]


# ---- the child: one model, other traffic -----------------------------------

@pytest.fixture
def engines(monkeypatch):
    """The program's two engine modules, put back after ``seed_engines``."""
    from docqa_tpu.engines import encoder as encoder_mod
    from docqa_tpu.engines import generate as generate_mod

    monkeypatch.setattr(generate_mod, "GenerateEngine",
                        generate_mod.GenerateEngine)
    monkeypatch.setattr(encoder_mod, "EncoderEngine",
                        encoder_mod.EncoderEngine)
    return generate_mod, encoder_mod


def leaves(tree):
    import jax
    import numpy as np

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("name, overlay", TINY, ids=[n for n, _ in TINY])
def test_two_seeds_give_one_decoder_tree_and_another_traffic(
        engines, name, overlay):
    import numpy as np

    from docqa_tpu.config import load_config

    generate_mod, encoder_mod = engines
    conf = arch.load_cell_config(os.path.join(ROOT, CONFIGS[name]["file"]),
                                 os.path.join(HERE, "data", overlay))
    cfg = load_config(env={}, overrides=child.program_overrides(conf))
    package = arch.load(conf)
    trees, encoders = [], []
    for seed in SEEDS:
        state = child.State()
        child.seed_engines(package, conf, seed, state)
        engine = generate_mod.GenerateEngine(cfg.decoder, gen=cfg.generate)
        assert "decoder_weights" in state.setup  # the benchmark's draw ran
        trees.append(leaves(engine.params))
        encoders.append(leaves(encoder_mod.EncoderEngine(cfg.encoder).params))
    assert len(trees[0]) == len(trees[1]) > 4
    for a, b in zip(*trees):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # ... and it is the draw the file states, not a constant of the code
    stated = leaves(package.weights.make_decoder_params(
        cfg.decoder, conf["weights_seed"] % 2**31, None))
    other = leaves(package.weights.make_decoder_params(
        cfg.decoder, (conf["weights_seed"] + 1) % 2**31, None))
    assert all(np.array_equal(a, b) for a, b in zip(trees[0], stated))
    assert not all(np.array_equal(a, b) for a, b in zip(trees[0], other))
    # the run's seed still draws the encoder
    assert not all(np.array_equal(a, b) for a, b in zip(*encoders))


@pytest.mark.parametrize("mix_name", sorted(
    {w["traffic"] for w in BENCH["workloads"]}))
def test_two_seeds_ask_in_another_order_about_other_patients(mix_name):
    mix = traffic.load(os.path.join(BENCH_DIR, "traffic", mix_name + ".json"))
    asked = []
    for seed in SEEDS:
        stream = traffic.questions(mix, seed, 2048, "client0")
        asked.append([next(stream) for _ in range(64)])
    assert asked[0] != asked[1]
    # the same multiset of kinds: a seed orders the work, it does not size it
    assert sorted(k for k, _ in asked[0]) == sorted(k for k, _ in asked[1])
    assert corpus.patient_chunks(SEEDS[0], 0) != corpus.patient_chunks(SEEDS[1], 0)


def test_check_prompts_follow_the_runs_seed():
    import numpy as np

    from harness import check

    spec = load(MISTRAL_FILE)["check"]
    a, _ = check.sample_prompts(SEEDS[0], 2048, 4, 2, spec)
    b, _ = check.sample_prompts(SEEDS[1], 2048, 4, 2, spec)
    assert a.shape == b.shape and not np.array_equal(a, b)


# ---- the load generator keeps to its own cores ------------------------------

@pytest.mark.parametrize("cores, split", [
    ({0, 1, 2, 3}, ([0, 1], [2, 3])),
    (range(13), (list(range(11)), [11, 12])),
    ({2, 5, 7, 11, 13}, ([2, 5, 7], [11, 13])),
    ({0, 1, 2}, None),
    ({0}, None),
], ids=["four", "the_chips_thirteen", "a_mask_with_holes", "three", "one"])
def test_the_split_leaves_both_processes_a_core(cores, split):
    import run

    assert run.split_cores(cores) == split
    if split is not None:
        server, mine = split
        assert server and len(mine) == 2 and not set(server) & set(mine)
        assert sorted(server + mine) == sorted(cores)


@pytest.mark.skipif(not hasattr(os, "sched_setaffinity"),
                    reason="no sched_setaffinity here")
def test_the_child_starts_on_the_servers_cores_and_the_parent_moves_off(
        monkeypatch, tmp_path):
    """``Child`` pins through the one thread the parent has at that point:
    the child inherits the server's cores, the parent ends on its two."""
    import argparse
    import subprocess

    import run

    mine = sorted(os.sched_getaffinity(0))
    if len(mine) < 4:
        pytest.skip("under four cores nothing is pinned")
    seen = {}

    class Popen:
        def __init__(self, cmd, **kw):
            seen["at_start"] = sorted(os.sched_getaffinity(0))
            self.pid, self.returncode = 0, 0

        def poll(self):
            return 0

    monkeypatch.setattr(subprocess, "Popen", Popen)
    args = argparse.Namespace(seed=1, trace=0, rehearsal="x")
    try:
        child = run.Child(args, {"chips": 1}, {"file": "f"}, str(tmp_path))
        after = sorted(os.sched_getaffinity(0))
        child.log.close()
        child.conn.close()
    finally:
        os.sched_setaffinity(0, mine)
    assert seen["at_start"] == mine[:-2] and after == mine[-2:]
    assert child.affinity == {"child": mine[:-2], "parent": mine[-2:]}


# ---- calibrate.py keeps the many draws -------------------------------------

def test_calibrate_draws_its_weights_from_each_seed(monkeypatch, capsys):
    import jax

    import calibrate
    from docqa_tpu.runtime import compile_cache
    from harness import check

    drawn, checked = [], []
    real_load = arch.load

    def load_spied(conf):
        package = real_load(conf)

        def make_decoder_params(dec_cfg, seed, mesh=None):
            drawn.append(seed)
            return {"seed": seed}

        package.weights = type("Weights", (), {
            **{k: staticmethod(v) for k, v in vars(package.weights).items()
               if callable(v)},
            "make_decoder_params": staticmethod(make_decoder_params),
        })
        return package

    def decoder_check(package, spec, engine, seed, **kw):
        checked.append((seed, engine.params["seed"]))
        return {"program": {"worst_row": 0.0}, "control": {"worst_row": 1.0},
                "kv_bits": 16, "controls": {}, "kv_only": {}}

    monkeypatch.setattr(arch, "load", load_spied)
    monkeypatch.setattr(check, "decoder_check", decoder_check)
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "calibrate.py", "--config", MISTRAL_FILE,
        "--overlay", os.path.join(HERE, "data", "tiny_overlay.json"),
        "--seeds", "5,4295000012",
    ])
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert calibrate.main() == 0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    stated = load(MISTRAL_FILE)["weights_seed"] % 2**31
    assert drawn == [5, 4295000012 % 2**31] and stated not in drawn
    assert checked == [(5, 5), (4295000012, 4295000012 % 2**31)]
    assert "largest program error" in capsys.readouterr().out
