"""The seam of ISSUE 27: a configuration names the package under
``benchmark/architectures/`` that holds its key map, seeded weights, plain
reference and byte counts.

* A second architecture arrives as files: in a copy of the tree a package,
  a configuration, a ``configs`` entry and a cell are ADDED, the cell's
  rehearsal is ``correct``, and every file that was there hashes as before.
* The package the file names is the one consulted: the same cell with that
  package's reference broken is not ``correct``; a package whose
  ``shapes.py`` charges twice the bytes reads twice the roofline share.
* The move changed no seeded weight; an unknown architecture or an
  unmapped published key is an error that names the file and the key.
"""

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import arch  # noqa: E402

MISTRAL_FILE = os.path.join(BENCH_DIR, "configs", "mistral-7b-int8.json")
OVERLAY = os.path.join(HERE, "data", "tiny_overlay.json")
# the fixture package's names for the published keys of the Mistral file
RENAMED = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
    "vocab_size": "vocab", "max_position_embeddings": "max_positions",
    "rope_theta": "rope_base", "rms_norm_eps": "norm_epsilon",
    "sliding_window": "attention_window", "weight_quantization": "weights_in",
}
DROPPED = {"model_type", "hidden_act", "tie_word_embeddings", "torch_dtype"}


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def renamed(conf):
    """A configuration of the Mistral block under the fixture's key names."""
    out = {RENAMED.get(k, k): v for k, v in conf.items() if k not in DROPPED}
    if "architecture" in out:
        out["architecture"] = "renamed"
    return out


def hashes(tree):
    """sha256 of every file the benchmark is made of, in ``tree``."""
    out = {}
    for base in ("BENCHMARK.json", "benchmark"):
        top = os.path.join(tree, base)
        walk = os.walk(top) if os.path.isdir(top) else [(tree, [], [base])]
        for folder, dirs, files in walk:
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                path = os.path.join(folder, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, tree)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def child_env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def copy_of_the_tree(tree):
    """The benchmark copied into ``tree``, the rest of the repo linked."""
    for name in os.listdir(ROOT):
        if (name.startswith(".")
                or name in ("benchmark", "BENCHMARK.json", "chiprun_out")):
            continue
        os.symlink(os.path.join(ROOT, name), tree / name)
    shutil.copytree(BENCH_DIR, tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)


def add_cell(bench, config, cell):
    """A configuration and a cell of ``rag_closed``'s traffic added to a
    BENCHMARK.json as a later PR adds them: entries, and the cell's name
    on the list of every metric ``rag_closed`` reports."""
    bench["configs"].append(config)
    bench["workloads"].append(cell)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "rag_closed" in metric.get("workloads", []):
            metric["workloads"].append(cell["name"])


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the tree (the benchmark copied, the rest linked) to which
    a second architecture, its configuration and its cell are added as a
    later PR would add them.  Returns (tree, hashes before, overlay)."""
    tree = tmp_path_factory.mktemp("tree")
    copy_of_the_tree(tree)
    before = hashes(tree)

    archs = tree / "benchmark" / "architectures"
    shutil.copytree(os.path.join(HERE, "data", "renamed_arch"),
                    archs / "renamed",
                    ignore=shutil.ignore_patterns("__pycache__"))
    # a package of the parent's two modules that charges twice the bytes
    os.makedirs(archs / "twice")
    (archs / "twice" / "__init__.py").write_text("")
    (archs / "twice" / "keys.py").write_text(
        "from architectures.mistral.keys import program_overrides  # noqa\n")
    (archs / "twice" / "shapes.py").write_text(
        "from architectures.mistral import shapes as _m\n\n\n"
        "def decode_step_min_bytes(conf, live_kv_tokens, chips):\n"
        "    return 2 * _m.decode_step_min_bytes(conf, live_kv_tokens, chips)\n")
    # a file draws the model its OWN name states (ISSUE 58)
    dump(dict(renamed(load(MISTRAL_FILE)),
              weights_seed=arch.stated_weights_seed("renamed-7b-int8")),
         tree / "benchmark" / "configs" / "renamed-7b-int8.json")
    overlay = tree / "renamed_overlay.json"
    dump(renamed(load(OVERLAY)), overlay)

    bench = load(tree / "BENCHMARK.json")
    add_cell(bench, {
        "name": "renamed-7b-int8", "source": "https://example.org/config.json",
        "file": "benchmark/configs/renamed-7b-int8.json", "reduced": [],
        "why": "the block the program runs, under other published key names",
    }, {
        "name": "rag_closed_renamed", "config": "renamed-7b-int8",
        "traffic": "rag_closed", "chips": 1, "why": "the seam, as files",
    })
    dump(bench, tree / "BENCHMARK.json")
    return tree, before, str(overlay)


def rehearse(tree, overlay, trace="0"):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rag_closed_renamed", "--seed", "4294967327", "--seconds", "2",
         "--trace", trace, "--rehearsal", overlay],
        cwd=tree, env=child_env(), capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def compared(out, name):
    line = next(ln for ln in out.splitlines()
                if ln.startswith(f"compared {name}:"))
    value, limit = line.split(": ", 1)[1].split(" (limit ")
    return float(value), float(limit.rstrip(")"))


@pytest.fixture(scope="module")
def sound(grown):
    tree, _before, overlay = grown
    return rehearse(tree, overlay, trace="1")


def test_a_second_architecture_and_its_cell_run_as_added_files(grown, sound):
    result, out = sound
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    value, limit = compared(out, "decoder_logit_rel_err")
    assert 0 < value < limit
    # the cell reports what rag_closed reports: same readers, same traffic
    assert {"admit_wait_p50_ms", "first_token_wait_p50_ms", "window_tok_s",
            "kv_pool_used_share"} <= set(result["metrics"])


def test_no_file_that_was_there_changed(grown, sound):
    tree, before, _overlay = grown
    after = hashes(tree)
    edited = [p for p in before if p != "BENCHMARK.json"
              and after.get(p) != before[p]]
    assert not edited
    added = set(after) - set(before)
    assert added and all(
        p.startswith(("benchmark/architectures/renamed/",
                      "benchmark/architectures/twice/",
                      "benchmark/configs/renamed-")) for p in added), added
    # BENCHMARK.json: entries were added, and cells to metrics' lists;
    # what was there reads as it read
    was, now = load(os.path.join(ROOT, "BENCHMARK.json")), load(
        tree / "BENCHMARK.json")
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == was[key]
    for key in ("configs", "workloads"):
        assert now[key][:len(was[key])] == was[key]
    for key in ("end_to_end", "per_layer"):
        assert len(now[key]) == len(was[key])
        for a, b in zip(was[key], now[key]):
            b = dict(b, workloads=[w for w in b.get("workloads", [])
                                   if w != "rag_closed_renamed"])
            assert b == dict(a, workloads=a.get("workloads", []))


def test_the_named_package_is_the_one_consulted(grown, sound):
    """The same cell, its package's reference broken on purpose (the final
    norm's gain doubled): not ``correct``, by the logit comparison."""
    tree, _before, overlay = grown
    path = tree / "benchmark" / "architectures" / "renamed" / "reference.py"
    text = path.read_text()
    assert "FINAL_NORM_GAIN = 1.0" in text
    path.write_text(text.replace("FINAL_NORM_GAIN = 1.0",
                                 "FINAL_NORM_GAIN = 2.0"))
    try:
        result, out = rehearse(tree, overlay)
    finally:
        path.write_text(text)
    assert result["correct"] is False and result["failed"] == 0
    value, limit = compared(out, "decoder_logit_rel_err")
    assert value > limit
    for name in ("kv_cache_bits_missing", "retrieval_score_err"):
        value, limit = compared(out, name)
        assert value <= limit, name  # the reference alone was wrong


def test_a_package_that_charges_twice_the_bytes_reads_twice_the_roofline(grown):
    """At the reader's level (a rehearsal prints no device metric): the
    recorded trace of ``data/`` and a hand-made ``ctx``."""
    tree, _before, _overlay = grown
    code = """
import json, sys
sys.path.insert(0, "benchmark")
from harness import xplane
from readers import decode_roofline
conf = json.load(open(%r))
ctx = {"trace": xplane.reduce_file(%r), "polled": [{"kv_tokens": 4096.0}],
       "device": {"kind": "TPU v5 lite"}, "cell": {"chips": 1}}
out = {}
for name in ("mistral", "twice"):
    ctx["conf"] = dict(conf, architecture=name)
    out[name] = decode_roofline.read(ctx, program="decode", exclude="prefill")
print(json.dumps(out))
""" % (MISTRAL_FILE, os.path.join(HERE, "data", "tiny_trace.xplane.pb"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=tree,
                          env=child_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # 7.2 GB of weights + 4096 tokens of keys and values at 819 GB/s,
    # over the recorded program's 1000 us
    conf = load(MISTRAL_FILE)
    least = arch.load_shapes(conf).shapes.decode_step_min_bytes(conf, 4096.0, 1)
    assert out["mistral"] == pytest.approx(100 * least / 819e9 / 1000e-6)
    assert out["twice"] == pytest.approx(2 * out["mistral"])


# ---- the move itself ------------------------------------------------------

# sha256 over layer 1 and both ends of the tiny tree of seed 7, read on the
# parent commit (harness/weights.py before the move) with the code below
BEFORE_THE_MOVE = {
    True: "1c28cf8fb0d5258b149ffd2f066510a312f7a152363c6aff41712b20007a2d04",
    False: "2e402e98d4c1b8628ec971c5a3bd3a51345c5c509bec8ca8b3b536cba7b6d492",
}


@pytest.mark.parametrize("quantized", [True, False], ids=["int8", "bf16"])
def test_the_seeded_weights_are_the_arrays_they_were(quantized):
    from docqa_tpu.config import DecoderConfig

    cfg = DecoderConfig(
        vocab_size=512, hidden_dim=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=1024,
        rope_theta=10000.0, sliding_window=1024, dtype="bfloat16",
    )
    cfg = dataclasses.replace(cfg, quantize_weights=quantized, quant_bits=8)
    params = arch.load(load(MISTRAL_FILE)).weights.make_decoder_params(cfg, 7)
    h = hashlib.sha256()
    for name in sorted(params):
        if name.startswith("l1_") or name in ("tok_emb", "lm_head",
                                              "lm_head__scale"):
            a = np.asarray(params[name])
            h.update(name.encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    assert h.hexdigest() == BEFORE_THE_MOVE[quantized]


def test_the_mistral_file_compares_at_the_sizes_its_limits_were_read_at():
    from harness import check

    assert load(MISTRAL_FILE)["check"] == {
        "prompt_lengths": [150, 290, 380, 450], "lane_rows": 512,
    }
    assert (check.LENGTH_JITTER, check.DECODE_STEPS) == (40, 2)


def test_the_compared_sizes_follow_the_file():
    from harness import check

    spec = {"prompt_lengths": [2000, 3000], "lane_rows": 3072}
    ids, lengths = check.sample_prompts(5, 512, 4, 1, spec)
    assert ids.shape == (4, 3072)
    assert [int(n) // 1000 for n in lengths] == [2, 3, 2, 3]
    with pytest.raises(ValueError, match="lane_rows"):
        check.sample_prompts(5, 512, 4, 1, dict(spec, lane_rows=3000))
    with pytest.raises(ValueError, match="does not fit"):
        check.sample_prompts(5, 512, 4, 1, dict(spec, lane_rows=2048))


@pytest.mark.parametrize("change, names", [
    ({"architecture": "moe_latent"}, ['"architecture"', "moe_latent"]),
    ({"architecture": None}, ['"architecture"', "None"]),
    ({"kv_lora_rank": 512}, ['"kv_lora_rank"', '"mistral"']),
    ({"n_routed_experts": 64, "first_k_dense_replace": 1},
     ['"first_k_dense_replace"', '"n_routed_experts"']),
    ({"hidden_act": "gelu"}, ['"hidden_act"', "gelu"]),
    ({"tie_word_embeddings": True}, ['"tie_word_embeddings"', "True"]),
], ids=["unknown_architecture", "no_architecture", "unmapped_key",
        "unmapped_keys", "a_block_the_program_has_not", "tied_embeddings"])
def test_what_the_package_cannot_take_is_an_error_with_file_and_key(
        tmp_path, change, names):
    path = tmp_path / "broken.json"
    dump({**load(MISTRAL_FILE), **change}, path)
    with pytest.raises(arch.ConfigError) as e:
        arch.load_cell_config(str(path))
    assert str(path) in str(e.value)
    for name in names:
        assert name in str(e.value), str(e.value)


def test_a_package_without_its_surface_is_refused(tmp_path, monkeypatch):
    (tmp_path / "architectures" / "half").mkdir(parents=True)
    (tmp_path / "architectures" / "half" / "__init__.py").write_text("")
    (tmp_path / "architectures" / "half" / "keys.py").write_text(
        "def program_overrides(conf):\n    return {}\n")
    (tmp_path / "architectures" / "half" / "shapes.py").write_text(
        "def weight_bytes(conf):\n    return {}\n")
    import architectures

    monkeypatch.setattr(arch, "ARCH_DIR", str(tmp_path / "architectures"))
    monkeypatch.setattr(architectures, "__path__", architectures.__path__
                        + [str(tmp_path / "architectures")])
    with pytest.raises(arch.ConfigError,
                       match=r"shapes\.py has no decode_step_min_bytes"):
        arch.load_shapes({"architecture": "half"})


def test_the_harness_names_no_tensor_and_no_published_key():
    """The grep of ISSUE 27: what depends on the block is in its package."""
    import re

    pattern = re.compile(r"wq|w_gate|num_key_value_heads|intermediate_size")
    files = [os.path.join(BENCH_DIR, "run.py"),
             os.path.join(BENCH_DIR, "calibrate.py")]
    for folder in ("harness", "readers"):
        files += [os.path.join(BENCH_DIR, folder, f)
                  for f in os.listdir(os.path.join(BENCH_DIR, folder))
                  if f.endswith(".py")]
    hits = []
    for path in files:
        with open(path, encoding="utf-8") as f:
            hits += [f"{path}:{i}" for i, line in enumerate(f, 1)
                     if pattern.search(line)]
    assert not hits
    for gone in ("reference.py", "shapes.py"):
        assert not os.path.exists(os.path.join(BENCH_DIR, "harness", gone))
