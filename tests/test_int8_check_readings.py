"""What ``tests/benchmark/test_benchmark_routing.py::
test_a_block_that_does_not_route_reads_what_it_read[mistral-*]`` still holds
after PR 41, asserted again.

That test (PR 28) pins what ``check.decoder_check`` reads of the PROGRAM, at
a tiny int8 preset, to the digits recorded on commit a9f6570.  PR 41 changed
the program's int8 arithmetic on purpose (``decoder._qmatmul`` scales the
dot's output, not the weight), so the recorded digits are outdated; the
file belongs to the benchmark (``BENCHMARK.json`` ``paths``) and only a
``benchmark`` PR may re-record them, so its three ``mistral`` ids are marked
``xfail(strict=True)`` from ``tests/conftest.py`` and every line of it is
asserted here with the digits of the new arithmetic (the ``renamed_arch``
ids, which pin no digit, still run there).  When the pins are re-recorded
in place, delete this file and the mark.
"""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmark"),
           os.path.join(ROOT, "tests", "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# decoder_check of the Mistral package on PR 41's tree, the pinned test's
# tiny preset, int8 weights, seeds 1 2 3: the program's worst row and mean
# row, a_int8, kv_int8 alone.  The mean row fell on every seed (a9f6570: 0.013819, 0.012530,
# 0.012588); the worst row of twelve, at a hidden size of 64, moved both
# ways (0.023086, 0.019840, 0.018671); the controls' digits are the pinned
# test's own — their arithmetic is the harness's and did not move.
AFTER = {
    1: (0.02335379458963871, 0.011408131569623947, 0.037905290722846985,
        0.013057383708655834),
    2: (0.018337612971663475, 0.01101775374263525, 0.040261708199977875,
        0.01739734597504139),
    3: (0.019161934033036232, 0.010476773604750633, 0.03831703960895538,
        0.010047242976725101),
}


@pytest.mark.parametrize("seed", sorted(AFTER))
def test_the_mistral_check_reads_what_the_new_arithmetic_reads(seed):
    from test_benchmark_architectures import MISTRAL_FILE, load

    from docqa_tpu.config import DecoderConfig
    from harness import arch, check

    package = arch.load(load(MISTRAL_FILE))
    spec = load(MISTRAL_FILE)["check"]
    n_rows = 1 + check.DECODE_STEPS * 4
    cfg = DecoderConfig(
        vocab_size=512, hidden_dim=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=1024,
        rope_theta=10000.0, sliding_window=1024, dtype="bfloat16",
        quantize_weights=True, quant_bits=8,
    )
    engine = types.SimpleNamespace(
        cfg=cfg, use_flash=False,
        params=package.weights.make_decoder_params(cfg, seed))
    out = check.decoder_check(
        package, spec, engine, seed, control=True, n_blocks=256,
        block_size=16, seq_capacity=1024, n_lanes=4, step_width=4)
    assert "routing" not in out and out["kv_bits"] == 16
    ids, lengths = check.sample_prompts(seed, 512, 4, n_rows - 1, spec)
    want = check.reference_logits(package, engine.params, cfg, ids, lengths,
                                  n_rows)
    got, bits, record = check.program_logits(
        engine, ids, lengths, check.DECODE_STEPS, 4, 256, 16, 1024)
    assert record is None and bits == 16
    assert out["program"] == check.logit_error(got, want)
    worst, mean, a_int8, kv_int8 = AFTER[seed]
    assert out["program"]["worst_row"] == pytest.approx(worst, rel=1e-6)
    assert out["program"]["mean_row"] == pytest.approx(mean, rel=1e-6)
    assert set(out["controls"]) == {"w_int4", "a_int8", "a_fp8"}
    assert out["control"] == out["controls"]["a_int8"]
    assert out["control"]["worst_row"] == pytest.approx(a_int8, rel=1e-6)
    assert out["kv_only"]["kv_int8"]["worst_row"] == pytest.approx(
        kv_int8, rel=1e-6)
