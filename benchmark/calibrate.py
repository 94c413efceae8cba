#!/usr/bin/env python3
"""Read the two numbers every limit of ``correct`` is set from, on the
chip, at the configuration's own size, many seeds in one process:

* the largest error sound runs of the program give, and
* the smallest error the lower-precision controls give (the plain
  reference put in the program's place, one step below each thing the
  configuration states — the ``weights.controls_for`` of its architecture
  package; for the Mistral block: int4 weights below
  int8, int8 and float8 activations with the cache below bfloat16; int8
  rows below bfloat16 rows for the store), and beside them what the
  cache alone in 8 bits reads (``weights.kv_only_controls``).

For a block that routes (README.md, "A block that routes") every logit
error is read under replay of the program's routing record, and per seed
the row also holds the sound run's largest ``router_choice_gap``, the gap
of each routing control (a ``Control`` with a ``router``), and the share of
decisions in which the program's set is not the reference's own.

    python3 benchmark/calibrate.py --config benchmark/configs/<name>.json \
        --seeds 1,2,3 [--retrieval-seeds 1,2,3]

No server, no traffic, no timed window: weights from the seed (each of
``--seeds`` draws a tree of its own — NOT the one draw the file's
``weights_seed`` states for the benchmark's runs: a limit is what many
draws read), the program's paged forwards, the reference, the control.  The benchmark's own
runs never call this; ``tests/benchmark`` holds the same comparison at a
size a test run can hold.  PERF.md records what this printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def decoder_summary(rows) -> list:
    """The lines a limit is set from, over the seeds' ``decoder_check``
    rows: the sound runs' largest reading and the controls' smallest."""
    if not rows:
        return []
    lines = [
        "decoder: largest program error "
        f"{max(r['program']['worst_row'] for r in rows)} "
        "smallest control error "
        f"{min(r['control']['worst_row'] for r in rows)} "
        f"kv pool bits {sorted({r['kv_bits'] for r in rows})}"
    ]
    for group in ("controls", "kv_only"):
        for name in rows[0][group]:
            values = [r[group][name]["worst_row"] for r in rows]
            lines.append(f"  {group} {name}: {min(values):.6g} .. {max(values):.6g}")
    if "routing" in rows[0]:
        # a block that routes: every error above was read under replay of
        # the program's record; the choices have a limit of their own
        gaps = [r["routing"]["worst_gap"] for r in rows]
        share = [r["routing"]["differing_share"] for r in rows]
        lines.append(
            f"router: largest choice gap of a sound run {max(gaps):.6g} "
            f"(from {min(gaps):.6g}); decisions that differ from the "
            f"reference's own {min(share):.4f} .. {max(share):.4f} of "
            f"{rows[0]['routing']['decisions']}"
        )
        for name, first in rows[0]["controls"].items():
            if "worst_gap" in first:
                values = [r["controls"][name]["worst_gap"] for r in rows]
                lines.append(f"  routing control {name}: choice gap "
                             f"{min(values):.6g} .. {max(values):.6g}")
    return lines


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--overlay", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--retrieval-seeds", default="")
    args = ap.parse_args()

    import jax
    import numpy as np

    from docqa_tpu.config import load_config
    from docqa_tpu.runtime.compile_cache import configure_compile_cache
    from harness import arch, check
    from harness.child import program_overrides

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    conf = arch.load_cell_config(args.config, args.overlay)
    cfg = load_config(env={}, overrides=program_overrides(conf))
    package = arch.load(conf)
    devices = jax.devices()
    print(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}")
    mesh = None
    if len(devices) > 1:
        from docqa_tpu.runtime.mesh import make_mesh

        mesh = make_mesh(cfg.mesh)
    gen = cfg.generate
    block = int(gen.kv_block_size)
    capacity = -(-cfg.decoder.max_seq_len // block) * block
    n_blocks = max(int(gen.kv_pool_tokens) // block,
                   gen.max_concurrent * capacity // block)
    use_flash = (
        jax.default_backend() == "tpu" and cfg.decoder.head_dim % 64 == 0
    )
    out = {"decoder": [], "retrieval": []}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t = time.monotonic()
        params = package.weights.make_decoder_params(
            cfg.decoder, seed % (2**31), mesh
        )
        engine = types.SimpleNamespace(
            cfg=cfg.decoder, params=params, use_flash=use_flash
        )
        row = check.decoder_check(
            package, conf["check"], engine, seed, n_blocks=n_blocks,
            block_size=block,
            seq_capacity=capacity, n_lanes=int(gen.max_concurrent),
            step_width=max(1, int(gen.speculative_k)), mesh=mesh, control=True,
        )
        row.update(seed=seed, seconds=round(time.monotonic() - t, 1))
        out["decoder"].append(row)
        print("decoder", json.dumps(row), flush=True)
        del params, engine
    for seed in [int(s) for s in args.retrieval_seeds.split(",") if s]:
        from docqa_tpu.index.store import VectorStore

        rng = np.random.default_rng([seed % (2**31), 3])
        n, dim = int(conf["corpus"]["rows"]), cfg.store.dim
        rows = rng.standard_normal((n, dim), dtype=np.float32)
        store = VectorStore(cfg.store, mesh=mesh)
        store.add(rows, [{"doc_id": "fill"}] * n)
        stored = check.to_bf16(check.unit_rows(rows))
        del rows
        q = check.retrieval_queries(stored, seed)
        k = int(cfg.store.default_k)
        row = {
            "seed": seed,
            "program": check.retrieval_error(
                stored, q, k, check.store_search(store)
            ),
            "control": check.retrieval_error(
                stored, q, k, check.control_search(stored)
            ),
        }
        out["retrieval"].append(row)
        print("retrieval", json.dumps(row), flush=True)
        del store, stored
    for line in decoder_summary(out["decoder"]):
        print(line)
    if out["retrieval"]:
        print("retrieval: largest program error",
              max(r["program"] for r in out["retrieval"]),
              "smallest control error",
              min(r["control"] for r in out["retrieval"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
