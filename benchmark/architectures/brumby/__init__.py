"""Brumby-14B-Base's block for the benchmark: the Qwen3-14B trunk with
every attention replaced by gated power retention of degree 2
(``https://huggingface.co/manifestai/Brumby-14B-Base``; Manifest AI,
"Scaling Context Requires Rethinking Attention", arXiv:2507.04239).
``keys`` and ``shapes`` are standard library; ``weights`` and ``reference``
import JAX.  Found by ``harness/arch.py`` through a configuration's
``"architecture": "brumby"``; this file imports nothing."""
