"""The looped GQA block of ByteDance's Ouro (``model_type`` ``ouro``;
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models"):
the Llama block with a norm on each sublayer's output, the whole stack run
``total_ut_steps`` times over the same parameters, the final norm closing
every step, K and V kept per (step, layer).  The block of
``docqa_tpu/models/decoder.py`` with ``loop_steps`` and ``sandwich_norm``.
Importing this package imports nothing: ``keys`` and ``shapes`` are
standard library, ``weights`` and ``reference`` import JAX."""
