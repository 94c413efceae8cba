"""The Mistral / Llama decoder block (arXiv:2310.06825): RMSNorm,
grouped-query attention with RoPE and a sliding window, SwiGLU MLP; float
or per-channel int8 weights.  The block of ``docqa_tpu/models/decoder.py``.
Importing this package imports nothing: ``keys`` and ``shapes`` are
standard library, ``weights`` and ``reference`` import JAX."""
