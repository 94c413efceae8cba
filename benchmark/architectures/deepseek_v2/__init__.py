"""The DeepSeek-V2 decoder block (arXiv:2405.04434): RMSNorm, multi-head
LATENT attention (low-rank query and key/value projections, a decoupled
rotary key shared by every head, YaRN-scaled RoPE), one leading dense
SwiGLU layer, then group-limited routed experts with shared experts, of
which a process holds a contiguous range.  The block of
``docqa_tpu/models/latent.py``.  Importing this package imports nothing:
``keys`` and ``shapes`` are standard library, ``weights`` and ``reference``
import JAX."""
