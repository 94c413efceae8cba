"""The Arcee AFMoE decoder block (Trinity; ``model_type`` ``afmoe``): RMSNorm
before AND after each sublayer, GQA attention with per-head q / k norms and
a sigmoid output gate — rotated and held to a sliding window in the
``sliding_attention`` layers, position-free over every row in the
``full_attention`` ones —, leading dense SwiGLU layers, then routed experts
(sigmoid scores, a selection-only bias, the taken scores normalised and
scaled) with a shared expert, of which a process holds a contiguous range.
The stack of mixer kinds of ``docqa_tpu/models/hybrid.py`` with the routed
feed-forward of ``docqa_tpu/models/routed.py``.  Importing this package
imports nothing: ``keys`` and ``shapes`` are standard library, ``weights``
and ``reference`` import JAX."""
