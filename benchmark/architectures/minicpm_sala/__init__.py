"""MiniCPM-SALA's block for the benchmark: a stack of lightning (decayed
linear) attention layers and InfLLM-V2 block-sparse attention layers
(``https://huggingface.co/openbmb/MiniCPM-SALA``).  ``keys`` and ``shapes``
are standard library; ``weights`` and ``reference`` import JAX.  Found by
``harness/arch.py`` through a configuration's ``"architecture":
"minicpm_sala"``; this file imports nothing."""
