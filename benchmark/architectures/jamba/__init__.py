"""AI21 Jamba's block for the benchmark: a stack of Mamba-1 state-space
layers and a few position-free GQA / MQA attention layers, every layer
followed by a dense SwiGLU MLP
(``https://huggingface.co/ai21labs/AI21-Jamba2-3B``, ``model_type``
``jamba``).  ``keys`` and ``shapes`` are standard library; ``weights`` and
``reference`` import JAX.  Found by ``harness/arch.py`` through a
configuration's ``"architecture": "jamba"``; this file imports nothing."""
