"""The same tensors as the block the program runs: made, dequantized and
controlled as ``architectures/mistral/weights.py`` does (a package may
import from another; a real second architecture has tensors of its own)."""

from architectures.mistral.weights import (  # noqa: F401
    controls_for,
    dequantized,
    kv_only_controls,
    make_decoder_params,
)
