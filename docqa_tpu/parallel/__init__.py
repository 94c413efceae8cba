from docqa_tpu.parallel.ring_attention import (
    ring_attention,
    ring_attention_local,
    ulysses_attention,
)
from docqa_tpu.parallel.sharding import (
    cache_pspecs,
    decoder_param_pspecs,
    shard_decoder_params,
)

__all__ = [
    "decoder_param_pspecs",
    "cache_pspecs",
    "shard_decoder_params",
    "ring_attention",
    "ring_attention_local",
    "ulysses_attention",
]
