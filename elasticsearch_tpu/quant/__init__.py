"""Vector codec subsystem: the quantization ladder + two-phase rescore.

`quant/codec.py` is the one owner of every storage-encoding recipe
(f32 / bf16 / int8 / int4 packed-nibble / binary sign-bit) — host and
device twins, per-row aux arrays, bytes-per-doc accounting. `quant/
tokens.py` is the token-block variant for late-interaction
(multi-vector) fields — metric prep, lane padding, per-token codec
rows, pooled coarse centroids. `quant/rescore.py` is the exact-rescore
half of two-phase serving. Everything that quantizes
(`ops/quantization`, `ops/pallas_knn_binned`'s query path, the IVF
partition upload, the sharded mesh build, the token-block extraction)
routes through here; tpulint TPU013 keeps it that way.
"""

from elasticsearch_tpu.quant import codec, rescore, tokens
from elasticsearch_tpu.quant.codec import (
    CODECS,
    PACKED_ENCODINGS,
    bytes_per_doc,
    encoding_of,
    get,
    is_packed,
)
from elasticsearch_tpu.quant.rescore import (
    DEFAULT_OVERSAMPLE,
    coarse_window,
    rescore_boards,
)

__all__ = [
    "CODECS", "PACKED_ENCODINGS", "bytes_per_doc", "codec", "encoding_of",
    "get", "is_packed", "rescore", "tokens", "DEFAULT_OVERSAMPLE",
    "coarse_window", "rescore_boards",
]
