"""Vector codec registry: the ONE owner of every encoding recipe.

The reference stores dense vectors only as f32 BinaryDocValues
(`DenseVectorFieldMapper.java:184-226`); on TPU the whole edge is
density — how many doc vectors fit in 16 GB of HBM — so the storage
encoding is a first-class subsystem, not a dtype string scattered over
call sites. This module owns the quantization ladder:

    encoding   device matrix          per-row aux      bytes/row @768d
    f32        f32 [N, D]             —                3072
    bf16       bf16 [N, D]            —                1536
    int8       int8 [N, D]            scale f32        768 (+4)
    int4       uint8 [N, D/2]         scale f32        384 (+4)   packed nibbles
    binary     uint32 [N, D/32]       mean|x| f32      96  (+4)   sign bits

Every codec exposes a host (numpy) encoder, a device (jnp, traceable)
twin, and a host decode twin — the np/jnp pairs are BYTE-identical by
construction and pinned by tests/test_quant_codecs.py, so the host
build path, the device query-quantization path, and the bench harness
can never drift apart. The arithmetic (scale-divide-round-clip,
sign-bit packing) lives HERE and nowhere else: tpulint TPU013 fires on
hand-rolled copies outside `elasticsearch_tpu/quant/`.

Scoring contracts per rung:

* int8 / int4 — symmetric per-row scales; the matmul runs on the
  packed planes and scores de-scale after (`ops/knn._block_scores`,
  `ops/knn_ivf`, `ops/pallas_ivf_fused`).
* binary — sign-bit Hamming: for unit vectors,
  dot(sign q, sign v) = D - 2·ham(q, v), so the coarse score is the
  affine popcount form (a monotone proxy for cosine). Binary (and
  int4, by default) serve two-phase: coarse top-(k·oversample) on the
  packed encoding, exact f32 rescore of the window through the
  columnar RowSource gather (`quant/rescore.py`).
"""

from __future__ import annotations

import threading
from typing import Dict, NamedTuple

import numpy as np

# chunk budget for host encoders: never materialize a second
# corpus-sized f32 temp (the 10M x 768 corpus is ~30 GB)
_CHUNK_BYTES = 64 << 20

# encoding name <-> device matrix dtype string (the reverse map the
# store and the segments re-encode selector read off a live corpus)
MATRIX_DTYPES = {
    "f32": "float32",
    "bf16": "bfloat16",
    "int8": "int8",
    "int4": "uint8",
    "binary": "uint32",
}
_ENCODING_BY_DTYPE = {v: k for k, v in MATRIX_DTYPES.items()}

# encodings whose device matrix is bit-packed (scored via the packed
# planes, served two-phase with exact rescore by default)
PACKED_ENCODINGS = ("int4", "binary")


def encoding_of(matrix_dtype) -> str:
    """Encoding name for a device matrix dtype (str or np/jnp dtype)."""
    return _ENCODING_BY_DTYPE.get(str(matrix_dtype), "f32")


class Encoded(NamedTuple):
    """One host-encoded row block: packed data + per-row aux scales."""

    data: np.ndarray     # [n, W] packed rows (dtype per codec)
    scales: np.ndarray   # [n] f32 per-row aux (ones when unused)


class VectorCodec:
    """One rung of the ladder. Subclasses own the arithmetic."""

    name = ""
    packed_np_dtype = np.float32

    def packed_width(self, dims: int) -> int:
        """Packed columns per row."""
        return dims

    def row_bytes(self, dims: int) -> int:
        """Packed matrix bytes per row."""
        return self.packed_width(dims) * np.dtype(self.packed_np_dtype).itemsize

    def aux_bytes(self) -> int:
        """Per-row aux bytes (scales)."""
        return 4

    def bytes_per_doc(self, dims: int) -> int:
        """Resident device bytes per doc: packed row + scales + the f32
        sq-norm every corpus carries — the number the density ladder
        bench and `_nodes/stats indices.knn` report."""
        return self.row_bytes(dims) + self.aux_bytes() + 4

    # -------------------------------------------------------------- host
    def encode_np(self, rows: np.ndarray) -> Encoded:  # pragma: no cover
        raise NotImplementedError

    def decode_np(self, data: np.ndarray,
                  scales: np.ndarray) -> np.ndarray:  # pragma: no cover
        raise NotImplementedError

    # ------------------------------------------------------------ device
    def encode_jnp(self, rows):  # pragma: no cover
        """Traceable twin of encode_np: (data, scales) jnp arrays,
        byte-identical to the host encoder on identical input."""
        raise NotImplementedError


class _F32Codec(VectorCodec):
    name = "f32"

    def aux_bytes(self) -> int:
        return 0

    def encode_np(self, rows: np.ndarray) -> Encoded:
        rows = np.asarray(rows, dtype=np.float32)
        return Encoded(rows, np.ones(len(rows), dtype=np.float32))

    def decode_np(self, data, scales):
        return np.asarray(data, dtype=np.float32)

    def encode_jnp(self, rows):
        import jax.numpy as jnp
        rows = rows.astype(jnp.float32)
        return rows, jnp.ones((rows.shape[0],), dtype=jnp.float32)


class _BF16Codec(VectorCodec):
    name = "bf16"

    def packed_width(self, dims: int) -> int:
        return dims

    def row_bytes(self, dims: int) -> int:
        return dims * 2

    def aux_bytes(self) -> int:
        return 0

    def encode_np(self, rows: np.ndarray) -> Encoded:
        import ml_dtypes
        rows = np.asarray(rows, dtype=np.float32).astype(ml_dtypes.bfloat16)
        return Encoded(rows, np.ones(len(rows), dtype=np.float32))

    def decode_np(self, data, scales):
        return np.asarray(data, dtype=np.float32)

    def encode_jnp(self, rows):
        import jax.numpy as jnp
        rows = rows.astype(jnp.bfloat16)
        return rows, jnp.ones((rows.shape[0],), dtype=jnp.float32)


class _Int8Codec(VectorCodec):
    """Per-row symmetric int8: scale = max|row|/127 (1e-30 floor)."""

    name = "int8"
    packed_np_dtype = np.int8

    def encode_np(self, rows: np.ndarray) -> Encoded:
        rows = np.asarray(rows, dtype=np.float32)
        n = rows.shape[0]
        q8 = np.empty(rows.shape, dtype=np.int8)
        scales = np.empty((n,), dtype=np.float32)
        chunk = max(1, _CHUNK_BYTES // max(rows.shape[1] * 4, 1))
        for lo in range(0, n, chunk):
            hi = lo + chunk
            block = rows[lo:hi]
            s = np.maximum(np.abs(block).max(axis=-1), 1e-30) / 127.0
            scales[lo:hi] = s
            q8[lo:hi] = np.clip(np.round(block / s[:, None]),
                                -127, 127).astype(np.int8)
        return Encoded(q8, scales)

    def decode_np(self, data, scales):
        return data.astype(np.float32) * np.asarray(scales)[:, None]

    def encode_jnp(self, rows):
        import jax.numpy as jnp
        rows = rows.astype(jnp.float32)
        max_abs = jnp.max(jnp.abs(rows), axis=-1)
        scales = jnp.maximum(max_abs, 1e-30) / 127.0
        q = jnp.clip(jnp.round(rows / scales[:, None]),
                     -127, 127).astype(jnp.int8)
        return q, scales


class _Int4Codec(VectorCodec):
    """Packed-nibble symmetric int4: scale = max|row|/7, two dims per
    byte (even dim in the low nibble, odd in the high), levels in
    [-7, 7] stored offset-by-8 so every nibble is a valid level."""

    name = "int4"
    packed_np_dtype = np.uint8

    def packed_width(self, dims: int) -> int:
        if dims % 2:
            raise ValueError(f"int4 encoding requires even dims, got {dims}")
        return dims // 2

    def encode_np(self, rows: np.ndarray) -> Encoded:
        rows = np.asarray(rows, dtype=np.float32)
        n, d = rows.shape
        w = self.packed_width(d)
        packed = np.empty((n, w), dtype=np.uint8)
        scales = np.empty((n,), dtype=np.float32)
        chunk = max(1, _CHUNK_BYTES // max(d * 4, 1))
        for lo in range(0, n, chunk):
            hi = lo + chunk
            block = rows[lo:hi]
            s = np.maximum(np.abs(block).max(axis=-1), 1e-30) / 7.0
            scales[lo:hi] = s
            q = np.clip(np.round(block / s[:, None]), -7, 7).astype(np.int8)
            packed[lo:hi] = ((q[:, 0::2] + 8).astype(np.uint8)
                             | ((q[:, 1::2] + 8).astype(np.uint8) << 4))
        return Encoded(packed, scales)

    def decode_np(self, data, scales):
        data = np.asarray(data)
        lo = (data & 0x0F).astype(np.int8) - 8
        hi = (data >> 4).astype(np.int8) - 8
        n, w = data.shape
        out = np.empty((n, 2 * w), dtype=np.float32)
        out[:, 0::2] = lo
        out[:, 1::2] = hi
        return out * np.asarray(scales)[:, None]

    def encode_jnp(self, rows):
        import jax.numpy as jnp
        rows = rows.astype(jnp.float32)
        max_abs = jnp.max(jnp.abs(rows), axis=-1)
        scales = jnp.maximum(max_abs, 1e-30) / 7.0
        q = jnp.clip(jnp.round(rows / scales[:, None]), -7, 7)
        lo = (q[:, 0::2] + 8).astype(jnp.uint8)
        hi = (q[:, 1::2] + 8).astype(jnp.uint8)
        return lo | (hi << 4), scales


class _BinaryCodec(VectorCodec):
    """Sign-bit binary: bit j of word w is sign(x[32w + j] >= 0). The
    per-row aux is mean|x| — the optimal 1-bit reconstruction magnitude,
    so decode_np returns sign(x)·mean|x| rather than bare ±1."""

    name = "binary"
    packed_np_dtype = np.uint32

    def packed_width(self, dims: int) -> int:
        if dims % 32:
            raise ValueError(
                f"binary encoding requires dims % 32 == 0, got {dims}")
        return dims // 32

    def encode_np(self, rows: np.ndarray) -> Encoded:
        rows = np.asarray(rows, dtype=np.float32)
        n, d = rows.shape
        w = self.packed_width(d)
        bits = (rows >= 0).astype(np.uint32).reshape(n, w, 32)
        weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))
        packed = (bits * weights[None, None, :]).sum(
            axis=-1, dtype=np.uint32)
        scales = np.abs(rows).mean(axis=-1).astype(np.float32)
        return Encoded(packed, scales)

    def decode_np(self, data, scales):
        data = np.asarray(data)
        n, w = data.shape
        shifts = np.arange(32, dtype=np.uint32)
        bits = ((data[:, :, None] >> shifts[None, None, :]) & 1)
        signs = bits.astype(np.float32).reshape(n, w * 32) * 2.0 - 1.0
        return signs * np.asarray(scales)[:, None]

    def encode_jnp(self, rows):
        import jax.numpy as jnp
        rows = rows.astype(jnp.float32)
        n, d = rows.shape
        w = self.packed_width(d)
        bits = (rows >= 0).astype(jnp.uint32).reshape(n, w, 32)
        weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
        packed = jnp.sum(bits * weights[None, None, :], axis=-1,
                         dtype=jnp.uint32)
        scales = jnp.mean(jnp.abs(rows), axis=-1).astype(jnp.float32)
        return packed, scales


CODECS: Dict[str, VectorCodec] = {}
_REGISTRY_LOCK = threading.Lock()


def register(codec: VectorCodec) -> VectorCodec:
    with _REGISTRY_LOCK:
        CODECS[codec.name] = codec
    return codec


register(_F32Codec())
register(_BF16Codec())
register(_Int8Codec())
register(_Int4Codec())
register(_BinaryCodec())


def get(name: str) -> VectorCodec:
    codec = CODECS.get(name)
    if codec is None:
        raise KeyError(
            f"unknown vector encoding [{name}]; registered: "
            f"{sorted(CODECS)}")
    return codec


def bytes_per_doc(name: str, dims: int) -> int:
    return get(name).bytes_per_doc(dims)


def is_packed(name: str) -> bool:
    return name in PACKED_ENCODINGS


# ---------------------------------------------------------------------------
# Device-side scoring helpers (the unpack half of the packed recipes —
# kept here so the pack and unpack bit conventions can never diverge)
# ---------------------------------------------------------------------------

def quantize_queries_int8_jnp(queries):
    """Per-QUERY symmetric int8 (the binned Pallas kernel's in-trace
    query quantization): (q8 [Q, D] int8, qscale [Q, 1] f32)."""
    import jax.numpy as jnp
    qmax = jnp.max(jnp.abs(queries), axis=-1, keepdims=True)
    qscale = jnp.maximum(qmax, 1e-30) / 127.0
    q8 = jnp.clip(jnp.round(queries / qscale), -127, 127).astype(jnp.int8)
    return q8, qscale


def int4_planes_jnp(packed, dtype=None):
    """Unpack a packed-nibble matrix [..., W] into its (even, odd) level
    planes [..., W] (values in [-8, 7]; encoders only emit [-7, 7]).
    With `dtype` the planes are cast for the matmul."""
    import jax.numpy as jnp
    lo = (packed & jnp.uint8(0x0F)).astype(jnp.int32) - 8
    hi = (packed >> 4).astype(jnp.int32) - 8
    if dtype is not None:
        lo, hi = lo.astype(dtype), hi.astype(dtype)
    return lo, hi


def split_query_planes_jnp(queries):
    """Match a query batch [Q, D] to the int4 plane layout:
    (even dims [Q, D/2], odd dims [Q, D/2]). A reshape, not a strided
    index: `queries[:, 0::2]` lowers to a gather that hands the matmul a
    transposed bf16 operand, which XLA-CPU's dot cannot run."""
    pairs = queries.reshape(queries.shape[0], -1, 2)
    return pairs[:, :, 0], pairs[:, :, 1]


def pack_sign_bits_jnp(queries):
    """Sign-bit pack a query batch [Q, D] into uint32 words [Q, D/32] —
    the in-trace twin of the binary codec's row encoder (bit layout is
    identical by construction)."""
    import jax.numpy as jnp
    nq, d = queries.shape
    w = d // 32
    bits = (queries >= 0).astype(jnp.uint32).reshape(nq, w, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits * weights[None, None, :], axis=-1,
                   dtype=jnp.uint32)


def hamming_pseudo_dots_jnp(qbits, words):
    """Coarse binary scores from packed sign bits.

    qbits [Q, W] uint32, words [N, W] uint32 → [Q, N] f32 in [-1, 1]:
    (D - 2·hamming)/D, the normalized sign-agreement — for
    cosine-normalized vectors this is the 1-bit estimate of the dot.
    Accumulates word-by-word so no [Q, N, W] popcount temp
    materializes (W is tiny — 24 words at 768 d — and the python loop
    unrolls into the trace)."""
    import jax
    import jax.numpy as jnp
    nq = qbits.shape[0]
    n, w = words.shape
    ham = jnp.zeros((nq, n), dtype=jnp.int32)
    for i in range(w):
        x = jnp.bitwise_xor(qbits[:, i:i + 1], words[None, :, i])
        ham = ham + jax.lax.population_count(x).astype(jnp.int32)
    d_bits = jnp.float32(w * 32)
    return (d_bits - 2.0 * ham.astype(jnp.float32)) / d_bits


def int4_blocked_dots_jnp(queries, blocks, dtype):
    """Un-descaled int4 dots for IVF probe tiles: queries [Q, D] f32,
    blocks [Q, C, W] packed uint8 → [Q, C] f32 — the one blocked-take
    scoring recipe shared by the single-device and mesh probe scorers
    (callers multiply the per-row scales in)."""
    import jax.numpy as jnp
    lo, hi = int4_planes_jnp(blocks, dtype)
    qe, qo = split_query_planes_jnp(queries)
    return (jnp.einsum("qd,qcd->qc", qe.astype(dtype), lo,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("qd,qcd->qc", qo.astype(dtype), hi,
                         preferred_element_type=jnp.float32))


def hamming_pseudo_dots_blocked_jnp(qbits, blocks):
    """Blocked-take variant for IVF probe tiles: qbits [Q, W],
    blocks [Q, C, W] uint32 → [Q, C] f32 pseudo-dots."""
    import jax
    import jax.numpy as jnp
    w = blocks.shape[-1]
    ham = jnp.zeros(blocks.shape[:-1], dtype=jnp.int32)
    for i in range(w):
        x = jnp.bitwise_xor(qbits[:, None, i], blocks[:, :, i])
        ham = ham + jax.lax.population_count(x).astype(jnp.int32)
    d_bits = jnp.float32(w * 32)
    return (d_bits - 2.0 * ham.astype(jnp.float32)) / d_bits
