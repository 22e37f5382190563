"""Versioned binary wire format.

Re-design of the reference's hand-rolled serialization
(`common/io/stream/StreamOutput.java:87`, `StreamInput.java`,
`NamedWriteableRegistry`): variable-length ints, length-prefixed UTF-8
strings, typed generic values, and named-writeable polymorphism. Every
stream carries the wire version negotiated at handshake so readers can
branch on `version` for backwards compatibility.
"""

from __future__ import annotations

import struct
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from elasticsearch_tpu.common.errors import SearchEngineError
from elasticsearch_tpu.version import WIRE_VERSION

# A generic list of doubles is n nine-byte (tag 3, big-endian double) pairs on
# the wire: one record of this dtype an element. From PACK_MIN elements on,
# one array write (or read) of them beats the element-wise walk, which stays
# for every other list; the bytes are the same either way.
_TAGGED_DOUBLE = np.dtype([("tag", "u1"), ("value", ">f8")])
_FLOATS_ONLY = {float}
PACK_MIN = 8


class StreamOutput:
    def __init__(self, version: int = WIRE_VERSION):
        self.version = version
        self._buf = bytearray()
        self.packed_lists = 0    # lists written as one array, for a caller's counter

    def bytes(self) -> bytes:
        return bytes(self._buf)

    def __len__(self):
        return len(self._buf)

    # -- primitives ----------------------------------------------------------
    def write_byte(self, b: int) -> None:
        self._buf.append(b & 0xFF)

    def write_bytes(self, data: bytes) -> None:
        self._buf.extend(data)

    def write_boolean(self, v: bool) -> None:
        self._buf.append(1 if v else 0)

    def write_int(self, v: int) -> None:
        self._buf.extend(struct.pack(">i", v))

    def write_long(self, v: int) -> None:
        self._buf.extend(struct.pack(">q", v))

    def write_float(self, v: float) -> None:
        self._buf.extend(struct.pack(">f", v))

    def write_double(self, v: float) -> None:
        self._buf.extend(struct.pack(">d", v))

    def write_vint(self, v: int) -> None:
        # LEB128-style varint over zig-zagged negatives kept out: reference
        # writeVInt requires non-negative; use write_zlong for signed.
        if v < 0:
            raise SearchEngineError(f"negative vint {v}")
        while v >= 0x80:
            self._buf.append((v & 0x7F) | 0x80)
            v >>= 7
        self._buf.append(v)

    def write_vlong(self, v: int) -> None:
        self.write_vint(v)

    def write_zlong(self, v: int) -> None:
        self.write_vint((v << 1) ^ (v >> 63) if v >= 0 else ((-v) << 1) - 1 | 1)

    def write_string(self, s: str) -> None:
        b = s.encode("utf-8")
        self.write_vint(len(b))
        self._buf.extend(b)

    def write_optional_string(self, s: Optional[str]) -> None:
        self.write_boolean(s is not None)
        if s is not None:
            self.write_string(s)

    def write_byte_array(self, data: bytes) -> None:
        self.write_vint(len(data))
        self._buf.extend(data)

    def write_string_list(self, items: List[str]) -> None:
        self.write_vint(len(items))
        for s in items:
            self.write_string(s)

    # -- generic (tagged) values --------------------------------------------
    def write_generic(self, v: Any) -> None:
        if v is None:
            self.write_byte(0)
        elif isinstance(v, bool):
            self.write_byte(1); self.write_boolean(v)
        elif isinstance(v, int):
            self.write_byte(2); self.write_zlong(v)
        elif isinstance(v, float):
            self.write_byte(3); self.write_double(v)
        elif isinstance(v, str):
            self.write_byte(4); self.write_string(v)
        elif isinstance(v, bytes):
            self.write_byte(5); self.write_byte_array(v)
        elif isinstance(v, (list, tuple)):
            n = len(v)
            self.write_byte(6); self.write_vint(n)
            # exactly `float`: a bool or an int has another tag, and a float
            # subclass (np.float64) keeps the walk it had
            if n >= PACK_MIN and set(map(type, v)) == _FLOATS_ONLY:
                pairs = np.empty(n, _TAGGED_DOUBLE)
                pairs["tag"] = 3
                pairs["value"] = v
                self._buf += pairs.tobytes()
                self.packed_lists += 1
            else:
                for item in v:
                    self.write_generic(item)
        elif isinstance(v, dict):
            self.write_byte(7); self.write_vint(len(v))
            for k, item in v.items():
                self.write_string(str(k))
                self.write_generic(item)
        else:
            raise SearchEngineError(f"cannot serialize type [{type(v).__name__}]")

    def write_named_writeable(self, obj: "NamedWriteable") -> None:
        self.write_string(obj.writeable_name())
        obj.write_to(self)


class StreamInput:
    def __init__(self, data: bytes, version: int = WIRE_VERSION,
                 registry: Optional["NamedWriteableRegistry"] = None):
        self.version = version
        self._data = memoryview(data)
        self._pos = 0
        self._registry = registry

    def remaining(self) -> int:
        return len(self._data) - self._pos

    def _take(self, n: int) -> memoryview:
        if self._pos + n > len(self._data):
            raise SearchEngineError("stream truncated")
        out = self._data[self._pos:self._pos + n]
        self._pos += n
        return out

    def read_byte(self) -> int:
        return self._take(1)[0]

    def read_bytes(self, n: int) -> bytes:
        return bytes(self._take(n))

    def read_boolean(self) -> bool:
        return self.read_byte() != 0

    def read_int(self) -> int:
        return struct.unpack(">i", self._take(4))[0]

    def read_long(self) -> int:
        return struct.unpack(">q", self._take(8))[0]

    def read_float(self) -> float:
        return struct.unpack(">f", self._take(4))[0]

    def read_double(self) -> float:
        return struct.unpack(">d", self._take(8))[0]

    def read_vint(self) -> int:
        shift = 0
        result = 0
        while True:
            b = self.read_byte()
            result |= (b & 0x7F) << shift
            if not b & 0x80:
                return result
            shift += 7
            if shift > 63:
                # reference StreamInput caps vint/vlong width; unbounded
                # varints from untrusted input become giant allocations
                raise SearchEngineError("variable-length int is too long")

    def read_vlong(self) -> int:
        return self.read_vint()

    def read_zlong(self) -> int:
        v = self.read_vint()
        return (v >> 1) ^ -(v & 1)

    def read_string(self) -> str:
        n = self.read_vint()
        try:
            return bytes(self._take(n)).decode("utf-8")
        except UnicodeDecodeError as e:
            raise SearchEngineError(f"malformed UTF-8 string on stream: {e}") from None

    def read_optional_string(self) -> Optional[str]:
        return self.read_string() if self.read_boolean() else None

    def read_byte_array(self) -> bytes:
        return self.read_bytes(self.read_vint())

    def read_string_list(self) -> List[str]:
        return [self.read_string() for _ in range(self.read_vint())]

    def read_generic(self) -> Any:
        tag = self.read_byte()
        if tag == 0:
            return None
        if tag == 1:
            return self.read_boolean()
        if tag == 2:
            return self.read_zlong()
        if tag == 3:
            return self.read_double()
        if tag == 4:
            return self.read_string()
        if tag == 5:
            return self.read_byte_array()
        if tag == 6:
            n = self.read_vint()
            size = n * _TAGGED_DOUBLE.itemsize
            if n >= PACK_MIN and self.remaining() >= size and self._data[self._pos] == 3:
                pairs = np.frombuffer(self._data, _TAGGED_DOUBLE, n, self._pos)
                if (pairs["tag"] == 3).all():
                    self._pos += size
                    return pairs["value"].tolist()
            return [self.read_generic() for _ in range(n)]
        if tag == 7:
            return {self.read_string(): self.read_generic() for _ in range(self.read_vint())}
        raise SearchEngineError(f"unknown generic tag [{tag}]")

    def read_named_writeable(self, category: type) -> Any:
        if self._registry is None:
            raise SearchEngineError("no NamedWriteableRegistry attached to stream")
        name = self.read_string()
        reader = self._registry.get_reader(category, name)
        return reader(self)


class NamedWriteable:
    """Polymorphic wire object (reference: NamedWriteable.java)."""

    def writeable_name(self) -> str:
        raise NotImplementedError

    def write_to(self, out: StreamOutput) -> None:
        raise NotImplementedError


class NamedWriteableRegistry:
    def __init__(self):
        self._readers: Dict[tuple, Callable[[StreamInput], Any]] = {}

    def register(self, category: type, name: str, reader: Callable[[StreamInput], Any]) -> None:
        key = (category, name)
        if key in self._readers:
            raise SearchEngineError(f"duplicate named writeable [{category.__name__}/{name}]")
        self._readers[key] = reader

    def get_reader(self, category: type, name: str) -> Callable[[StreamInput], Any]:
        reader = self._readers.get((category, name))
        if reader is None:
            raise SearchEngineError(f"unknown named writeable [{category.__name__}/{name}]")
        return reader
