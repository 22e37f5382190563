"""The generic wire codec written out plainly, one element at a time.

The reference that `StreamOutput.write_generic` and the translog's records
are compared with, byte for byte. It imports nothing of the program, so a
faster inner loop there cannot change the bytes unseen: this is the format
as the tree wrote it before any list was packed.
"""

import struct
import zlib


def vint(n):
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def zlong(v):
    return vint((v << 1) ^ (v >> 63) if v >= 0 else ((-v) << 1) - 1 | 1)


def string(s):
    b = s.encode("utf-8")
    return vint(len(b)) + b


def generic(v):
    if v is None:
        return b"\x00"
    if isinstance(v, bool):
        return b"\x01" + (b"\x01" if v else b"\x00")
    if isinstance(v, int):
        return b"\x02" + zlong(v)
    if isinstance(v, float):
        return b"\x03" + struct.pack(">d", v)
    if isinstance(v, str):
        return b"\x04" + string(v)
    if isinstance(v, bytes):
        return b"\x05" + vint(len(v)) + v
    if isinstance(v, (list, tuple)):
        return b"\x06" + vint(len(v)) + b"".join(generic(x) for x in v)
    if isinstance(v, dict):
        return b"\x07" + vint(len(v)) + b"".join(
            string(str(k)) + generic(x) for k, x in v.items())
    raise TypeError(type(v).__name__)


def translog_record(op):
    payload = generic(op)
    return vint(len(payload)) + payload + struct.pack(">I", zlib.crc32(payload))
