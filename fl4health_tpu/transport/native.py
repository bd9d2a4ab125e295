"""Loader for the native framing codec (_codec.cpp) with a pure-Python twin.

The shared object is compiled on first use with the system C++ toolchain and
cached next to the source as ``_codec.<sha8 of the source>.so``: the name is
keyed on what was compiled, so a binary left behind by another version of
the source (a copied tree, a checkout switch) can never be picked up — it
is simply not the file this source looks for. Environments without a
compiler (or with FL4HEALTH_NO_NATIVE=1) run the ``PyFraming`` fallback —
identical wire format, zlib's C crc32, ~same speed for small frames, slower
for giant ones.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import struct
import subprocess
import threading
import zlib
from pathlib import Path

logger = logging.getLogger(__name__)

_MAGIC = 0x464C3448
_VERSION = 1
_FIXED = struct.Struct("<IHHIQ")  # magic, version, flags, header_len, payload_len

_lock = threading.Lock()
_native = None
_native_tried = False


def _so_path(src: Path) -> Path:
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:8]
    return src.with_name(f"_codec.{digest}.so")


def _compile_native(src: Path | None = None) -> ctypes.CDLL | None:
    src = src or Path(__file__).with_name("_codec.cpp")
    so = _so_path(src)
    if not so.exists():
        # build under a private name, then publish atomically: concurrent
        # first users (test children) must never load a half-written object
        tmp = so.with_name(f"{so.name}.{os.getpid()}.tmp")
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-o", str(tmp), str(src)]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
        except (OSError, subprocess.SubprocessError) as e:
            logger.info("native codec build failed (%s); using Python framing", e)
            tmp.unlink(missing_ok=True)
            return None
    try:
        lib = ctypes.CDLL(str(so))
    except OSError as e:
        logger.info("native codec load failed (%s); using Python framing", e)
        return None
    lib.fl4h_crc32.restype = ctypes.c_uint32
    lib.fl4h_crc32.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32]
    lib.fl4h_frame_size.restype = ctypes.c_int64
    lib.fl4h_frame_size.argtypes = [ctypes.c_uint32, ctypes.c_uint64]
    lib.fl4h_frame.restype = ctypes.c_int64
    lib.fl4h_frame.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_uint16, ctypes.c_char_p, ctypes.c_uint64,
    ]
    lib.fl4h_unframe.restype = ctypes.c_int64
    lib.fl4h_unframe.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint16),
    ]
    lib.fl4h_pack_nibbles.restype = ctypes.c_int64
    lib.fl4h_pack_nibbles.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
    ]
    lib.fl4h_unpack_nibbles.restype = ctypes.c_int64
    lib.fl4h_unpack_nibbles.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
    ]
    return lib


def get_native() -> ctypes.CDLL | None:
    global _native, _native_tried
    if os.environ.get("FL4HEALTH_NO_NATIVE"):
        return None
    with _lock:
        if not _native_tried:
            _native = _compile_native()
            _native_tried = True
        return _native


class FrameError(ValueError):
    pass


_ERRORS = {-1: "short frame", -2: "bad magic", -3: "bad version", -4: "bad crc"}


class NativeFraming:
    """ctypes bridge over _codec.so."""

    def __init__(self, lib: ctypes.CDLL):
        self.lib = lib

    def frame(self, header: bytes, payload: bytes, flags: int = 0) -> bytes:
        size = self.lib.fl4h_frame_size(len(header), len(payload))
        out = ctypes.create_string_buffer(size)
        n = self.lib.fl4h_frame(
            header, len(header), payload, len(payload), flags, out, size
        )
        if n < 0:
            raise FrameError("frame buffer sizing failed")
        return out.raw[:n]

    def unframe(self, buf: bytes) -> tuple[bytes, bytes, int]:
        ho = ctypes.c_uint32()
        hl = ctypes.c_uint32()
        po = ctypes.c_uint64()
        pl = ctypes.c_uint64()
        fl = ctypes.c_uint16()
        rc = self.lib.fl4h_unframe(
            buf, len(buf), ctypes.byref(ho), ctypes.byref(hl),
            ctypes.byref(po), ctypes.byref(pl), ctypes.byref(fl),
        )
        if rc != 0:
            raise FrameError(_ERRORS.get(rc, f"unframe error {rc}"))
        h = buf[ho.value : ho.value + hl.value]
        p = buf[po.value : po.value + pl.value]
        return h, p, fl.value

    def crc32(self, data: bytes) -> int:
        return self.lib.fl4h_crc32(data, len(data), 0)


class PyFraming:
    """Pure-Python twin (same bytes on the wire)."""

    def frame(self, header: bytes, payload: bytes, flags: int = 0) -> bytes:
        body = _FIXED.pack(_MAGIC, _VERSION, flags, len(header), len(payload))
        body += header + payload
        return body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)

    def unframe(self, buf: bytes) -> tuple[bytes, bytes, int]:
        if len(buf) < _FIXED.size + 4:
            raise FrameError("short frame")
        magic, version, flags, hlen, plen = _FIXED.unpack_from(buf)
        if magic != _MAGIC:
            raise FrameError("bad magic")
        if version != _VERSION:
            raise FrameError("bad version")
        total = _FIXED.size + hlen + plen + 4
        if len(buf) < total:
            raise FrameError("short frame")
        (expect,) = struct.unpack_from("<I", buf, total - 4)
        if expect != (zlib.crc32(buf[: total - 4]) & 0xFFFFFFFF):
            raise FrameError("bad crc")
        return (
            buf[_FIXED.size : _FIXED.size + hlen],
            buf[_FIXED.size + hlen : _FIXED.size + hlen + plen],
            flags,
        )

    def crc32(self, data: bytes) -> int:
        return zlib.crc32(data) & 0xFFFFFFFF


def get_framing():
    lib = get_native()
    return NativeFraming(lib) if lib is not None else PyFraming()


# ---------------------------------------------------------------------------
# int4 nibble packing (compressed wire frames, codec.py encode_compressed)
# ---------------------------------------------------------------------------

def _pack_int4_py(vals) -> bytes:
    import numpy as np

    u = np.asarray(vals, np.int8).view(np.uint8) & 0xF
    if u.size % 2:
        u = np.concatenate([u, np.zeros((1,), np.uint8)])
    return (u[0::2] | (u[1::2] << 4)).astype(np.uint8).tobytes()


def _unpack_int4_py(packed: bytes, n: int):
    import numpy as np

    b = np.frombuffer(packed, np.uint8)
    out = np.empty(2 * b.size, np.int16)
    out[0::2] = b & 0xF
    out[1::2] = b >> 4
    return (((out[:n] ^ 0x8) - 0x8)).astype(np.int8)


def pack_int4(vals) -> bytes:
    """Pack signed int4 values (int8 array, each in [-8, 7]) two per byte,
    low nibble first — native C++ when available, NumPy twin otherwise
    (byte-identical; tests/transport/test_native.py pins the parity)."""
    import numpy as np

    v = np.ascontiguousarray(vals, np.int8)
    lib = get_native()
    if lib is None:
        return _pack_int4_py(v)
    out = ctypes.create_string_buffer((v.size + 1) // 2)
    n = lib.fl4h_pack_nibbles(v.tobytes(), v.size, out, len(out))
    if n < 0:
        raise FrameError("int4 pack buffer sizing failed")
    return out.raw[:n]


def unpack_int4(packed: bytes, n: int):
    """Inverse of :func:`pack_int4`: ``n`` sign-extended int8 values."""
    import numpy as np

    if len(packed) < (n + 1) // 2:
        raise FrameError(
            f"int4 payload too short: {len(packed)} bytes for {n} values"
        )
    lib = get_native()
    if lib is None:
        return _unpack_int4_py(packed, n)
    out = ctypes.create_string_buffer(max(n, 1))
    rc = lib.fl4h_unpack_nibbles(packed, n, out, len(out))
    if rc < 0:
        raise FrameError("int4 unpack buffer sizing failed")
    return np.frombuffer(out.raw[:n], np.int8).copy()
