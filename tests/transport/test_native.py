"""transport/native.py: framing codec contract (native C++ + pure-Python
twin) and the RPC observability accounting exercised THROUGH the native
transport — the per-silo latency histograms / failure counters were pinned
for loopback/coordinator in PR 1 but never driven over the native framing
path."""

import os
import struct
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from fl4health_tpu.observability.registry import (
    MetricsRegistry,
    set_registry,
)
from fl4health_tpu.transport import native
from fl4health_tpu.transport.native import (
    FrameError,
    PyFraming,
    get_framing,
    get_native,
)

CASES = (
    (b"", b""),
    (b"h", b"p"),
    (b'{"leaves": []}', b"\x00" * 1024),
    (b"x" * 300, bytes(range(256)) * 17),
)


class TestPyFraming:
    @pytest.mark.parametrize("header,payload", CASES)
    def test_roundtrip(self, header, payload):
        f = PyFraming()
        h, p, flags = f.unframe(f.frame(header, payload, flags=3))
        assert (h, p, flags) == (header, payload, 3)

    def test_short_frame(self):
        with pytest.raises(FrameError, match="short frame"):
            PyFraming().unframe(b"tiny")

    def test_bad_magic(self):
        buf = bytearray(PyFraming().frame(b"h", b"p"))
        buf[0] ^= 0xFF
        with pytest.raises(FrameError, match="bad magic"):
            PyFraming().unframe(bytes(buf))

    def test_bad_version(self):
        f = PyFraming()
        body = struct.pack("<IHHIQ", 0x464C3448, 99, 0, 1, 1) + b"hp"
        buf = body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)
        with pytest.raises(FrameError, match="bad version"):
            f.unframe(buf)

    def test_bad_crc(self):
        buf = bytearray(PyFraming().frame(b"head", b"payload"))
        buf[-6] ^= 0x01  # corrupt a payload byte, CRC now mismatches
        with pytest.raises(FrameError, match="bad crc"):
            PyFraming().unframe(bytes(buf))

    def test_truncated_payload(self):
        buf = PyFraming().frame(b"head", b"payload" * 100)
        with pytest.raises(FrameError, match="short frame"):
            PyFraming().unframe(buf[: len(buf) // 2])

    def test_crc32_matches_zlib(self):
        data = b"the wire contract"
        assert PyFraming().crc32(data) == zlib.crc32(data) & 0xFFFFFFFF


class TestNoNativeEnv:
    def test_fl4health_no_native_forces_python_fallback(self, monkeypatch):
        monkeypatch.setenv("FL4HEALTH_NO_NATIVE", "1")
        assert get_native() is None
        assert isinstance(get_framing(), PyFraming)


class TestSourceHashKeyedBuild:
    """The built object is named after a hash of the source, so a binary
    from another version of ``_codec.cpp`` (a copied tree) is never the
    file this source loads — whatever its mtime says."""

    def test_stale_object_next_to_changed_source_is_not_loaded(
        self, tmp_path, monkeypatch
    ):
        import subprocess

        src = tmp_path / "_codec.cpp"
        src.write_text("// v1\n")
        stale = native._so_path(src)
        stale.write_bytes(b"built from v1")
        src.write_text("// v2\n")
        # NEWER than the source: the old mtime rule would have loaded it
        os.utime(stale, (src.stat().st_mtime + 60,) * 2)
        assert native._so_path(src) != stale

        def no_compiler(*a, **kw):
            raise OSError("no compiler in this test")

        monkeypatch.setattr(subprocess, "run", no_compiler)
        # the v2 object does not exist and cannot be built: the answer is
        # "no native codec", never the v1 binary
        assert native._compile_native(src) is None
        assert not native._so_path(src).exists()
        assert not list(tmp_path.glob("*.tmp"))


needs_native = pytest.mark.skipif(
    get_native() is None, reason="native codec unavailable (no compiler)"
)


@needs_native
class TestNativeFraming:
    """The C++ codec must be BYTE-identical to the Python twin — a frame
    produced by either side decodes on the other (mixed deployments)."""

    @pytest.mark.parametrize("header,payload", CASES)
    def test_bytes_identical_to_python(self, header, payload):
        assert (get_framing().frame(header, payload, flags=1)
                == PyFraming().frame(header, payload, flags=1))

    @pytest.mark.parametrize("header,payload", CASES)
    def test_cross_unframe(self, header, payload):
        nat, py = get_framing(), PyFraming()
        assert py.unframe(nat.frame(header, payload)) == (header, payload, 0)
        assert nat.unframe(py.frame(header, payload)) == (header, payload, 0)

    def test_native_error_codes(self):
        nat = get_framing()
        with pytest.raises(FrameError, match="short frame"):
            nat.unframe(b"tiny")
        buf = bytearray(nat.frame(b"h", b"p"))
        buf[0] ^= 0xFF
        with pytest.raises(FrameError, match="bad magic"):
            nat.unframe(bytes(buf))
        buf = bytearray(nat.frame(b"head", b"payload"))
        buf[-6] ^= 0x01
        with pytest.raises(FrameError, match="bad crc"):
            nat.unframe(bytes(buf))

    def test_crc32_parity(self):
        data = bytes(range(256)) * 3
        assert get_framing().crc32(data) == PyFraming().crc32(data)


class TestRpcAccountingOverNativeTransport:
    """PR 1's per-silo latency histograms / failure counters, driven through
    the REAL transport stack (codec with the active framing -> loopback TCP
    -> coordinator), not just the coordinator unit seam."""

    @pytest.fixture
    def registry(self):
        reg = MetricsRegistry()
        prev = set_registry(reg)
        yield reg
        set_registry(prev)

    def test_latency_histogram_and_byte_counters(self, registry):
        from fl4health_tpu.transport import (
            LoopbackServer,
            broadcast_round,
            decode,
            encode,
        )

        def handler(frame: bytes) -> bytes:
            params = decode(frame, like={"w": jnp.zeros(3)})
            return encode({"params": {"w": params["w"] + 1.0},
                           "n": jnp.asarray(2.0)})

        silos = [LoopbackServer(handler) for _ in range(2)]
        try:
            replies = broadcast_round(
                [(s.host, s.port) for s in silos],
                {"w": jnp.asarray([1.0, 2.0, 3.0])},
                {"params": {"w": jnp.zeros(3)}, "n": jnp.zeros(())},
            )
        finally:
            for s in silos:
                s.close()
        np.testing.assert_allclose(np.asarray(replies[0]["params"]["w"]),
                                   [2.0, 3.0, 4.0])
        snap = registry.snapshot()
        # one latency observation per live silo, labeled per silo
        hist = snap["transport_rpc_latency_seconds"]
        assert len(hist) == 2
        assert all(h["count"] == 1 for h in hist.values())
        # the codec's wire-byte accounting ran through the active framing
        assert snap["transport_bytes_encoded_total"] > 0
        assert snap["transport_bytes_decoded_total"] > 0

    def test_failure_counter_on_dead_silo(self, registry):
        from fl4health_tpu.transport import LoopbackServer, broadcast_round

        # allocate-and-close: a port with nothing listening
        dead = LoopbackServer(lambda b: b)
        dead.close()
        with pytest.raises(Exception):
            broadcast_round(
                [(dead.host, dead.port)],
                {"w": jnp.zeros(2)},
                {"params": {"w": jnp.zeros(2)}, "n": jnp.zeros(())},
                timeout=0.5,
            )
        snap = registry.snapshot()
        # failures carry a reason label (labels serialize sorted): a dead
        # port is a connection failure, not a timeout or decode error
        failure_key = f'{{reason="connection",silo="{dead.host}:{dead.port}"}}'
        assert snap["transport_rpc_failures_total"][failure_key] == 1.0
        silo = f'{{silo="{dead.host}:{dead.port}"}}'
        # no latency observation for the failed round trip (failures must
        # not drag the percentiles of working silos) — the instrument is
        # registered up front but stays empty
        assert snap["transport_rpc_latency_seconds"][silo]["count"] == 0


class TestInt4Packing:
    """Nibble pack/unpack for compressed int4 wire frames: the native C++
    helpers and the NumPy twin must agree byte-for-byte."""

    def test_native_matches_python_bytes(self):
        import numpy as np

        from fl4health_tpu.transport.native import (
            _pack_int4_py,
            _unpack_int4_py,
            get_native,
        )

        lib = get_native()
        if lib is None:
            pytest.skip("native codec unavailable (no compiler)")
        from fl4health_tpu.transport import native

        for n in (0, 1, 2, 7, 100, 101):
            vals = np.random.default_rng(n).integers(
                -8, 8, size=n
            ).astype(np.int8)
            assert native.pack_int4(vals) == _pack_int4_py(vals), n
            np.testing.assert_array_equal(
                native.unpack_int4(native.pack_int4(vals), n), vals
            )
            np.testing.assert_array_equal(
                _unpack_int4_py(_pack_int4_py(vals), n), vals
            )

    def test_sign_extension_covers_full_range(self):
        import numpy as np

        from fl4health_tpu.transport.native import (
            _pack_int4_py,
            _unpack_int4_py,
        )

        vals = np.arange(-8, 8, dtype=np.int8)
        np.testing.assert_array_equal(
            _unpack_int4_py(_pack_int4_py(vals), 16), vals
        )

    def test_short_payload_raises(self):
        from fl4health_tpu.transport.native import unpack_int4

        with pytest.raises(FrameError, match="too short"):
            unpack_int4(b"\x00", 5)
