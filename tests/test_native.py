"""Native codec tests: C++ HMAC/frame-scan vs Python reference."""

import hashlib
import hmac
import struct

import pytest

from maggy_tpu import native


class TestNativeCodec:
    def test_builds(self):
        assert native.is_native(), "g++ build of framing.cpp failed"

    def test_hmac_matches_python(self):
        for key, msg in [
            (b"k", b""),
            (b"secret-key", b"hello world"),
            (b"x" * 64, b"y" * 1000),
            (b"long-key" * 20, b"payload"),  # key > 64 bytes -> hashed
        ]:
            expected = hmac.new(key, msg, hashlib.sha256).digest()
            assert native.hmac_sha256(key, msg) == expected

    def frame(self, payload: bytes, key: bytes) -> bytes:
        mac = hmac.new(key, payload, hashlib.sha256).digest()
        return struct.pack(">I", len(payload)) + mac + payload

    def test_frame_scan_valid(self):
        key = b"s3cret"
        payload = b"\x81\xa4type\xa3REG"
        buf = self.frame(payload, key)
        consumed = native.frame_scan(buf, key, 1 << 20)
        assert consumed == len(buf)

    def test_frame_scan_incomplete(self):
        key = b"k"
        buf = self.frame(b"abcdef", key)
        assert native.frame_scan(buf[:10], key, 1 << 20) == 0
        assert native.frame_scan(buf[:-1], key, 1 << 20) == 0

    def test_frame_scan_bad_mac(self):
        key = b"k"
        buf = bytearray(self.frame(b"abcdef", key))
        buf[10] ^= 0xFF  # corrupt the mac
        assert native.frame_scan(bytes(buf), key, 1 << 20) == -2

    def test_frame_scan_oversized(self):
        key = b"k"
        buf = struct.pack(">I", 1 << 30) + b"\x00" * 32
        assert native.frame_scan(buf, key, 1 << 20) == -1

    def test_frame_scan_two_frames(self):
        key = b"k"
        b1 = self.frame(b"first", key)
        b2 = self.frame(b"second", key)
        consumed = native.frame_scan(b1 + b2, key, 1 << 20)
        assert consumed == len(b1)
        assert native.frame_scan((b1 + b2)[consumed:], key, 1 << 20) == len(b2)

    def test_python_fallback_agrees(self, monkeypatch):
        monkeypatch.setattr(native, "get_lib", lambda: None)
        key = b"fallback"
        buf = self.frame(b"payload!", key)
        assert native.frame_scan(buf, key, 1 << 20) == len(buf)
        assert native.hmac_sha256(key, b"m") == \
            hmac.new(key, b"m", hashlib.sha256).digest()

    def test_frame_scan_fuzz_never_crashes(self):
        """Untrusted bytes from the network must never crash the scanner:
        any result other than a valid frame just drops the connection."""
        import numpy as np

        from maggy_tpu import native

        rng = np.random.default_rng(0)
        secret = b"k" * 16
        for _ in range(300):
            n = int(rng.integers(0, 200))
            buf = bytearray(rng.integers(0, 256, size=n, dtype=np.uint8).tobytes())
            result = native.frame_scan(buf, secret, 1 << 20)
            assert isinstance(result, int)
            assert result <= len(buf)


class TestArtifactFollowsSource:
    """The shared object is named by framing.cpp's content hash, so a
    copied tree can never load a binary built from another source."""

    def test_loaded_binary_is_named_by_the_source_hash(self):
        with open(native._SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        assert native.is_native()
        assert native._so_path().endswith(
            "_maggy_native.{}.so".format(digest))
        import os

        assert os.path.exists(native._so_path())

    def test_changed_source_does_not_trust_the_old_binary(
            self, tmp_path, monkeypatch):
        import shutil

        src = tmp_path / "framing.cpp"
        shutil.copy(native._SRC, src)
        monkeypatch.setattr(native, "_SRC", str(src))
        before = native._so_path()
        src.write_text(src.read_text() + "\n// edited\n")
        assert native._so_path() != before

    def test_failed_build_says_so_and_falls_back(self, tmp_path, monkeypatch):
        (tmp_path / "framing.cpp").write_text("this is not C++")
        monkeypatch.setattr(native, "_HERE", str(tmp_path))
        monkeypatch.setattr(native, "_SRC", str(tmp_path / "framing.cpp"))
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_build_attempted", False)
        with pytest.warns(UserWarning, match="native codec not built"):
            assert native.get_lib() is None
        key = b"fallback"
        assert native.hmac_sha256(key, b"m") == \
            hmac.new(key, b"m", hashlib.sha256).digest()


class TestNativeTfrecord:
    def test_crc32c_matches_python_table(self):
        from maggy_tpu import native
        from maggy_tpu.train.tfrecord import _CRC32C_TABLE

        if not native.is_native():
            pytest.skip("no toolchain")

        def py_crc(data):
            crc = 0xFFFFFFFF
            for b in data:
                crc = (crc >> 8) ^ _CRC32C_TABLE[(crc ^ b) & 0xFF]
            return crc ^ 0xFFFFFFFF

        import os as _os

        for n in (0, 1, 7, 8, 9, 63, 64, 65, 1024):
            data = _os.urandom(n)
            assert native.crc32c(data) == py_crc(data), n
        # RFC 3720 vector.
        assert native.crc32c(b"123456789") == 0xE3069283

    def test_scan_matches_writer(self, tmp_path):
        from maggy_tpu import native
        from maggy_tpu.train.tfrecord import encode_example, write_tfrecord

        if not native.is_native():
            pytest.skip("no toolchain")
        path = str(tmp_path / "d.tfrecord")
        examples = [{"x": float(i), "n": i} for i in range(20)]
        write_tfrecord(path, examples)
        data = open(path, "rb").read()
        spans = native.tfrecord_scan(data)
        assert len(spans) == 20
        assert data[spans[3][0]:spans[3][0] + spans[3][1]] == \
            encode_example(examples[3])

    def test_scan_detects_corruption_and_truncation(self, tmp_path):
        from maggy_tpu import native
        from maggy_tpu.train.tfrecord import write_tfrecord

        if not native.is_native():
            pytest.skip("no toolchain")
        path = str(tmp_path / "d.tfrecord")
        write_tfrecord(path, [{"x": 1}])
        data = bytearray(open(path, "rb").read())
        data[-6] ^= 0xFF
        with pytest.raises(ValueError, match="crc"):
            native.tfrecord_scan(bytes(data))
        good = bytes(open(path, "rb").read())
        with pytest.raises(ValueError, match="Truncated"):
            native.tfrecord_scan(good[:-3])
