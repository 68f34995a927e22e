"""`native.build_shared_lib`: a binary is rebuilt when its source's
CONTENT differs from what built it, never because of an mtime — a copied
or freshly checked-out tree must not load a stale binary."""

import ctypes
import os
import shutil

import pytest

from lodestar_tpu import native

pytestmark = pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")

_SRC = 'extern "C" int answer() { return %d; }\n'


def test_rebuild_follows_content_not_mtime(tmp_path, monkeypatch):
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    src = tmp_path / "probe.cpp"
    src.write_text(_SRC % 41)
    first = native.build_shared_lib("libprobe", ["probe.cpp"], 60)
    assert first is not None and ctypes.CDLL(first).answer() == 41
    built_at = os.path.getmtime(first)

    # a newer mtime with the same bytes (a copy, a checkout): no rebuild
    os.utime(src, (built_at + 3600, built_at + 3600))
    assert native.build_shared_lib("libprobe", ["probe.cpp"], 60) == first
    assert os.path.getmtime(first) == built_at

    # other bytes under an OLDER mtime than the binary: rebuilt, and the
    # binary the old source produced is gone
    src.write_text(_SRC % 42)
    os.utime(src, (built_at - 3600, built_at - 3600))
    second = native.build_shared_lib("libprobe", ["probe.cpp"], 60)
    assert second not in (None, first) and ctypes.CDLL(second).answer() == 42
    assert not os.path.exists(first)


def test_failed_build_is_reported_not_raised(tmp_path, monkeypatch):
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    (tmp_path / "broken.cpp").write_text("this is not C++\n")
    assert native.build_shared_lib("libbroken", ["broken.cpp"], 60) is None
    assert native.build_shared_lib("libmissing", ["missing.cpp"], 60) is None
