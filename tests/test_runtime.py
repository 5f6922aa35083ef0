"""Native runtime (C++ PNG encoder) build + round-trip tests."""

import io

import numpy as np
import pytest

from tpustack import runtime


@pytest.fixture(scope="module")
def lib_ok():
    if not runtime.available():
        pytest.skip("no compiler / native build unavailable")
    return True


def test_png_roundtrip_via_pil(lib_ok):
    rng = np.random.RandomState(0)
    img = rng.randint(0, 256, (37, 53, 3), dtype=np.uint8)  # odd sizes on purpose
    png = runtime.png_encode(img)
    assert png[:8] == b"\x89PNG\r\n\x1a\n"

    from PIL import Image

    decoded = np.asarray(Image.open(io.BytesIO(png)).convert("RGB"))
    np.testing.assert_array_equal(decoded, img)


def test_png_rejects_bad_input(lib_ok):
    with pytest.raises(ValueError):
        runtime.png_encode(np.zeros((4, 4), np.uint8))
    with pytest.raises(ValueError):
        runtime.png_encode(np.zeros((4, 4, 3), np.float32))


def test_image_util_uses_native_when_available(lib_ok):
    from tpustack.utils.image import array_to_png

    img = np.zeros((16, 16, 3), np.uint8)
    png = array_to_png(img)
    assert png[:8] == b"\x89PNG\r\n\x1a\n"


def test_png_sizes_reasonable(lib_ok):
    """Compressible content should compress (all-zero image ≪ raw)."""
    img = np.zeros((256, 256, 3), np.uint8)
    png = runtime.png_encode(img)
    assert len(png) < 5000


def test_leftover_so_is_rebuilt_not_trusted(lib_ok, monkeypatch):
    """A ``.so`` whose stamp does not name this source on this host (a
    stale build, or an ignored file that came along with a copy of the
    tree) is rebuilt; mtimes are not consulted."""
    with open(runtime._STAMP_PATH, "w") as f:
        f.write("built-somewhere-else")
    assert not runtime._built_here()
    monkeypatch.setattr(runtime, "_lib", None)
    assert runtime.available()
    with open(runtime._STAMP_PATH) as f:
        assert f.read() == runtime._build_id()
    assert runtime.encoder() == "native"


def test_failed_build_serves_pil_and_says_so(monkeypatch):
    """No compiler → the leftover binary is NOT used; the process reports
    the PIL encoder instead of quietly keeping whatever was on disk."""
    import subprocess

    def no_make(*a, **k):
        raise subprocess.CalledProcessError(2, "make", stderr=b"no g++")

    monkeypatch.setattr(runtime, "_lib", None)
    monkeypatch.setattr(runtime, "_load_failed", False)
    monkeypatch.setattr(runtime, "_built_here", lambda: False)
    monkeypatch.setattr(subprocess, "run", no_make)
    assert runtime.encoder() == "pil"
    from tpustack.utils.image import array_to_png

    assert array_to_png(np.zeros((8, 8, 3), np.uint8))[:4] == b"\x89PNG"
