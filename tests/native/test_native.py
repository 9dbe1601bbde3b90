"""C++ data-plane tests: native results must equal the Python
reference implementations exactly."""

import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from mmlspark_tpu.native import (
    bin_matrix,
    ensure_built,
    is_available,
    level_histogram,
    load_csv,
    load_libsvm,
    murmur3_batch,
)
from mmlspark_tpu.ops.hashing import murmur3_32


@pytest.fixture(scope="module", autouse=True)
def built():
    assert ensure_built(), "g++ build of the native library failed"


class TestMurmur:
    def test_matches_python_reference(self):
        keys = ["age", "income", "city=sf", "", "日本語", "x" * 100]
        got = murmur3_batch(keys, seed=42)
        want = np.asarray([murmur3_32(k, 42) for k in keys], np.uint32)
        assert np.array_equal(got, want)


class TestBinning:
    def test_matches_searchsorted(self):
        rng = np.random.default_rng(0)
        vals = rng.normal(size=(1000, 5))
        uppers = np.sort(rng.normal(size=(5, 16)), axis=1)
        got = bin_matrix(vals, uppers)
        want = np.empty_like(got)
        for j in range(5):
            want[:, j] = np.minimum(
                np.searchsorted(uppers[j], vals[:, j], side="left"), 15)
        assert np.array_equal(got, want)


class TestLoaders:
    def test_csv_roundtrip(self, tmp_path):
        rng = np.random.default_rng(1)
        mat = np.round(rng.normal(size=(200, 4)), 6)
        p = tmp_path / "data.csv"
        header = "a,b,c,d\n"
        lines = [",".join(f"{v:.6f}" for v in row) for row in mat]
        p.write_text(header + "\n".join(lines) + "\n")
        got = load_csv(str(p), skip_header=True)
        assert got.shape == (200, 4)
        assert np.allclose(got, mat, atol=1e-9)

    def test_csv_no_trailing_newline(self, tmp_path):
        p = tmp_path / "x.csv"
        p.write_text("1.5,2.5\n3.5,4.5")
        got = load_csv(str(p), skip_header=False)
        assert np.allclose(got, [[1.5, 2.5], [3.5, 4.5]])

    def test_libsvm(self, tmp_path):
        p = tmp_path / "d.svm"
        p.write_text("1 1:0.5 3:2.0\n-1 2:1.5\n1 1:1.0 2:2.0 3:3.0\n")
        x, y = load_libsvm(str(p))
        assert np.array_equal(y, [1, -1, 1])
        want = np.asarray([[0.5, 0.0, 2.0], [0.0, 1.5, 0.0],
                           [1.0, 2.0, 3.0]])
        assert np.array_equal(x, want)

    def test_missing_file_raises(self):
        with pytest.raises(IOError):
            load_csv("/nonexistent/file.csv")


class TestLevelHistogram:
    """The GBDT level-histogram kernel at the ctypes level (the trainer
    dispatch and the pure_callback integration are covered by
    tests/gbdt/test_hist_native.py)."""

    def _case(self, n=4000, f=5, b=31, width=8, seed=0,
              bin_dtype=np.uint8):
        rng = np.random.default_rng(seed)
        return (rng.integers(0, b, size=(n, f)).astype(bin_dtype),
                rng.normal(size=n).astype(np.float32),
                rng.uniform(0.1, 1.0, size=n).astype(np.float32),
                (rng.random(n) < 0.9).astype(np.float32),
                rng.integers(0, width, size=n).astype(np.int32),
                width, b)

    @pytest.mark.parametrize("bin_dtype", [np.uint8, np.int32])
    def test_matches_numpy_fallback(self, monkeypatch, bin_dtype):
        from mmlspark_tpu.native import bindings

        args = self._case(bin_dtype=bin_dtype)
        native = level_histogram(*args)
        monkeypatch.setattr(bindings, "ensure_built", lambda: False)
        ref = bindings.level_histogram(*args)
        np.testing.assert_allclose(native, ref, rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(native[..., 2], ref[..., 2])

    def test_direct_and_sorted_paths_bit_identical(self):
        """The node-partitioned (sorted) C++ path must add into each
        (node, feature, bin) cell in the same ascending row order as
        the direct path: integer stats make every add exact, so folding
        a width-64 (sorted-path) histogram onto width-4 node ids must
        reproduce the direct-path width-4 histogram bit-for-bit.

        The wide case's tile (64 * 17 * 255 * 16 B ≈ 4.4 MB) exceeds
        kHistL2Budget (4 MB), so it actually takes the sorted path;
        the width-4 fold target stays comfortably on the direct path —
        the pairing the test exists to compare."""
        rng = np.random.default_rng(7)
        n, f, b = 50000, 17, 255
        binned = rng.integers(0, b, size=(n, f)).astype(np.uint8)
        grad = rng.integers(-8, 9, size=n).astype(np.float32)
        hess = rng.integers(1, 9, size=n).astype(np.float32)
        live = np.ones(n, np.float32)
        local64 = rng.integers(0, 64, size=n).astype(np.int32)
        h64 = level_histogram(binned, grad, hess, live, local64, 64, b)
        h4 = level_histogram(binned, grad, hess, live,
                             (local64 % 4).astype(np.int32), 4, b)
        agg = np.zeros_like(h4)
        for w in range(64):
            agg[w % 4] += h64[w]
        np.testing.assert_array_equal(agg, h4)

    def test_dead_rows_and_empty_nodes(self):
        binned, grad, hess, live, local, width, b = self._case(width=16)
        live = np.zeros_like(live)
        live[:10] = 1.0
        local[:10] = 3  # one hot node; the rest empty or dead
        out = level_histogram(binned, grad, hess, live, local, width, b)
        assert out[np.arange(width) != 3].sum() == 0
        assert out[3, 0, :, 2].sum() == 10

    def test_empty_input(self):
        out = level_histogram(np.zeros((0, 4), np.uint8),
                              np.zeros(0, np.float32),
                              np.zeros(0, np.float32),
                              np.zeros(0, np.float32),
                              np.zeros(0, np.int32), 2, 8)
        assert out.shape == (2, 4, 8, 3)
        assert not out.any()


class TestIntegration:
    def test_binmapper_native_path_matches_python(self, monkeypatch):
        """BinMapper.transform's native fast path must equal the pure
        python loop bit-for-bit (incl. NaN -> bin 0)."""
        from mmlspark_tpu.ops import binning as binning_mod
        from mmlspark_tpu.ops.binning import BinMapper

        rng = np.random.default_rng(2)
        x = rng.normal(size=(500, 3))
        x[::17, 1] = np.nan
        mapper = BinMapper.fit(x, max_bin=32)
        native = mapper.transform(x)
        # force the python path by knocking out the native helper
        monkeypatch.setattr(BinMapper, "_transform_native",
                            lambda self, arr: None)
        python = mapper.transform(x)
        assert np.array_equal(np.asarray(native), np.asarray(python))
        assert (np.asarray(python)[::17, 1] == 0).all()


class TestBuildAcrossProcesses:
    def test_processes_starting_on_a_fresh_tree_all_load(self, tmp_path):
        """Several processes reach ``ensure_built`` on a library-less
        copy of ``native/`` while the first of them is still compiling
        (six test workers on a fresh checkout): each must end with the
        library loaded and working. ``make`` used to link onto the
        target in place, so a process whose ``make`` found the target
        there loaded a file another was still writing, and remembered
        the failure for its life."""
        repo = Path(__file__).resolve().parents[2]
        native = tmp_path / "native"
        native.mkdir()
        for name in ("Makefile", "data_plane.cpp"):
            shutil.copy(repo / "native" / name, native / name)
        code = (
            "import sys\n"
            "from mmlspark_tpu.native import bindings\n"
            "bindings._NATIVE_DIR = sys.argv[1]\n"
            "bindings._SO_PATH = sys.argv[1] + '/libmmlspark_native.so'\n"
            "assert bindings.ensure_built(), 'library not loaded'\n"
            "print(int(bindings.murmur3_batch(['age'], 42)[0]))\n")
        env = dict(os.environ, PYTHONPATH=str(repo))

        def start():
            return subprocess.Popen(
                [sys.executable, "-c", code, str(native)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)

        def check(procs):
            want = str(murmur3_32("age", 42))
            for i, p in enumerate(procs):
                out, err = p.communicate(timeout=300)
                assert p.returncode == 0, f"process {i}: {err[-800:]}"
                assert out.strip() == want, f"process {i}: {out!r}"
            assert [f.name for f in native.glob("*.tmp.*")] == []

        procs = []
        for _ in range(6):
            procs.append(start())
            time.sleep(0.4)     # starts spread over the first's compile
        check(procs)
        # a rebuild replaces the library by rename: the file a process
        # has mapped is never truncated and rewritten under it
        target = native / "libmmlspark_native.so"
        before = target.stat().st_ino
        later = time.time() + 5
        os.utime(native / "data_plane.cpp", (later, later))
        check([start()])
        assert target.stat().st_ino != before
