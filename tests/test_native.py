"""Native C++ host pipeline: build, bindings, and numpy equivalence."""
import numpy as np
import pytest

from fedtorch_tpu.native import (
    HostPrefetcher, cyclic_pad_indices, gather_rows, native_available,
    seeded_permutation,
)


def test_library_builds():
    assert native_available(), "g++ build of pipeline.cpp failed"


class TestBuildRace:
    """_build_library must never leave a half-written .so where a
    racing process could dlopen it: compile to a temp path, land via
    atomic rename, serialized by a per-path file lock."""

    def _patch_paths(self, tmp_path, monkeypatch):
        import pathlib

        import fedtorch_tpu.native.host_pipeline as hp
        src = tmp_path / "src.cpp"
        src.write_text("// fake source")
        monkeypatch.setattr(hp, "_SRC", str(src))
        monkeypatch.setattr(hp, "_LIB_STEM", str(tmp_path / "lib"))
        return hp, pathlib.Path(hp._lib_path())

    def test_never_compiles_in_place_and_no_tmp_residue(
            self, tmp_path, monkeypatch):
        hp, lib = self._patch_paths(tmp_path, monkeypatch)
        outs = []

        def fake_run(cmd, **kw):
            out = cmd[cmd.index("-o") + 1]
            assert out != str(lib)  # in-place write = the race bug
            outs.append(out)
            with open(out, "wb") as f:
                f.write(b"SO")

        assert hp._build_library(run=fake_run) == str(lib)
        assert lib.read_bytes() == b"SO"
        assert len(outs) == 1
        residue = [p for p in tmp_path.iterdir() if ".tmp." in p.name]
        assert residue == []

    def test_concurrent_builders_compile_once(self, tmp_path,
                                              monkeypatch):
        import threading
        import time
        hp, lib = self._patch_paths(tmp_path, monkeypatch)
        compiles = []

        def slow_run(cmd, **kw):
            compiles.append(cmd)
            time.sleep(0.2)  # hold the lock long enough to collide
            with open(cmd[cmd.index("-o") + 1], "wb") as f:
                f.write(b"SO")

        results = []
        threads = [threading.Thread(
            target=lambda: results.append(
                hp._build_library(run=slow_run))) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the loser waited on the lock, re-checked freshness, and
        # adopted the winner's build instead of compiling again
        assert results == [str(lib), str(lib)]
        assert len(compiles) == 1
        assert lib.read_bytes() == b"SO"

    def test_failed_compile_leaves_nothing(self, tmp_path, monkeypatch):
        hp, lib = self._patch_paths(tmp_path, monkeypatch)

        def broken_run(cmd, **kw):
            import subprocess
            with open(cmd[cmd.index("-o") + 1], "wb") as f:
                f.write(b"PART")  # partial output before the failure
            raise subprocess.CalledProcessError(1, cmd, stderr=b"boom")

        # the numpy fallback is announced, not silent
        with pytest.warns(RuntimeWarning, match="numpy fallback"):
            assert hp._build_library(run=broken_run) is None
        assert not lib.exists()
        residue = [p for p in tmp_path.iterdir() if ".tmp." in p.name]
        assert residue == []

    def test_freshness_follows_source_hash(self, tmp_path, monkeypatch):
        """A binary is only ever adopted for the source it was built
        from: the content hash is in the file name, so an edited
        pipeline.cpp rebuilds whatever the mtimes say (a copied tree
        does not preserve them), and the old binary is removed."""
        import os
        hp, lib_v1 = self._patch_paths(tmp_path, monkeypatch)
        compiles = []

        def fake_run(cmd, **kw):
            compiles.append(cmd)
            with open(cmd[cmd.index("-o") + 1], "wb") as f:
                f.write(b"SO%d" % len(compiles))

        assert hp._build_library(run=fake_run) == str(lib_v1)
        # same source: adopted, not rebuilt — even with the binary's
        # mtime far in the past
        os.utime(lib_v1, (0, 0))
        assert hp._build_library(run=fake_run) == str(lib_v1)
        assert len(compiles) == 1
        # edited source, binary NEWER than source: still rebuilt
        (tmp_path / "src.cpp").write_text("// edited source")
        os.utime(tmp_path / "src.cpp", (0, 0))
        lib_v2 = hp._lib_path()
        assert lib_v2 != str(lib_v1)
        assert hp._build_library(run=fake_run) == lib_v2
        assert len(compiles) == 2
        assert not lib_v1.exists()  # stale version removed


def test_seeded_perm_valid_and_deterministic():
    p1 = seeded_permutation(1000, seed=42)
    p2 = seeded_permutation(1000, seed=42)
    p3 = seeded_permutation(1000, seed=43)
    np.testing.assert_array_equal(p1, p2)
    assert not np.array_equal(p1, p3)
    np.testing.assert_array_equal(np.sort(p1), np.arange(1000))


def test_gather_rows_matches_numpy():
    rng = np.random.RandomState(0)
    for dtype in (np.float32, np.int64, np.uint8):
        src = rng.randint(0, 100, (500, 7, 3)).astype(dtype)
        idx = rng.randint(0, 500, 1234)
        np.testing.assert_array_equal(gather_rows(src, idx), src[idx])


def test_gather_rows_multithreaded():
    rng = np.random.RandomState(1)
    src = rng.randn(10000, 32).astype(np.float32)
    idx = rng.randint(0, 10000, 50000)
    np.testing.assert_array_equal(gather_rows(src, idx, num_threads=4),
                                  src[idx])


def test_cyclic_pad():
    idx = np.asarray([3, 1, 4], np.int32)
    out = cyclic_pad_indices(idx, 8)
    np.testing.assert_array_equal(out, [3, 1, 4, 3, 1, 4, 3, 1])


def test_prefetcher_overlaps():
    import time
    produced = []

    def produce(step):
        if step >= 5:
            raise StopIteration
        time.sleep(0.01)
        produced.append(step)
        return step * 2

    pf = HostPrefetcher(produce, depth=2)
    got = []
    while True:
        item = pf.next()
        if item is None:
            break
        got.append(item)
    assert got == [0, 2, 4, 6, 8]
    pf.close()


class TestSvmlightParser:
    """Native svmlight parser (ft_svmlight_scan/parse) against the
    format-faithful fixture generator AND sklearn's parser."""

    def _gen(self, tmp_path, name, n, f, labels, **kw):
        from format_fixtures import write_svmlight
        path = str(tmp_path / name)
        dense, ys = write_svmlight(path, n, f, labels=labels, **kw)
        return path, dense, ys

    def test_matches_generator_and_sklearn(self, tmp_path):
        import numpy as np
        from fedtorch_tpu.native.host_pipeline import parse_svmlight
        path, dense, ys = self._gen(tmp_path, "train", 200, 12,
                                    "pm1", comments=True)
        with open(path, "rb") as fh:
            got = parse_svmlight(fh.read())
        if got is None:
            import pytest
            pytest.skip("native toolchain unavailable")
        x, y = got
        # the generator's dense matrix holds full doubles; the text
        # carries 6 sig figs, so both parsers see the rounded values
        np.testing.assert_allclose(x, dense.astype(np.float32),
                                   rtol=1e-5, atol=1e-8)
        np.testing.assert_array_equal(y, ys.astype(np.float32))
        # bitwise-identical to sklearn on the same bytes
        from sklearn.datasets import load_svmlight_file
        xs, ys2 = load_svmlight_file(path)
        np.testing.assert_array_equal(
            x, np.asarray(xs.todense(), np.float32))
        np.testing.assert_array_equal(y, ys2.astype(np.float32))

    def test_multithreaded_parse_matches(self, tmp_path):
        import numpy as np
        from fedtorch_tpu.native.host_pipeline import parse_svmlight
        path, dense, ys = self._gen(tmp_path, "big", 5000, 24, "year")
        with open(path, "rb") as fh:
            raw = fh.read()
        got = parse_svmlight(raw, num_threads=4)
        if got is None:
            import pytest
            pytest.skip("native toolchain unavailable")
        x, y = got
        np.testing.assert_allclose(x, dense.astype(np.float32),
                                   rtol=1e-5, atol=1e-8)
        np.testing.assert_array_equal(y, ys.astype(np.float32))

    def test_n_features_override_and_no_trailing_newline(self):
        import numpy as np
        from fedtorch_tpu.native.host_pipeline import parse_svmlight
        got = parse_svmlight(b"1 2:0.5", n_features=6)
        if got is None:
            import pytest
            pytest.skip("native toolchain unavailable")
        x, y = got
        assert x.shape == (1, 6) and y.tolist() == [1.0]
        assert x[0, 1] == np.float32(0.5) and x.sum() == np.float32(0.5)

    def test_malformed_raises(self):
        import pytest
        from fedtorch_tpu.native.host_pipeline import parse_svmlight
        if parse_svmlight(b"1 1:0.5\n") is None:
            pytest.skip("native toolchain unavailable")
        for bad in (b"1 3:0.5 2:0.1\n",   # non-ascending
                    b"1 0:0.5\n",          # index < 1
                    b"1 7:0.5\n",          # > n_features (with override)
                    b"1 2=0.5\n"):         # bad separator
            with pytest.raises(ValueError, match="svmlight"):
                parse_svmlight(bad, n_features=4)

    def test_load_libsvm_uses_native_path(self, tmp_path, monkeypatch):
        """End-to-end through load_libsvm: the engine-facing reader
        produces the same splits whichever parser runs."""
        import numpy as np
        from format_fixtures import write_svmlight
        from fedtorch_tpu.data.datasets import load_libsvm
        base = tmp_path / "rcv1"
        write_svmlight(str(base / "rcv1_train.binary.bz2"), 40, 8,
                       labels="pm1", compress=True)
        write_svmlight(str(base / "rcv1_test.binary.bz2"), 10, 8,
                       labels="pm1", compress=True, seed=1)
        native = load_libsvm("rcv1", str(tmp_path))
        import fedtorch_tpu.native.host_pipeline as hp
        monkeypatch.setattr(hp, "parse_svmlight",
                            lambda *a, **k: None)  # force sklearn
        sk = load_libsvm("rcv1", str(tmp_path))
        np.testing.assert_array_equal(native.train_x, sk.train_x)
        np.testing.assert_array_equal(native.train_y, sk.train_y)
        np.testing.assert_array_equal(native.test_x, sk.test_x)

    def test_missing_value_rejected_not_misparsed(self):
        """A pair with a missing value must raise, not silently consume
        the NEXT line's label as the value (code-review r4)."""
        import pytest
        from fedtorch_tpu.native.host_pipeline import parse_svmlight
        if parse_svmlight(b"1 1:0.5\n") is None:
            pytest.skip("native toolchain unavailable")
        with pytest.raises(ValueError, match="svmlight"):
            parse_svmlight(b"1 2:\n5 1:9\n", n_features=4)

    def test_scan_fast_path_matches_comment_path(self):
        """max_index via the backward last-token walk (no '#') equals
        the tokenizing walk (with '#')."""
        import pytest
        from fedtorch_tpu.native.host_pipeline import parse_svmlight
        plain = b"1 2:0.5 7:1.25\n-1 3:0.1\n"
        commented = b"1 2:0.5 7:1.25 # note\n-1 3:0.1\n"
        a = parse_svmlight(plain)
        if a is None:
            pytest.skip("native toolchain unavailable")
        b = parse_svmlight(commented)
        assert a[0].shape == b[0].shape == (2, 7)
        import numpy as np
        np.testing.assert_array_equal(a[0], b[0])
