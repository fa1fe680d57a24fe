"""Native loader tests (C++ shim via ctypes)."""

import numpy as np
import pytest

from dask_ml_tpu import io as dio

# hypothesis gates ONLY the property classes below — a module-level
# importorskip silently dropped the entire deterministic loader suite on
# images without it (this one), which is exactly the coverage hole the
# ISSUE-3 satellite closes
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - environment-dependent
    HAVE_HYPOTHESIS = False

    def given(*_a, **_k):  # placeholder decorators so the module imports
        return lambda fn: fn

    settings = given

    class _St:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _St()

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed"
)


@pytest.fixture(scope="module")
def csv_file(tmp_path_factory):
    rng = np.random.RandomState(0)
    X = np.round(rng.normal(size=(537, 6)).astype(np.float32), 5)
    p = tmp_path_factory.mktemp("io") / "data.csv"
    np.savetxt(p, X, delimiter=",", fmt="%.5f")
    return str(p), X


class TestLoaderBuild:
    """The shared object is keyed on ``loader.cpp``'s CONTENT: an mtime
    means nothing after a copy or a checkout, so neither a missing nor a
    stale binary may stand in for the committed source."""

    @pytest.fixture
    def private_native_dir(self, tmp_path, monkeypatch):
        import shutil

        src = tmp_path / "loader.cpp"
        shutil.copy(dio._SRC, src)
        monkeypatch.setattr(dio, "_SRC", str(src))
        monkeypatch.setattr(dio, "_lib", None)
        return tmp_path

    def test_missing_and_stale_so_both_build_from_source(
            self, private_native_dir, csv_file):
        import os

        path, X = csv_file
        stale = private_native_dir / "_loader.so"
        stale.write_bytes(b"not a shared object")
        # newer than the source: the old mtime rule would have loaded it
        os.utime(stale, (2**31, 2**31))
        dio._load()
        built = dio._so_path()
        assert os.path.exists(built) and not stale.exists()
        np.testing.assert_allclose(dio.read_csv(path), X, rtol=1e-6)

    def test_content_change_rebuilds(self, private_native_dir, monkeypatch):
        import os

        dio._load()
        first = dio._so_path()
        with open(dio._SRC, "a") as f:
            f.write("\n// one more line of source\n")
        monkeypatch.setattr(dio, "_lib", None)
        dio._load()
        second = dio._so_path()
        assert second != first
        assert os.path.exists(second) and not os.path.exists(first)


class TestCSV:
    def test_dims(self, csv_file):
        p, X = csv_file
        assert dio.csv_dims(p) == X.shape

    def test_read_matches_numpy(self, csv_file):
        p, X = csv_file
        out = dio.read_csv(p)
        assert out.dtype == np.float32
        np.testing.assert_allclose(out, X, rtol=1e-5)

    def test_multithreaded_identical(self, csv_file):
        p, X = csv_file
        np.testing.assert_array_equal(
            dio.read_csv(p, n_threads=1), dio.read_csv(p, n_threads=7)
        )

    def test_header_skipped(self, tmp_path):
        p = tmp_path / "h.csv"
        p.write_text("a,b\n1.5,2.5\n3.0,4.0\n")
        out = dio.read_csv(str(p), has_header=True)
        np.testing.assert_allclose(out, [[1.5, 2.5], [3.0, 4.0]])
        assert dio.csv_dims(str(p), has_header=True) == (2, 2)

    def test_malformed_raises(self, tmp_path):
        p = tmp_path / "bad.csv"
        p.write_text("1.0,2.0\nfoo,bar\n")
        with pytest.raises(OSError):
            dio.read_csv(str(p))

    def test_missing_file_raises(self):
        with pytest.raises(OSError):
            dio.csv_dims("/nonexistent/x.csv")

    def test_short_row_raises(self, tmp_path):
        # A row with fewer fields must NOT silently consume values from the
        # next line (strtof skips '\n' as whitespace).
        p = tmp_path / "short.csv"
        p.write_text("1.0,2.0\n3.0\n5.0,6.0\n")
        with pytest.raises(OSError):
            dio.read_csv(str(p))

    def test_long_row_raises(self, tmp_path):
        p = tmp_path / "long.csv"
        p.write_text("1.0,2.0\n3.0,4.0,9.9\n")
        with pytest.raises(OSError):
            dio.read_csv(str(p))

    def test_no_trailing_newline_ok(self, tmp_path):
        p = tmp_path / "nonl.csv"
        p.write_text("1.0,2.0\n3.0,4.0")
        out = dio.read_csv(str(p))
        np.testing.assert_allclose(out, [[1.0, 2.0], [3.0, 4.0]])

    def test_stream_blocks(self, csv_file):
        p, X = csv_file
        blocks = list(dio.stream_csv_blocks(p, 100))
        assert [b.shape[0] for b in blocks] == [100] * 5 + [37]
        np.testing.assert_allclose(np.vstack(blocks), X, rtol=1e-5)

    def test_sharded_ingest(self, csv_file, mesh):
        p, X = csv_file
        s = dio.read_csv_sharded(p)
        from dask_ml_tpu.core import unshard

        assert s.shape == X.shape
        np.testing.assert_allclose(unshard(s), X, rtol=1e-5)


class TestBinary:
    def test_roundtrip(self, tmp_path, rng):
        X = rng.normal(size=(64, 5)).astype(np.float32)
        p = tmp_path / "x.bin"
        X.tofile(p)
        out = dio.read_binary(str(p), (64, 5))
        np.testing.assert_array_equal(out, X)

    def test_offset(self, tmp_path, rng):
        X = rng.normal(size=(10, 4)).astype(np.float32)
        p = tmp_path / "x.bin"
        X.tofile(p)
        out = dio.read_binary(str(p), (5, 4), offset_bytes=5 * 4 * 4)
        np.testing.assert_array_equal(out, X[5:])

    def test_short_file_raises(self, tmp_path):
        p = tmp_path / "short.bin"
        np.zeros(3, dtype=np.float32).tofile(p)
        with pytest.raises(OSError):
            dio.read_binary(str(p), (100, 100))


class TestIncrementalPipeline:
    def test_stream_into_incremental(self, csv_file, mesh):
        """End-to-end: native loader blocks → Incremental partial_fit."""
        from sklearn.linear_model import SGDClassifier

        from dask_ml_tpu.wrappers import Incremental

        p, X = csv_file
        w = np.ones(X.shape[1])
        y = (X @ w > 0).astype(np.int32)
        inc = Incremental(SGDClassifier(random_state=0))
        lo = 0
        for block in dio.stream_csv_blocks(p, 128):
            inc.partial_fit(block, y[lo: lo + len(block)], classes=[0, 1])
            lo += len(block)
        acc = (inc.predict(X) == y).mean()
        assert acc > 0.8


class TestNativeStreamSession:
    def test_blocks_match_full_read(self, tmp_path, rng):
        p = tmp_path / "s.csv"
        X = rng.normal(size=(997, 5)).astype(np.float32)
        np.savetxt(p, X, delimiter=",", fmt="%.6f")
        full = dio.read_csv(str(p))
        blocks = list(dio.stream_csv_blocks(str(p), 100, prefetch=3))
        assert [b.shape[0] for b in blocks] == [100] * 9 + [97]
        np.testing.assert_array_equal(np.concatenate(blocks), full)

    def test_abandoned_generator_closes_cleanly(self, tmp_path, rng):
        p = tmp_path / "s.csv"
        np.savetxt(p, rng.normal(size=(500, 3)), delimiter=",", fmt="%.4f")
        gen = dio.stream_csv_blocks(str(p), 50, prefetch=2)
        next(gen)
        next(gen)
        gen.close()  # must join the native worker without hanging

    def test_malformed_row_errors(self, tmp_path):
        p = tmp_path / "bad.csv"
        p.write_text("1.0,2.0\n3.0\n5.0,6.0\n")
        with pytest.raises(OSError):
            list(dio.stream_csv_blocks(str(p), 2))

    def test_empty_file(self, tmp_path):
        p = tmp_path / "empty.csv"
        p.write_text("")
        assert list(dio.stream_csv_blocks(str(p), 10)) == []

    def test_error_surfaces_after_valid_prefix(self, tmp_path):
        """All valid blocks before a malformed row are yielded, THEN the
        error raises — deterministic prefix despite prefetch."""
        p = tmp_path / "mid.csv"
        lines = ["%d.0,%d.0" % (i, i) for i in range(10)]
        lines[7] = "bad_row"
        p.write_text("\n".join(lines) + "\n")
        got = []
        with pytest.raises(OSError):
            for b in dio.stream_csv_blocks(str(p), 2, prefetch=4):
                got.append(b)
        assert len(got) == 3  # rows 0-5 (3 full blocks before row 7's block)

    def test_zero_block_rows_rejected(self, tmp_path):
        p = tmp_path / "z.csv"
        p.write_text("1.0,2.0\n")
        with pytest.raises(ValueError, match="block_rows"):
            next(dio.stream_csv_blocks(str(p), 0))


class TestFastFloatParse:
    """The C++ fast field parser (Clinger fast path) must agree with
    Python's float() across the decimal forms numeric CSV actually
    contains, and fall back cleanly on the forms it rejects."""

    def test_adversarial_forms(self, tmp_path):
        fields = [
            "0", "-0", "1", "-1", "0.5", "-.5", "+.25", "3.", "1e0",
            "1E5", "-2.5e-3", "6.02214076e23", "1e-22", "9.999999e21",
            # fallback territory: >19 digits, big exponents, inf/nan
            "123456789012345678901234567890", "1e300", "1e-300",
            "-1.7976931348623157e308", "4.9e-324", "inf", "-inf", "nan",
            "0.000000000000000000001", "1234567.1234567890123",
            # hex floats: the fast path must punt these to strtof whole
            "0x1A", "-0X2p1", "0x0.8p1", "7", "8", "9",
        ]
        assert len(fields) % 5 == 0
        rows = [fields[i:i + 5] for i in range(0, len(fields), 5)]
        txt = "\n".join(",".join(r) for r in rows) + "\n"
        p = tmp_path / "adv.csv"
        p.write_text(txt)
        out = dio.read_csv(str(p))

        def pyfloat(v):
            try:
                return float(v)
            except ValueError:  # hex floats: Python needs fromhex
                return float.fromhex(v)

        expect = np.array(
            [[np.float32(pyfloat(v)) for v in r] for r in rows],
            dtype=np.float32)
        np.testing.assert_array_equal(
            np.nan_to_num(out, nan=12345.0),
            np.nan_to_num(expect, nan=12345.0))

    def test_random_float_roundtrip_property(self, tmp_path):
        # float32 values formatted the ways writers actually format them
        r = np.random.RandomState(3)
        vals = np.concatenate([
            r.normal(scale=10.0 ** r.randint(-20, 20, 500), size=500),
            r.rand(500), np.zeros(10),
        ]).astype(np.float32)
        vals = vals[: (len(vals) // 4) * 4].reshape(-1, 4)
        for fmt in ("%.6g", "%.9g", "%r", "%.17g"):
            p = tmp_path / "r.csv"
            if fmt == "%r":
                txt = "\n".join(
                    ",".join(repr(float(v)) for v in row) for row in vals)
            else:
                txt = "\n".join(
                    ",".join(fmt % v for v in row) for row in vals)
            p.write_text(txt + "\n")
            out = dio.read_csv(str(p))
            if fmt in ("%r", "%.9g", "%.17g"):
                # enough digits to round-trip float32 exactly
                np.testing.assert_array_equal(out, vals, err_msg=fmt)
            else:
                np.testing.assert_allclose(out, vals, rtol=1e-5,
                                           err_msg=fmt)


@needs_hypothesis
class TestWindowedStreamProperties:
    """Adversarial window-boundary coverage for the windowed streaming
    session (round 5: the session went from whole-file-resident to a
    moving window; every refill/compact/carry-over cycle is new code).
    DMLT_STREAM_WINDOW_BYTES shrinks the window to a few tens of bytes
    so tiny files exercise MANY windows, lines split across refills,
    blank lines at region starts, and missing trailing newlines."""

    @settings(max_examples=40, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        n_rows=st.integers(1, 40),
        n_cols=st.integers(1, 5),
        block_rows=st.integers(1, 7),
        window=st.integers(16, 200),
        trailing=st.booleans(),
        blanks=st.booleans(),
    )
    def test_stream_matches_whole_file_parse(
            self, seed, n_rows, n_cols, block_rows, window, trailing,
            blanks):
        import os
        import tempfile
        rng = np.random.RandomState(seed % (2**31 - 1))
        rows = rng.normal(size=(n_rows, n_cols)) * 10.0 ** rng.randint(
            -3, 4, size=(n_rows, n_cols))
        lines = [",".join(f"{v:.6g}" for v in r) for r in rows]
        if blanks:
            # blank lines anywhere (including the very start and between
            # window boundaries) must be skipped, as the whole-file
            # parser does
            out = []
            for ln in lines:
                if rng.rand() < 0.3:
                    out.append("")
                out.append(ln)
            if rng.rand() < 0.5:
                out.append("")
            lines = out
        text = "\n".join(lines)
        if trailing:
            text += "\n"
        with tempfile.NamedTemporaryFile(
                "w", suffix=".csv", delete=False) as f:
            f.write(text)
            p = f.name
        saved = os.environ.get("DMLT_STREAM_WINDOW_BYTES")
        os.environ["DMLT_STREAM_WINDOW_BYTES"] = str(window)
        try:
            got = [b.copy() for b in dio.stream_csv_blocks(p, block_rows)]
        finally:
            if saved is None:
                os.environ.pop("DMLT_STREAM_WINDOW_BYTES", None)
            else:
                os.environ["DMLT_STREAM_WINDOW_BYTES"] = saved
        stream = (np.vstack(got) if got
                  else np.zeros((0, n_cols), np.float32))
        whole = dio.read_csv(p)
        os.unlink(p)
        assert stream.shape == whole.shape, (stream.shape, whole.shape)
        np.testing.assert_array_equal(stream, whole)
        assert all(b.shape[0] <= block_rows for b in got)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), window=st.integers(16, 120))
    def test_malformed_line_prefix_across_windows(self, seed, window):
        import os
        import tempfile
        """The deterministic-prefix error contract must hold at ANY
        window size: every full block before the first malformed line
        is delivered, then the error raises."""
        rng = np.random.RandomState(seed % (2**31 - 1))
        n = int(rng.randint(4, 30))
        bad = int(rng.randint(0, n))
        lines = [f"{i}.0,{i * 2}.0" for i in range(n)]
        lines[bad] = "not,numeric_at_all"
        with tempfile.NamedTemporaryFile(
                "w", suffix=".csv", delete=False) as f:
            f.write("\n".join(lines) + "\n")
            p = f.name
        saved = os.environ.get("DMLT_STREAM_WINDOW_BYTES")
        os.environ["DMLT_STREAM_WINDOW_BYTES"] = str(window)
        got = []
        try:
            with pytest.raises(OSError):
                for b in dio.stream_csv_blocks(p, 2):
                    got.append(b.copy())
        finally:
            if saved is None:
                os.environ.pop("DMLT_STREAM_WINDOW_BYTES", None)
            else:
                os.environ["DMLT_STREAM_WINDOW_BYTES"] = saved
            os.unlink(p)
        assert len(got) == bad // 2  # full blocks strictly before the bad row
        if got:
            np.testing.assert_array_equal(
                np.vstack(got)[:, 0],
                np.arange(bad // 2 * 2, dtype=np.float32))


class TestStreamEdgeCases:
    """ISSUE-3 satellite: reader edge cases x prefetch permutations —
    the stream contract must be depth-invariant and degenerate-safe."""

    def test_csv_empty_file(self, tmp_path):
        p = tmp_path / "empty.csv"
        p.write_text("")
        assert dio.csv_dims(str(p)) == (0, 0)
        assert list(dio.stream_csv_blocks(str(p), 10)) == []

    def test_csv_header_only(self, tmp_path):
        p = tmp_path / "h.csv"
        p.write_text("a,b\n")
        assert list(
            dio.stream_csv_blocks(str(p), 10, has_header=True)
        ) == []

    def test_csv_block_rows_exceed_n_rows(self, csv_file):
        p, X = csv_file
        blocks = list(dio.stream_csv_blocks(p, X.shape[0] * 10))
        assert len(blocks) == 1 and blocks[0].shape == X.shape
        np.testing.assert_allclose(blocks[0], X, rtol=1e-5)

    def test_csv_last_partial_block(self, csv_file):
        p, X = csv_file  # 537 rows: 2x250 + 37
        blocks = list(dio.stream_csv_blocks(p, 250))
        assert [b.shape[0] for b in blocks] == [250, 250, 37]
        np.testing.assert_allclose(np.vstack(blocks), X, rtol=1e-5)

    @pytest.mark.parametrize("prefetch", [1, 2, 4])
    def test_csv_prefetch_permutations_bit_identical(self, csv_file,
                                                     prefetch):
        """The native session's prefetch worker must never reorder or
        alter blocks: every depth is bit-identical to serial-ish depth 1
        at every block boundary (including the partial tail)."""
        p, X = csv_file
        base = [b.copy() for b in dio.stream_csv_blocks(p, 100, prefetch=1)]
        got = [b.copy() for b in dio.stream_csv_blocks(
            p, 100, prefetch=prefetch)]
        assert len(base) == len(got)
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("depth", [0, 1, 3])
    def test_csv_pipeline_depth_permutations(self, csv_file, depth):
        """The PYTHON-level prefetch pipeline over the reader: same
        blocks, same order, at every DASK_ML_TPU_PREFETCH_DEPTH."""
        from dask_ml_tpu.pipeline import prefetch_blocks

        p, X = csv_file
        got = [
            b.copy() for b in prefetch_blocks(
                dio.stream_csv_blocks(p, 100), depth=depth)
        ]
        assert [b.shape[0] for b in got] == [100] * 5 + [37]
        np.testing.assert_allclose(np.vstack(got), X, rtol=1e-5)

    def test_binary_stream_roundtrip(self, tmp_path, rng):
        X = rng.normal(size=(257, 8)).astype(np.float32)
        p = tmp_path / "x.bin"
        X.tofile(p)
        blocks = list(dio.stream_binary_blocks(str(p), 100, 8))
        assert [b.shape[0] for b in blocks] == [100, 100, 57]
        np.testing.assert_array_equal(np.vstack(blocks), X)

    def test_binary_empty_file(self, tmp_path):
        p = tmp_path / "empty.bin"
        p.write_bytes(b"")
        assert list(dio.stream_binary_blocks(str(p), 10, 4)) == []

    def test_binary_block_rows_exceed_n_rows(self, tmp_path, rng):
        X = rng.normal(size=(7, 3)).astype(np.float32)
        p = tmp_path / "small.bin"
        X.tofile(p)
        blocks = list(dio.stream_binary_blocks(str(p), 1000, 3))
        assert len(blocks) == 1
        np.testing.assert_array_equal(blocks[0], X)

    def test_binary_trailing_partial_row_ignored(self, tmp_path):
        # 10 floats at n_features=4: 2 complete rows + 2 stray values
        np.arange(10, dtype=np.float32).tofile(tmp_path / "part.bin")
        blocks = list(
            dio.stream_binary_blocks(str(tmp_path / "part.bin"), 10, 4)
        )
        assert [b.shape for b in blocks] == [(2, 4)]
        np.testing.assert_array_equal(
            np.vstack(blocks), np.arange(8, dtype=np.float32).reshape(2, 4)
        )

    def test_binary_missing_file_raises(self):
        with pytest.raises(OSError):
            list(dio.stream_binary_blocks("/nonexistent/x.bin", 10, 4))

    @pytest.mark.parametrize("depth", [0, 2])
    def test_binary_pipeline_depth_bit_identical(self, tmp_path, rng,
                                                 depth):
        from dask_ml_tpu.pipeline import prefetch_blocks

        X = rng.normal(size=(530, 6)).astype(np.float32)
        p = tmp_path / "s.bin"
        X.tofile(p)
        got = [
            b.copy() for b in prefetch_blocks(
                dio.stream_binary_blocks(str(p), 128, 6), depth=depth)
        ]
        assert [b.shape[0] for b in got] == [128, 128, 128, 128, 18]
        np.testing.assert_array_equal(np.vstack(got), X)
