"""Native host-kernel tests: the C++ binning / tree-predict kernels must be
bit-identical to their numpy fallbacks, and the loader must degrade
gracefully without a toolchain (NativeLoader.java:47-105 analogue)."""

import shutil

import numpy as np
import pytest

import mmlspark_tpu.native as native
from mmlspark_tpu.gbdt import BinMapper, Booster
from mmlspark_tpu.gbdt.booster import TrainOptions

HAS_GXX = shutil.which("g++") is not None


def _force_fallback(monkeypatch):
    """Make the loader report 'no native lib' so the numpy path runs."""
    monkeypatch.setattr(native, "_LIB", False)


def make_data(n=300, f=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    x[:, 2] = np.round(np.abs(x[:, 2]) * 3)          # low-cardinality column
    x[rng.random((n, f)) < 0.05] = np.nan            # missing cells
    # ±inf cells: the C++ and numpy binners implement comparison-binning
    # independently (isnan guard + searchsorted vs lower_bound); the
    # bit-identity gate must cover the inf path too
    x[rng.random((n, f)) < 0.03] = np.inf
    x[rng.random((n, f)) < 0.03] = -np.inf
    y = (np.nan_to_num(x[:, 0]) > 0).astype(np.float64)
    return x, y


@pytest.mark.skipif(not HAS_GXX, reason="no C++ toolchain")
class TestNativeKernels:
    def test_lib_builds_and_loads(self):
        assert native.available()

    def test_artefact_name_follows_source_hash(self, monkeypatch, tmp_path):
        """The cached .so is keyed on kernels.cpp's CONTENT: a copied tree
        (arbitrary mtimes, git-ignored _build/) must never reuse a library
        built from other source."""
        import hashlib
        import os

        with open(native._SRC, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()[:16]
        path = native._compile()
        assert os.path.basename(path) == f"libmmlsparktpu-{digest}.so"
        # different source -> different artefact, however old the file is
        other = tmp_path / "kernels.cpp"
        other.write_bytes(open(native._SRC, "rb").read() + b"\n// edit\n")
        os.utime(other, (0, 0))
        monkeypatch.setattr(native, "_SRC", str(other))
        assert native._lib_name() != os.path.basename(path)

    def test_binning_bit_identical(self, monkeypatch):
        x, _ = make_data()
        mapper = BinMapper(max_bin=63, categorical_indexes=(2,)).fit(x)
        with_native = mapper.transform(x)
        _force_fallback(monkeypatch)
        pure_numpy = mapper.transform(x)
        np.testing.assert_array_equal(with_native, pure_numpy)

    def test_predict_bit_identical(self, monkeypatch):
        x, y = make_data()
        xx = np.nan_to_num(x)
        b = Booster.train(
            xx, y, TrainOptions(objective="binary", num_iterations=12, num_leaves=15)
        )
        with_native = b.predict_raw(xx, device="host")
        _force_fallback(monkeypatch)
        pure_numpy = b.predict_raw(xx, device="host")
        np.testing.assert_array_equal(np.asarray(with_native),
                                      np.asarray(pure_numpy))
        # and both equal the jitted device traversal
        np.testing.assert_array_equal(
            np.asarray(with_native), np.asarray(b.predict_raw(xx, device="device"))
        )

    def test_predict_multiclass_bit_identical(self, monkeypatch):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(200, 5))
        y = rng.integers(0, 3, size=200).astype(np.float64)
        b = Booster.train(
            x, y, TrainOptions(objective="multiclass", num_class=3,
                               num_iterations=6, num_leaves=7)
        )
        with_native = b.predict_raw(x, device="host")
        _force_fallback(monkeypatch)
        pure_numpy = b.predict_raw(x, device="host")
        np.testing.assert_array_equal(np.asarray(with_native),
                                      np.asarray(pure_numpy))


class TestGracefulFallback:
    def test_no_native_env_still_works(self, monkeypatch):
        """Binning + host predict run pure-numpy when the lib is absent."""
        _force_fallback(monkeypatch)
        assert not native.available()
        x, y = make_data(n=120)
        xx = np.nan_to_num(x)
        b = Booster.train(
            xx, y, TrainOptions(objective="binary", num_iterations=4, num_leaves=7)
        )
        p = b.predict(xx, device="host")
        assert np.isfinite(np.asarray(p)).all()
