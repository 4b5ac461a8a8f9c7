"""Core runtime tests: Table, Params, Pipeline, serialization, mesh."""

import numpy as np
import pytest

from mmlspark_tpu.core import (
    Table,
    Param,
    Params,
    ServiceParam,
    Transformer,
    Estimator,
    Model,
    Pipeline,
    PipelineModel,
    pipeline_model,
    Timer,
    register_stage,
    save_stage,
    load_stage,
    registry,
    find_unused_column_name,
)


# -- Table ------------------------------------------------------------------
class TestTable:
    def test_construct_and_access(self):
        t = Table({"a": [1, 2, 3], "b": ["x", "y", "z"]})
        assert t.num_rows == 3
        assert t.columns == ["a", "b"]
        assert isinstance(t["a"], np.ndarray)
        assert isinstance(t["b"], list)

    def test_ragged_rejected(self):
        with pytest.raises(ValueError):
            Table({"a": [1, 2], "b": [1]})

    def test_vector_column(self):
        t = Table({"v": np.ones((4, 8))})
        assert t["v"].shape == (4, 8)
        assert t.num_rows == 4

    def test_functional_updates(self):
        t = Table({"a": [1, 2]})
        t2 = t.with_column("b", [3.0, 4.0])
        assert "b" not in t and "b" in t2
        t3 = t2.rename({"a": "c"})
        assert set(t3.columns) == {"c", "b"}
        t4 = t2.drop("a")
        assert t4.columns == ["b"]
        assert t2.select("b").columns == ["b"]

    def test_gather_filter_concat_split(self):
        t = Table({"a": np.arange(10), "s": [str(i) for i in range(10)]})
        g = t.gather([1, 3, 5])
        assert g["a"].tolist() == [1, 3, 5]
        assert g["s"] == ["1", "3", "5"]
        f = t.filter(lambda r: r["a"] % 2 == 0)
        assert f["a"].tolist() == [0, 2, 4, 6, 8]
        c = g.concat(f)
        assert c.num_rows == 8
        left, right = t.split(0.7, seed=1)
        assert left.num_rows == 7 and right.num_rows == 3
        assert sorted(left["a"].tolist() + right["a"].tolist()) == list(range(10))

    def test_from_rows_and_rows(self):
        rows = [{"a": 1, "b": "x"}, {"a": 2, "b": "y"}]
        t = Table.from_rows(rows)
        assert list(t.rows()) == rows

    def test_equals_tolerant(self):
        a = Table({"x": np.array([1.0, 2.0])})
        b = Table({"x": np.array([1.0, 2.0 + 1e-9])})
        c = Table({"x": np.array([1.0, 2.1])})
        assert a.equals(b)
        assert not a.equals(c)

    def test_meta(self):
        t = Table({"a": [1, 2]}).with_meta("a", {"category_values": ["p", "q"]})
        assert t.meta("a")["category_values"] == ["p", "q"]
        assert t.meta("missing_col") == {} if "missing_col" not in t else True

    def test_find_unused_column_name(self):
        t = Table({"x": [1], "x_1": [2]})
        assert find_unused_column_name("x", t) == "x_2"
        assert find_unused_column_name("y", t) == "y"


# -- Params -----------------------------------------------------------------
class _Demo(Params):
    alpha = Param(1.0, "alpha value", ptype=float, validator=lambda v: v >= 0)
    name = Param("d", "a name", ptype=str)
    svc = ServiceParam(None, "scalar-or-column")


class TestParams:
    def test_defaults_and_set(self):
        d = _Demo()
        assert d.get("alpha") == 1.0
        d.set(alpha=2.5)
        assert d.alpha == 2.5
        d.alpha = 3.0
        assert d.get("alpha") == 3.0

    def test_validation(self):
        d = _Demo()
        with pytest.raises(ValueError):
            d.set(alpha=-1.0)
        with pytest.raises(TypeError):
            d.set(name=42)
        with pytest.raises(KeyError):
            d.set(nope=1)

    def test_copy_isolated(self):
        d = _Demo(alpha=5.0)
        e = d.copy({"alpha": 6.0})
        assert d.alpha == 5.0 and e.alpha == 6.0

    def test_service_param_scalar_and_column(self):
        t = Table({"c": [10, 20, 30]})
        d = _Demo()
        assert d.resolve("svc", t) is None
        d.set(svc=7)
        assert d.resolve("svc", t) == [7, 7, 7]
        d.set_col(svc="c")
        assert d.resolve("svc", t) == [10, 20, 30]

    def test_explain(self):
        assert "alpha value" in _Demo().explain_params()


# -- Pipeline + serialization ----------------------------------------------
@register_stage
class _AddOne(Transformer):
    input_col = Param("x", "in", ptype=str)
    output_col = Param("y", "out", ptype=str)

    def _transform(self, table):
        return table.with_column(self.get("output_col"), table[self.get("input_col")] + 1)


@register_stage
class _MeanShift(Estimator):
    input_col = Param("x", "in", ptype=str)

    def _fit(self, table):
        m = _MeanShiftModel()
        m.set(input_col=self.get("input_col"))
        m.mean = float(np.mean(table[self.get("input_col")]))
        return m


@register_stage
class _MeanShiftModel(Model):
    input_col = Param("x", "in", ptype=str)
    mean: float = 0.0

    def _transform(self, table):
        c = self.get("input_col")
        return table.with_column(c, table[c] - self.mean)

    def _save_state(self):
        return {"mean": self.mean}

    def _load_state(self, state):
        self.mean = state["mean"]


class TestPipeline:
    def test_fit_transform(self):
        t = Table({"x": np.array([1.0, 2.0, 3.0])})
        pipe = Pipeline([_AddOne(), _MeanShift()])
        model = pipe.fit(t)
        assert isinstance(model, PipelineModel)
        out = model.transform(t)
        np.testing.assert_allclose(out["x"], [-1.0, 0.0, 1.0])
        assert out["y"].tolist() == [2.0, 3.0, 4.0]

    def test_pipeline_model_builder(self):
        pm = pipeline_model(_AddOne(), _AddOne(input_col="y", output_col="z"))
        out = pm.transform(Table({"x": np.array([0.0])}))
        assert out["z"].tolist() == [2.0]

    def test_timer(self):
        tm = Timer(_AddOne())
        out = tm.transform(Table({"x": np.array([1.0])}))
        assert out["y"].tolist() == [2.0]
        assert tm.last_elapsed is not None and tm.last_elapsed >= 0

    def test_save_load_roundtrip(self, tmp_path):
        t = Table({"x": np.array([1.0, 2.0, 3.0])})
        model = Pipeline([_AddOne(), _MeanShift()]).fit(t)
        p = str(tmp_path / "pm")
        save_stage(model, p)
        loaded = load_stage(p)
        assert loaded.transform(t).equals(model.transform(t))

    def test_save_load_unfitted_pipeline(self, tmp_path):
        pipe = Pipeline([_AddOne(output_col="q")])
        p = str(tmp_path / "pipe")
        pipe.save(p)
        loaded = load_stage(p)
        stages = loaded.get("stages")
        assert len(stages) == 1 and stages[0].get("output_col") == "q"

    def test_registry_contains_stages(self):
        names = {cls.__name__ for cls in registry().values()}
        assert {"Pipeline", "PipelineModel", "_AddOne"} <= names


# -- mesh -------------------------------------------------------------------
class TestMesh:
    def test_eight_virtual_devices(self):
        import jax

        assert jax.device_count() == 8

    def test_mesh_and_shard_rows(self, mesh8):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from mmlspark_tpu.parallel import DATA_AXIS, shard_rows

        x, n = shard_rows(np.arange(10, dtype=np.float32), mesh8)
        assert n == 10 and x.shape[0] == 16  # padded to multiple of 8

        @jax.jit
        def total(v):
            return jnp.sum(v)

        assert float(total(x)) == sum(range(10))

    def test_psum_over_mesh(self, mesh8):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from mmlspark_tpu.parallel import DATA_AXIS, MODEL_AXIS

        x = np.ones((8, 4), np.float32)

        f = shard_map(
            lambda v: jax.lax.psum(jnp.sum(v), DATA_AXIS),
            mesh=mesh8,
            in_specs=P(DATA_AXIS, None),
            out_specs=P(),
        )
        assert float(f(x)) == 32.0


# -- review-driven regression tests ----------------------------------------
class TestReviewRegressions:
    def test_empty_gather_and_filter_chain(self):
        t = Table({"a": np.array([1.0, 2.0]), "s": ["x", "y"]})
        empty = t.filter(lambda r: False)
        assert empty.num_rows == 0
        assert empty.filter(lambda r: True).num_rows == 0
        assert t.gather([]).num_rows == 0

    def test_rename_collision_raises(self):
        t = Table({"a": [1], "b": [2]})
        with pytest.raises(ValueError):
            t.rename({"a": "b"})

    def test_numpy_scalar_state_roundtrip(self, tmp_path):
        m = _MeanShiftModel()
        m.mean = np.float64(3.5)  # natural np.mean result
        p = str(tmp_path / "m")
        save_stage(m, p)
        loaded = load_stage(p)
        assert isinstance(loaded.mean, float) and loaded.mean == 3.5

    def test_registry_qualified_names(self):
        from mmlspark_tpu.core import stage_class

        assert stage_class("Pipeline").__name__ == "Pipeline"
        assert stage_class(f"{Pipeline.__module__}.Pipeline") is stage_class("Pipeline")

    def test_with_column_drops_stale_meta(self):
        t = Table({"a": [1, 2]}).with_meta("a", {"category_values": ["p", "q"]})
        t2 = t.with_column("a", [3, 4])
        assert "category_values" not in t2.meta("a")


# -- compile cache ----------------------------------------------------------
class TestCompileCachePlacement:
    """mmlspark_tpu/__init__.py places JAX's persistent compile cache once,
    at import: `JAX_COMPILATION_CACHE_DIR` wins and the code sets nothing;
    otherwise a fixed in-checkout path, the same in every interpreter."""

    @staticmethod
    def _cache_dir_in_fresh_interpreter(**env_overrides):
        """What `jax.config.jax_compilation_cache_dir` says after the
        package import (no backend is initialised by reading it)."""
        import os
        import subprocess
        import sys

        from conftest import subprocess_env

        env = subprocess_env()
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.update(env_overrides)
        out = subprocess.run(
            [sys.executable, "-c",
             "import mmlspark_tpu, jax; "
             "print(jax.config.jax_compilation_cache_dir)"],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=os.path.sep)
        assert out.returncode == 0, out.stderr[-500:]
        return out.stdout.strip().splitlines()[-1]

    def test_unset_env_gives_the_fixed_in_checkout_path(self):
        import os

        import mmlspark_tpu

        expected = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(mmlspark_tpu.__file__))), ".jax_cache")
        # two fresh interpreters agree: no pid, no temp name, no timestamp
        assert self._cache_dir_in_fresh_interpreter() == expected
        assert self._cache_dir_in_fresh_interpreter() == expected

    def test_env_wins_and_code_sets_nothing(self, monkeypatch, tmp_path):
        import jax

        import mmlspark_tpu

        assert self._cache_dir_in_fresh_interpreter(
            JAX_COMPILATION_CACHE_DIR=str(tmp_path)) == str(tmp_path)
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        mmlspark_tpu._place_compile_cache()
        assert calls == []
