"""Unit gates for bench.py's artifact-shaping helpers.

The bench is the round's judged artifact; its orchestration helpers
(JSON-line extraction, family-field merge, FLOP sanity, timing) must
behave under every degraded outcome (missing family, null child output,
inflated cost analysis) — these are pure-python fast checks.
"""

import sys

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])

import bench  # noqa: E402


class TestLastJsonLine:
    def test_picks_last_valid_json(self):
        out = 'noise\n{"a": 1}\nlog line\n{"b": 2}\n'
        assert bench._last_json_line(out) == {"b": 2}

    def test_null_child_output_parses_to_none(self):
        # a CPU-forced solo child prints "null" (family skipped); the
        # orchestrator must treat that as "no result", not crash
        assert bench._last_json_line("null\n") is None

    def test_no_json_returns_none(self):
        assert bench._last_json_line("no json here\n") is None
        assert bench._last_json_line("") is None


class TestFamilyExtras:
    def test_gbdt_large_extra_none_gives_all_null(self):
        extra = bench._gbdt_large_extra(None)
        assert set(k for k in extra) == {
            "gbdt_large_rows_per_sec", "gbdt_large_fit_seconds",
            "gbdt_large_train_acc", "gbdt_large_valid_auc",
            "gbdt_large_modeled_hbm_gbps",
            "gbdt_large_modeled_hbm_frac_of_peak", "gbdt_large_bin_dtype",
            "gbdt_large_device_binning", "gbdt_predict_rows_per_sec",
            "gbdt_predict_resident_rows_per_sec",
        }
        assert all(v is None for v in extra.values())

    def test_gbdt_large_extra_populated(self):
        extra = bench._gbdt_large_extra({
            "rows_per_sec": 123456.78, "fit_seconds": 4.2, "acc": 0.91,
            "valid_auc": 0.87, "modeled_hbm_gbps": 55.5,
            "modeled_hbm_frac_of_peak": 0.068, "bin_dtype": "uint8",
            "device_binning": True, "predict_rows_per_sec": 1e6,
            "predict_resident_rows_per_sec": 5e6,
        })
        assert extra["gbdt_large_rows_per_sec"] == 123456.8
        assert extra["gbdt_large_train_acc"] == 0.91
        assert extra["gbdt_large_bin_dtype"] == "uint8"
        assert extra["gbdt_predict_resident_rows_per_sec"] == 5e6

    def test_trainer_extra_nulls_on_none(self):
        extra = bench._trainer_extra(None)
        assert extra["trainer_images_per_sec"] is None
        assert extra["trainer_vs_baseline"] is None

    def test_transformer_extra_nulls_on_none(self):
        extra = bench._transformer_extra(None)
        assert extra["transformer_train_flash_tokens_per_sec"] is None
        assert extra["transformer_fwd_mfu"] is None

    def test_merge_overrides_core_nulls(self):
        line = {"extra": dict(bench._gbdt_large_extra(None))}
        line["extra"].update(bench._gbdt_large_extra(
            {"rows_per_sec": 10.0}))
        assert line["extra"]["gbdt_large_rows_per_sec"] == 10.0


class TestMeasurementHonesty:
    def test_flops_sane_rejects_inflated_count(self, capsys):
        # an 8x padded-conv inflation must fall back to the analytic count
        assert bench.flops_sane(8e9, 1e9, "t") == 1e9
        assert "using analytic" in capsys.readouterr().err

    def test_flops_sane_accepts_close_count(self):
        assert bench.flops_sane(1.2e9, 1e9) == 1.2e9

    def test_flops_sane_handles_missing_sides(self):
        assert bench.flops_sane(None, 2.0) == 2.0
        assert bench.flops_sane(3.0, None) == 3.0

    def test_mfu(self):
        assert bench._mfu(98.5, 197.0) == 0.5
        assert bench._mfu(None, 197.0) is None
        assert bench._mfu(5.0, None) is None

    def test_median_timed_is_median(self, monkeypatch):
        calls = iter([0.0, 10.0, 10.0, 11.0, 11.0, 11.5])
        monkeypatch.setattr(bench.time, "perf_counter",
                            lambda: next(calls))
        # deltas: 10, 1, 0.5 -> median 1
        assert bench.median_timed(lambda: None, reps=3) == pytest.approx(1.0)


class TestAotGate:
    """tools/aot_gate.py is the on-chip compile gate. Off the chip only
    two things can be checked, and both are cheap: it refuses to run, and
    every Pallas block it would compile obeys Mosaic's block-shape rule."""

    def test_refuses_to_run_without_a_tpu(self):
        import pathlib
        import subprocess

        from conftest import subprocess_env

        gate = pathlib.Path(__file__).parents[1] / "tools/aot_gate.py"
        env = subprocess_env()
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-u", str(gate)], capture_output=True,
            text=True, timeout=300, env=env,
        )
        assert out.returncode == 2, out.stderr[-500:]
        assert "no TPU" in out.stderr
        assert "AOT GATE SUMMARY" not in out.stdout

    def test_pallas_blocks_obey_the_mosaic_block_rule(self, monkeypatch):
        """Each of a block's last two dims is a multiple of (8, 128) or
        spans the array's whole dim — the rule Mosaic enforces at
        lowering and the interpreter never checks (the flash forward's
        lse block broke it for as long as only the interpreter ran it).
        A pure shape check at the gate's shapes: pallas_call is replaced
        by a recorder and the wrappers run under eval_shape."""
        import jax
        import jax.experimental.pallas as pl
        import jax.numpy as jnp

        from tools import aot_gate

        calls = []

        def recording_pallas_call(kernel, *, out_shape, in_specs, out_specs,
                                  **_kw):
            def run(*args):
                outs_list = (list(out_shape)
                             if isinstance(out_shape, (list, tuple))
                             else [out_shape])
                specs_list = (list(out_specs)
                              if isinstance(out_specs, (list, tuple))
                              else [out_specs])
                calls.append(
                    [(sp.block_shape, a.shape) for sp, a in
                     zip(list(in_specs) + specs_list,
                         list(args) + outs_list)])
                outs = [jnp.zeros(o.shape, o.dtype) for o in outs_list]
                return (outs if isinstance(out_shape, (list, tuple))
                        else outs[0])
            return run

        monkeypatch.setattr(pl, "pallas_call", recording_pallas_call)
        # hist_build selects the variant through these; setenv registers
        # their restoration
        monkeypatch.setenv("MMLSPARK_TPU_HIST_GROUP", "1")
        monkeypatch.setenv("MMLSPARK_TPU_FUSED_HIST", "0")
        builds = [
            lambda: aot_gate.hist_build(),
            lambda: aot_gate.hist_build(bins_dtype=jnp.uint8),
            lambda: aot_gate.hist_build(group=4, bins_dtype=jnp.uint8),
            lambda: aot_gate.hist_build(fused=True, bins_dtype=jnp.uint8),
            lambda: aot_gate.flash_build(512),
            lambda: aot_gate.flash_build(4096),
            lambda: aot_gate.flash_build(512, grad=True),
        ]
        for build in builds:
            fn, args = build()
            jax.eval_shape(fn, *args)
        assert len(calls) == len(builds)
        for blocks in calls:
            for block, shape in blocks:
                assert len(block) == len(shape) >= 2, (block, shape)
                for b, full, mult in zip(block[-2:], shape[-2:], (8, 128)):
                    assert b == full or b % mult == 0, (block, shape)


class TestChipModel:
    def test_chip_peaks_on_cpu(self):
        kind, tflops, gbps = bench.chip_peaks()
        assert tflops is None and gbps is None  # tests run on CPU backend

    def test_every_table_key_names_one_part(self):
        # no bare generation key ("v5", "v4") that would hand an unlisted
        # part a sibling's peak: "TPU v5" alone must not resolve
        keys = [k for k, _ in bench._CHIP_PEAKS]
        assert not any(k in "tpu v5" for k in keys)
        assert not any(k in "tpu v4 lite" for k in keys)
        peaks = dict(bench._CHIP_PEAKS)
        assert peaks["v5 lite"] == (197.0, 819.0)
        assert np.isfinite(peaks["v5p"][0])

    def test_unknown_accelerator_is_an_error(self, monkeypatch):
        import jax

        class Unknown:
            platform = "tpu"
            device_kind = "TPU v9 hypothetical"

        monkeypatch.setattr(jax, "devices", lambda *a: [Unknown()])
        with pytest.raises(RuntimeError, match="no peak numbers"):
            bench.chip_peaks()
