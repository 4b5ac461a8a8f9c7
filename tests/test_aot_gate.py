"""tools/aot_gate.py, as far as it can be checked off the chip."""

import sys

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])


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

        def recording_pallas_call(kernel, *, out_shape, in_specs=None,
                                  out_specs=None, grid_spec=None, **_kw):
            prefetched = 0
            if grid_spec is not None:
                # the specs inside a grid spec; its scalar-prefetch
                # operands come first and have no block
                in_specs, out_specs = grid_spec.in_specs, grid_spec.out_specs
                prefetched = grid_spec.num_scalar_prefetch

            def run(*args):
                args = args[prefetched:]
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
