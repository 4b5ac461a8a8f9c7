"""Distributed-determinism tests (SURVEY.md §7 "distributed determinism").

The hazard: float psum is not associative; the reduction order XLA picks can
depend on topology/device order, and a near-tied split-gain argmax can flip
on rounding jitter — breaking LightGBM's replicated-model-by-construction
invariant (LightGBMClassifier.scala:82-85). These tests (a) demonstrate the
hazard in plain numpy, (b) pin the guarantees of the deterministic
reductions in `parallel.collectives`, and (c) prove the GBDT engine's
`deterministic` flag yields byte-identical models across device
permutations of the mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.parallel.collectives import (
    psum_exact_fixedpoint,
    psum_kahan,
    psum_ordered,
)

AXIS = "d"

# Adversarial shard partials: catastrophic cancellation makes the fp32 sum
# depend on the order the shards are folded in.
CANCELLING = np.array(
    [3.0e7, 1.0, -3.0e7, 1.0, 1.0e7, 1.0, -1.0e7, 1.0], np.float32
)


def _mesh(perm=None):
    devs = jax.devices()[:8]
    if perm is not None:
        devs = [devs[i] for i in perm]
    return Mesh(np.asarray(devs), (AXIS,))


def _run(fn, shard_values, mesh):
    """shard_values: (S,) — shard i contributes shard_values[i]. Returns the
    per-device reduction results (S,)."""
    x = jnp.asarray(shard_values, jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(AXIS)))
    out = jax.jit(
        shard_map(
            lambda v: fn(v, AXIS), mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)
        )
    )(xs)
    return np.asarray(out)


def test_numpy_demonstrates_order_dependence():
    """The hazard is real: fp32 sums of the same shard partials differ by
    summation order, enough to flip a near-tied split-gain comparison."""
    a = np.float32(0.0)
    for v in CANCELLING:                      # left-to-right
        a = np.float32(a + v)
    b = np.float32(0.0)
    for v in CANCELLING[::-1]:                # reversed
        b = np.float32(b + v)
    assert a != b, "expected fp32 order dependence in the adversarial sums"
    # a near-tied competitor gain sitting between the two orderings' results
    # would win against one ordering and lose against the other
    competitor = np.float32((a + b) / 2)
    assert (a > competitor) != (b > competitor)


class TestOrderedAndKahan:
    def test_psum_ordered_identical_on_all_devices(self):
        out = _run(psum_ordered, CANCELLING, _mesh())
        assert np.all(out == out[0])

    def test_psum_ordered_matches_fixed_left_to_right_fold(self):
        out = _run(psum_ordered, CANCELLING, _mesh())
        acc = np.float32(0.0)
        for v in CANCELLING:
            acc = np.float32(acc + v)
        assert out[0] == acc

    def test_psum_ordered_invariant_under_device_permutation(self):
        """The fold order is the mesh's LOGICAL axis order, so permuting the
        physical devices behind it cannot change the bits."""
        base = _run(psum_ordered, CANCELLING, _mesh())
        perm = _run(psum_ordered, CANCELLING, _mesh(perm=[3, 1, 7, 5, 0, 2, 6, 4]))
        assert np.array_equal(base, perm)

    def test_psum_kahan_recovers_exact_sum(self):
        """Neumaier compensation recovers the exact (float64) sum here,
        which plain left-to-right fp32 folding does not."""
        out = _run(psum_kahan, CANCELLING, _mesh())
        exact = float(np.sum(CANCELLING.astype(np.float64)))
        assert np.all(out == out[0])
        assert float(out[0]) == exact


class TestExactFixedpoint:
    def test_bit_exact_under_shard_assignment_permutation(self):
        """Integer-quantized partials make the reduction associative AND
        commutative: reassigning which shard holds which partial cannot
        change a single bit of the result."""
        mesh = _mesh()
        base = _run(psum_exact_fixedpoint, CANCELLING, mesh)
        rng = np.random.default_rng(0)
        for _ in range(3):
            shuffled = CANCELLING[rng.permutation(8)]
            out = _run(psum_exact_fixedpoint, shuffled, mesh)
            assert np.array_equal(base, out)

    def test_bit_exact_under_device_permutation(self):
        base = _run(psum_exact_fixedpoint, CANCELLING, _mesh())
        perm = _run(psum_exact_fixedpoint, CANCELLING,
                    _mesh(perm=[7, 6, 5, 4, 3, 2, 1, 0]))
        assert np.array_equal(base, perm)

    def test_accuracy_within_quantization_step(self):
        rng = np.random.default_rng(1)
        vals = rng.normal(size=8).astype(np.float32)
        out = _run(psum_exact_fixedpoint, vals, _mesh())
        exact = float(np.sum(vals.astype(np.float64)))
        # step = max_abs * n / 2^23; the sum of n roundings is within n/2 steps
        step = float(np.abs(vals).max()) * 8 / 2**23
        assert abs(float(out[0]) - exact) <= 4 * step
        assert np.all(out == out[0])

    def test_zero_input(self):
        out = _run(psum_exact_fixedpoint, np.zeros(8, np.float32), _mesh())
        assert np.all(out == 0.0)


class TestDeterministicGBDT:
    """End-to-end: `deterministic=True` makes the mesh-trained model
    byte-identical across device permutations of the mesh (LightGBM's
    `deterministic` param, the engine's hist_psum routing)."""

    @pytest.fixture(scope="class")
    def data(self):
        rng = np.random.default_rng(7)
        n, f = 512, 6
        x = rng.normal(size=(n, f))
        # weak signal + label noise: plenty of near-tied candidate splits
        y = (x[:, 0] * 0.3 + x[:, 1] * 0.29 + rng.normal(scale=1.0, size=n)
             > 0).astype(np.float64)
        return x, y

    def _fit_text(self, x, y, mesh, deterministic, **extra):
        from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

        opts = TrainOptions(
            objective="binary", num_iterations=8, num_leaves=15,
            min_data_in_leaf=5, deterministic=deterministic, **extra,
        )
        return Booster.train(x, y, opts, mesh=mesh).to_text()

    def test_byte_identical_across_device_permutations(self, data):
        x, y = data
        t1 = self._fit_text(x, y, _mesh(), deterministic=True)
        t2 = self._fit_text(x, y, _mesh(perm=[5, 2, 7, 0, 3, 6, 1, 4]),
                            deterministic=True)
        assert t1 == t2

    def test_voting_parallel_deterministic_across_permutations(self, data):
        """The voting path's selected-feature histogram merge rides the
        same hist_psum routing — deterministic mode must pin it too."""
        x, y = data
        texts = [
            self._fit_text(x, y, _mesh(perm=perm), deterministic=True,
                           tree_learner="voting_parallel", top_k=3)
            for perm in (None, [6, 3, 0, 5, 2, 7, 4, 1])
        ]
        assert texts[0] == texts[1]

    def test_deterministic_matches_plain_quality(self, data):
        """The quantized merge must not change model quality measurably."""
        x, y = data
        from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

        mesh = _mesh()
        accs = []
        for det in (False, True):
            opts = TrainOptions(
                objective="binary", num_iterations=8, num_leaves=15,
                min_data_in_leaf=5, deterministic=det,
            )
            b = Booster.train(x, y, opts, mesh=mesh)
            accs.append(float(((b.predict(x) > 0.5) == (y > 0.5)).mean()))
        assert abs(accs[0] - accs[1]) < 0.02
