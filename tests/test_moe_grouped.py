"""The experts' grouped products (`parallel/moe.py`: `_grouped_pallas`,
`_grouped_plan`, `grouped_tiles`, `_experts`): the chip's kernel runs here
interpreted, at tiny tiles, against a dense product a group.

Limits, each with its reason:
- float32: 1e-5 of the largest value: float32 against float32 at the
  highest precision, only the order of the sums differs;
- bfloat16 against the float32 result from the same bfloat16 operands: ONE
  last place (the kernel rounds once, after the activation), and `SUMS`
  1e-6 for float32 sums of another order where terms cancel;
- bfloat16 against `lax.ragged_dot`, which rounds both sums BEFORE the
  activation: four last places (silu carries a sum's rounding 1 + |a|
  times, |a| under 3 here)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mmlspark_tpu.observability.metrics import get_registry
from mmlspark_tpu.parallel import moe
from mmlspark_tpu.parallel.moe import (dropless_buffer_rows, grouped_tiles,
                                       moe_ffn_dropless)

K = 128
SUMS = 1e-6

CASES = {
    # rows, picks an expert, tile of rows, columns, tile of columns
    "an_empty_group": (64, [10, 0, 30, 5], 16, 256, 128),
    "a_group_across_tiles": (96, [3, 70, 0, 9], 16, 256, 128),
    "groups_inside_one_tile": (64, [3, 4, 5, 2], 16, 256, 128),
    "every_pick_on_one_expert": (64, [0, 0, 64, 0], 32, 256, 128),
    "fewer_picks_than_a_tile": (64, [2, 1, 0, 3], 32, 256, 128),
    "no_pick_at_all": (32, [0, 0, 0, 0], 16, 256, 128),
    # 384 = 3 x 128 in column tiles of 256: the last one hangs over
    "an_odd_multiple_of_128": (64, [20, 7, 30, 0], 16, 384, 256),
    # the whole-buffer branch: every row is a pick, the last tile ragged
    "the_whole_buffer": (72, [20, 12, 30, 10], 16, 256, 256),
}


def _operands(rows, picks, n, dtype, seed=0):
    held = len(picks)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        xs=jax.random.normal(keys[0], (rows, K), dtype),
        gate=(jax.random.normal(keys[1], (held, K, n)) * K ** -0.5
              ).astype(dtype),
        up=(jax.random.normal(keys[2], (held, K, n)) * K ** -0.5
            ).astype(dtype),
        down=(jax.random.normal(keys[3], (held, n, K)) * n ** -0.5
              ).astype(dtype),
        scale=jax.random.uniform(keys[4], (rows, 1), jnp.float32),
        picks=jnp.asarray(picks, jnp.int32))


def _dense(xs, weights, picks):
    """A dense float32 product a group at the highest precision."""
    out = np.zeros((xs.shape[0], weights.shape[2]), np.float32)
    start = 0
    for e, count in enumerate(np.asarray(picks)):
        rows = slice(start, start + int(count))
        out[rows] = jnp.dot(xs[rows].astype(jnp.float32),
                            weights[e].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        start += int(count)
    return out


def _last_place(a: np.ndarray, bits: int = 8) -> np.ndarray:
    _m, e = np.frexp(np.abs(a))
    return np.ldexp(1.0, e - bits)


class TestKernel:
    @pytest.mark.parametrize("stage", ["gated", "down", "down_weighed"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", list(CASES))
    def test_equals_a_dense_product_a_group(self, case, dtype, stage):
        rows, picks, tm, n, tn = CASES[case]
        dtype = jnp.dtype(dtype)
        o = _operands(rows, picks, n, dtype)
        plan = moe._grouped_plan(o["picks"], rows, tm)
        if stage == "gated":
            got = moe._grouped_pallas(
                o["xs"], (o["gate"], o["up"]), plan, tm=tm, tn=tn,
                name="ragged-dot-gated", interpret=True)
            a, b = (_dense(o["xs"], o[m], picks) for m in ("gate", "up"))
            want = np.asarray(jax.nn.silu(a) * b)
            near = [lax.ragged_dot(o["xs"], o[m], o["picks"],
                                   preferred_element_type=dtype)
                    for m in ("gate", "up")]
            near = (jax.nn.silu(near[0].astype(jnp.float32))
                    * near[1]).astype(dtype)
        else:
            # the second product's shape: (rows, n) by (held, n, K)
            act = jax.random.normal(jax.random.PRNGKey(5), (rows, n), dtype)
            scale = o["scale"] if stage == "down_weighed" else None
            got = moe._grouped_pallas(
                act, (o["down"],), plan, tm=tm, tn=128,
                name="ragged-dot-down", scale=scale, interpret=True)
            want = _dense(act, o["down"], picks)
            near = lax.ragged_dot(act, o["down"], o["picks"],
                                  preferred_element_type=dtype)
            if scale is not None:       # weighed in float32, rounded once
                want = want * np.asarray(scale)
                near = (near.astype(jnp.float32) * scale).astype(dtype)
        n_here = sum(picks)
        assert got.shape == want.shape and got.dtype == dtype
        got, near = (np.asarray(a, np.float32)[:n_here] for a in (got, near))
        want = want[:n_here]
        if dtype == jnp.float32:
            assert np.abs(got - want).max(initial=0.0) <= 1e-5 * max(
                1.0, np.abs(want).max(initial=0.0))
        else:
            assert (np.abs(got - want) <= _last_place(want) + SUMS).all()
            assert (np.abs(got - near) <= 4 * _last_place(want) + SUMS).all()

    @pytest.mark.parametrize("case", list(CASES))
    def test_the_plan_visits_each_expert_s_tiles_once_in_order(self, case):
        rows, picks, tm, _n, _tn = CASES[case]
        group, tile, offsets, visits = (np.asarray(a) for a in (
            moe._grouped_plan(jnp.asarray(picks, jnp.int32), rows, tm)))
        want, start = [], 0
        for e, count in enumerate(picks):
            if count:
                want += [(e, t) for t in range(
                    start // tm, -(-(start + count) // tm))]
            start += count
        assert list(zip(group[:visits], tile[:visits])) == want
        assert list(offsets) == [0, *np.cumsum(picks)]
        assert len(group) == -(-rows // tm) + len(picks) - 1 >= visits
        # no tile wholly past the picks is visited, and none out of range
        assert (tile[:visits] * tm < max(sum(picks), 1)).all()
        assert (tile >= 0).all() and (tile < -(-rows // tm)).all()


REAL = {
    # the five buffers of the two decoder cells: tokens, top_k, held,
    # routed, expert width
    "moonlight_long": (32768, 6, 16, 64, 1408),
    "moonlight_short": (4096, 6, 16, 64, 1408),
    "lfm2_long": (32768, 4, 8, 32, 1792),
    "lfm2_short": (2048, 4, 8, 32, 1792),
    "lfm2_one_row": (1024, 4, 8, 32, 1792),
}


class TestTileRule:
    @pytest.mark.parametrize("shape", list(REAL))
    def test_the_choices_at_the_cells_shapes_fit_the_kernel_s_vmem(
            self, shape):
        tokens, top_k, held, routed, w = REAL[shape]
        rows = dropless_buffer_rows(tokens, top_k, held, routed)
        assert rows == {"moonlight_long": 74240, "moonlight_short": 9728,
                        "lfm2_long": 49664, "lfm2_short": 3584,
                        "lfm2_one_row": 2048}[shape]
        # what the kernel alone measured best, or within 2% of it
        assert grouped_tiles(rows, 2048, w, 2, 2) == (
            256, {1408: 512, 1792: 256}[w])
        assert grouped_tiles(rows, w, 2048, 2, 1) == (256, 1024)
        # and the whole-buffer branch's rows
        for rows in (rows, tokens * top_k):
            for k, n, operands in ((2048, w, 2), (w, 2048, 1)):
                tm, tn = grouped_tiles(rows, k, n, 2, operands)
                assert tm % 16 == 0 and tn % 128 == 0 and tn <= n
                assert moe._grouped_bytes(tm, k, tn, 2, operands) <= (
                    moe._GROUPED_VMEM) <= 16 * 2 ** 20

    def test_a_buffer_smaller_than_a_tile_takes_its_own_rows(self):
        assert grouped_tiles(40, 128, 256, 2, 2) == (48, 256)
        assert grouped_tiles(40, 256, 128, 2, 1) == (48, 128)

    @pytest.mark.parametrize("k,n,itemsize", [
        (2048, 1408, 4),            # float32 operands
        (2048, 1400, 2),            # a width that is no multiple of 128
        (100, 1408, 2)])
    def test_what_the_kernel_does_not_take_keeps_ragged_dot(self, k, n,
                                                            itemsize):
        assert grouped_tiles(4096, k, n, itemsize, 2) is None


def _counted(**labels):
    counter = get_registry().counter(
        "mmlspark_tpu_moe_grouped_calls_total", labels=("kernel", "stage",
                                                        "tile"))
    return counter.labels(**labels).value


class TestLayer:
    def _args(self, tokens=96, dtype=jnp.bfloat16, seed=3, d=128, w=256,
              n=8):
        keys = jax.random.split(jax.random.PRNGKey(seed), 6)
        return (jax.random.normal(keys[0], (tokens, d)),
                jax.random.normal(keys[1], (d, n)) * d ** -0.5,
                0.1 * jax.random.normal(keys[2], (n,)),
                jax.random.normal(keys[3], (n, d, w)) * d ** -0.5,
                jax.random.normal(keys[4], (n, d, w)) * d ** -0.5,
                jax.random.normal(keys[5], (n, w, d)) * w ** -0.5)

    def _as_on_the_chip(self, monkeypatch):
        """Both products and the combine through their kernels,
        interpreted, and no zeroing pass between them."""
        monkeypatch.setattr(moe, "_experts", functools.partial(
            moe._experts, tiles=((16, 128), (16, 128)), interpret=True))
        monkeypatch.setattr(moe, "_combine", functools.partial(
            moe._combine_pallas, interpret=True))
        monkeypatch.setattr(moe, "_combine_in_kernel", lambda d: True)

    @pytest.mark.parametrize("tokens,held,bias", [
        (96, (0, 8), 0.0),          # every expert held: no switch
        (300, (2, 2), 0.0),         # the small buffer's branch
        (256, (0, 2), 9.0)])        # the whole-buffer branch: 512 of 768
    def test_the_kernel_s_path_equals_three_ragged_dot(self, monkeypatch,
                                                       tokens, held, bias):
        """`moe_ffn_dropless` as the chip runs it against the CPU's path
        (three `lax.ragged_dot` over the parameters as they lie, the
        weighing and XLA's combine), bfloat16: within a rounding of the
        layer's output (four last places of the largest value a token adds
        up)."""
        x, router, b, gate, up, down = self._args(tokens)
        lo, hi = held[0], held[0] + held[1]
        b = b.at[lo:hi].add(bias)
        args = (x, router, b, gate[lo:hi], up[lo:hi], down[lo:hi])
        kw = dict(n_routed_experts=8, experts_held=held, top_k=3,
                  scaling=2.446, dtype=jnp.bfloat16)
        want, picks_want = moe_ffn_dropless(*args, **kw)
        self._as_on_the_chip(monkeypatch)
        got, picks = moe_ffn_dropless(*args, **kw)
        assert np.array_equal(picks, picks_want)
        small = dropless_buffer_rows(tokens, 3, held[1], 8)
        assert {96: small == 288, 300: int(picks.sum()) < small < 900,
                256: small <= int(picks.sum()) < 768}[tokens]
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.abs(want).max() > 0.1
        assert (np.abs(got - want) <= 4 * _last_place(
            np.full_like(want, np.abs(want).max()))).all()

    @pytest.mark.parametrize("path", ["xla", "kernels"])
    def test_nan_past_the_picks_reaches_no_token(self, monkeypatch, path):
        """Rows of the buffer past the picks that are here may hold
        anything: with NaN planted there behind the products, the layer's
        output is the sound one bit for bit, through XLA's combine (the
        zeroing pass) and through the kernel's (which reads the picks' rows
        only)."""
        args = self._args(tokens=300)
        args = (*args[:3], *(m[2:4] for m in args[3:]))
        kw = dict(n_routed_experts=8, experts_held=(2, 2), top_k=3,
                  scaling=2.446, dtype=jnp.bfloat16)
        if path == "kernels":
            self._as_on_the_chip(monkeypatch)
        want, picks = moe_ffn_dropless(*args, **kw)
        sound = moe._experts

        def planted(xs, gate, up, down, picks, weight_of_row, **kw):
            out = sound(xs, gate, up, down, picks, weight_of_row, **kw)
            past = jnp.arange(xs.shape[0]) >= picks.sum()
            return jnp.where(past[:, None], jnp.nan, out)

        monkeypatch.setattr(moe, "_experts", planted)
        got, _picks = moe_ffn_dropless(*args, **kw)
        assert 0 < int(picks.sum()) < dropless_buffer_rows(300, 3, 2, 8)
        assert np.isfinite(np.asarray(got, np.float32)).all()
        assert np.array_equal(got, want)

    def test_the_counter_says_what_ran_each_stage(self):
        _x, _router, _b, gate, up, down = self._args(tokens=32)
        xs = jnp.zeros((48, 128), jnp.bfloat16)
        weights = [m[:2].astype(jnp.bfloat16) for m in (gate, up, down)]
        rest = (jnp.asarray([20, 9], jnp.int32), jnp.ones((48,), jnp.float32))
        before = {(k, s, t): _counted(kernel=k, stage=s, tile=t)
                  for k, s, t in (("ragged_dot", "gated", "none"),
                                  ("ragged_dot", "down", "none"),
                                  ("pallas", "gated", "16x128"),
                                  ("pallas", "down", "32x128"))}
        moe._experts(xs, *weights, *rest)            # the CPU: no kernel
        moe._experts(xs, *weights, *rest, tiles=((16, 128), (32, 128)),
                     interpret=True)
        moe._experts(xs, *weights, *rest, tiles=((16, 128), None),
                     interpret=True)
        after = {key: _counted(kernel=key[0], stage=key[1], tile=key[2])
                 for key in before}
        assert {key: after[key] - before[key] for key in before} == {
            ("ragged_dot", "gated", "none"): 1,
            ("ragged_dot", "down", "none"): 2,
            ("pallas", "gated", "16x128"): 2,
            ("pallas", "down", "32x128"): 1}

    def test_no_concatenated_weights_on_any_path(self):
        """Neither path makes (held, d, 2w) or (rows, 2w): read from the
        jaxpr of the layer on the CPU and through the kernel."""
        args = self._args()
        kw = dict(n_routed_experts=8, experts_held=(0, 8), top_k=3,
                  dtype=jnp.bfloat16)
        text = str(jax.make_jaxpr(
            lambda *a: moe_ffn_dropless(*a, **kw))(*args))
        assert "8,128,512" not in text and ",512]" not in text
        assert text.count("ragged_dot") >= 3


# --------------------------------------------------------------------- #
# a second activation and a second scoring (the family that states them) #
# --------------------------------------------------------------------- #

def _activations(**labels):
    return get_registry().counter(
        "mmlspark_tpu_moe_activation_calls_total",
        labels=("activation",)).labels(**labels).value


class TestReluGate:
    """relu(a) * b in the first product's epilogue and in the `ragged_dot`
    path alike: a static argument of the same call, not a second kernel."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", ["an_empty_group",
                                      "a_group_across_tiles",
                                      "an_odd_multiple_of_128"])
    def test_the_kernel_equals_a_dense_product_a_group(self, case, dtype):
        rows, picks, tm, n, tn = CASES[case]
        dtype = jnp.dtype(dtype)
        o = _operands(rows, picks, n, dtype)
        plan = moe._grouped_plan(o["picks"], rows, tm)
        got = moe._grouped_pallas(
            o["xs"], (o["gate"], o["up"]), plan, tm=tm, tn=tn,
            name="ragged-dot-gated", activation="relu", interpret=True)
        a, b = (_dense(o["xs"], o[m], picks) for m in ("gate", "up"))
        want = (np.maximum(a, 0.0) * b)[:sum(picks)]
        got = np.asarray(got, np.float32)[:sum(picks)]
        if dtype == jnp.float32:
            assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
        else:
            assert (np.abs(got - want) <= _last_place(want) + SUMS).all()
        # half the hidden values are exactly zero, which silu's never are
        assert 0.3 < (got == 0.0).mean() < 0.7
        silu = np.asarray(moe._grouped_pallas(
            o["xs"], (o["gate"], o["up"]), plan, tm=tm, tn=tn,
            name="ragged-dot-gated", interpret=True), np.float32)
        assert (silu[:sum(picks)] == 0.0).mean() < 0.01

    @pytest.mark.parametrize("path", ["ragged_dot", "kernels"])
    def test_the_layer_with_relu_is_the_equations(self, monkeypatch, path):
        """`moe_ffn_dropless(activation="relu")` against every expert
        computed densely for every token and weighed by its gate, float32
        on the CPU's path and bfloat16 through the interpreted kernels."""
        layer = TestLayer()
        dtype = jnp.float32 if path == "ragged_dot" else jnp.bfloat16
        x, router, b, gate, up, down = layer._args(tokens=96)
        kw = dict(n_routed_experts=8, experts_held=(0, 8), top_k=3,
                  dtype=dtype, activation="relu")
        if path == "kernels":
            layer._as_on_the_chip(monkeypatch)
        got, picks = moe_ffn_dropless(x, router, b, gate, up, down, **kw)
        picked, weights = moe.route_top_k(x, router, b, 3)
        xs, mats = x.astype(dtype), [m.astype(dtype) for m in (gate, up,
                                                               down)]
        want = np.zeros(x.shape, np.float32)
        for e in range(8):
            g = np.where(np.asarray(picked) == e, np.asarray(weights),
                         0.0).sum(-1, keepdims=True)
            hidden = np.maximum(np.asarray(xs @ mats[0][e], np.float32),
                                0.0) * np.asarray(xs @ mats[1][e],
                                                  np.float32)
            want += g * np.asarray(
                jnp.asarray(hidden, dtype) @ mats[2][e], np.float32)
        assert int(picks.sum()) == 96 * 3
        tol = 1e-4 if path == "ragged_dot" else 0.05
        assert np.abs(np.asarray(got, np.float32) - want).max() \
            < tol * np.abs(want).max()
        # silu in its place is another layer
        other, _p = moe_ffn_dropless(x, router, b, gate, up, down,
                                     **dict(kw, activation="silu"))
        assert np.abs(np.asarray(other, np.float32) - want).max() \
            > 0.05 * np.abs(want).max()

    def test_the_activation_is_counted_and_an_unknown_one_refused(self):
        xs = jnp.zeros((48, 128), jnp.bfloat16)
        w = jnp.zeros((2, 128, 256), jnp.bfloat16)
        rest = (jnp.asarray([20, 9], jnp.int32), jnp.ones((48,), jnp.float32))
        before = {a: _activations(activation=a) for a in ("silu", "relu")}
        grouped = {(k, s): _counted(kernel=k, stage=s, tile="none")
                   for k, s in (("ragged_dot", "gated"),)}
        moe._experts(xs, w, w, w.reshape(2, 256, 128), *rest)
        moe._experts(xs, w, w, w.reshape(2, 256, 128), *rest,
                     activation="relu")
        assert {a: _activations(activation=a) - before[a]
                for a in before} == {"silu": 1, "relu": 1}
        # the grouped calls' counter keeps its three labels
        assert _counted(kernel="ragged_dot", stage="gated",
                        tile="none") == grouped[("ragged_dot", "gated")] + 2
        with pytest.raises(ValueError, match="unknown gate activation"):
            moe._experts(xs, w, w, w.reshape(2, 256, 128), *rest,
                         activation="gelu")

    @pytest.mark.parametrize("tokens,rows", [(32768, 74240), (4096, 9728)])
    def test_the_rule_at_experts_of_768_on_a_hidden_width_of_2560(
            self, tokens, rows):
        """`smallthinker_21b_a3b.score_mixed_context`'s two buffers (6
        picks of 64, 16 held): 768 columns in two blocks of 384 (two of
        512 would compute a third more), 2560 in one; both inside the
        kernel's VMEM, the whole-buffer branch's rows too."""
        assert dropless_buffer_rows(tokens, 6, 16, 64) == rows
        for rows in (rows, tokens * 6):
            assert grouped_tiles(rows, 2560, 768, 2, 2) == (256, 384)
            assert grouped_tiles(rows, 768, 2560, 2, 1) == (256, 2560)
        assert moe._grouped_bytes(256, 2560, 384, 2, 2) <= moe._GROUPED_VMEM
        assert moe._grouped_bytes(256, 2560, 512, 2, 2) <= moe._GROUPED_VMEM
        assert moe._grouped_bytes(256, 768, 2560, 2, 1) <= moe._GROUPED_VMEM


class TestSoftmaxRouting:
    def _inputs(self, seed=0, tokens=50, d=32, n=16):
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (jax.random.normal(keys[0], (tokens, d)),
                jax.random.normal(keys[1], (d, n)),
                jax.random.normal(keys[2], (n,)))

    def test_softmax_over_the_picks_is_softmax_over_all_renormalised(self):
        x, router, _b = self._inputs()
        picked, weights = moe.route_top_k(x, router, None, 4,
                                          scoring="softmax")
        logits = x @ router
        assert np.array_equal(picked, lax.top_k(logits, 4)[1])
        over_all = jnp.take_along_axis(jax.nn.softmax(logits, -1), picked, 1)
        np.testing.assert_allclose(
            weights, over_all / over_all.sum(-1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
        # the softmax over all experts left as it is has no model here
        with pytest.raises(ValueError, match="normalise=False"):
            moe.route_top_k(x, router, None, 4, normalise=False,
                            scoring="softmax")
        # the scaling factor multiplies the weights
        _p, scaled = moe.route_top_k(x, router, None, 4, scaling=2.5,
                                     scoring="softmax")
        np.testing.assert_allclose(scaled, 2.5 * weights, rtol=1e-6)

    def test_the_bias_selects_and_does_not_weigh(self):
        x, router, _b = self._inputs(seed=1)
        bias = jnp.zeros(16).at[3].set(50.0).at[9].set(-50.0)
        picked, weights = moe.route_top_k(x, router, bias, 4,
                                          scoring="softmax")
        assert (np.asarray(picked) == 3).any(axis=1).all()
        assert not (np.asarray(picked) == 9).any()
        chosen = jnp.take_along_axis(x @ router, picked, 1)     # no bias
        np.testing.assert_allclose(weights, jax.nn.softmax(chosen, -1),
                                   rtol=1e-5)

    def test_sigmoid_is_what_it_was(self):
        """The default scoring, written out as the parent had it."""
        x, router, bias = self._inputs(seed=2)
        picked, weights = moe.route_top_k(x, router, bias, 4, epsilon=1e-6)
        scores = jax.nn.sigmoid(x @ router)
        assert np.array_equal(picked, lax.top_k(scores + bias, 4)[1])
        chosen = jnp.take_along_axis(scores, picked, 1)
        np.testing.assert_allclose(
            weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
            rtol=1e-6)
        named = moe.route_top_k(x, router, bias, 4, epsilon=1e-6,
                                scoring="sigmoid")
        assert np.array_equal(named[1], weights)
        assert not np.allclose(moe.route_top_k(
            x, router, bias, 4, scoring="softmax")[1], weights, atol=1e-3)

    def test_picks_made_earlier_take_the_routers_place(self):
        """`routed=`: the layer computes with the picks it is handed (a
        router that read another input) and reads no router of its own."""
        layer = TestLayer()
        x, router, b, gate, up, down = layer._args(tokens=64,
                                                   dtype=jnp.float32)
        other = jax.random.normal(jax.random.PRNGKey(11), x.shape)
        kw = dict(n_routed_experts=8, experts_held=(2, 4), top_k=3)
        routed = moe.route_top_k(other, router, None, 3, scoring="softmax")
        got, picks = moe_ffn_dropless(x, None, None, gate[2:6], up[2:6],
                                      down[2:6], routed=routed, **kw)
        held = ((routed[0] >= 2) & (routed[0] < 6))
        assert int(picks.sum()) == int(held.sum())
        # the same picks from the layer's own router on that other input
        own, own_picks = moe_ffn_dropless(
            other, router, jnp.zeros(8), gate[2:6], up[2:6], down[2:6],
            scoring="softmax", **kw)
        assert np.array_equal(own_picks, picks)
        assert not np.allclose(own, got, atol=1e-3)    # other experts' input
