"""`recommendation/topk.py` `top_k_rows`: the Pallas selection, interpreted
on the CPU, against `jax.lax.top_k` bit for bit in values AND columns
(ties, `-inf` rows, widths off 8 and 128, rows off the tile), the block
program of `SARModel.recommend_for_all_users` through it at ONE shape a
pass, the shape rule, the counter, and what an import pays."""

import functools
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.observability.metrics import get_registry
from mmlspark_tpu.recommendation import SARModel, sar, topk
from mmlspark_tpu.recommendation.topk import top_k_rows

KINDS = ("normal", "ties", "masked", "one_class")


@functools.lru_cache(maxsize=1)
def _normal(rows: int, width: int):
    return np.random.default_rng(1000 * rows + width).standard_normal(
        (rows, width), np.float32)


def scores(kind: str, rows: int, width: int):
    rng = np.random.default_rng(rows + width)
    x = _normal(rows, width)
    if kind == "ties":
        # eight levels: every row full of equal values
        x = rng.integers(0, 8, (rows, width)).astype(np.float32)
    elif kind == "masked":
        # the seen mask's case: five finite entries a row, fewer than most
        # k, and every seventh row none at all
        keep = np.zeros((rows, width), bool)
        five = (rng.integers(0, width // 5, (rows, 1))
                + np.arange(5) * (width // 5))
        np.put_along_axis(keep, five, True, 1)
        x = np.where(keep, x, -np.inf).astype(np.float32)
        x[::7] = -np.inf
    elif kind == "one_class":
        # a row's 20 best in ONE class (consecutive registers of columns,
        # one sublane; a narrow tile's runs are shorter, and they spread):
        # its list runs out, and the tile is scanned again and again
        x = x.copy()
        x[:, 5:5 + 8 * 20:8] += 100.0
    return jnp.asarray(x)


def equal_bit_for_bit(x, k):
    values, columns = top_k_rows(x, k, interpret=True)
    want_values, want_columns = jax.lax.top_k(x, k)
    assert values.dtype == jnp.float32 and columns.dtype == jnp.int32
    assert values.shape == columns.shape == (x.shape[0], k)
    np.testing.assert_array_equal(np.asarray(columns),
                                  np.asarray(want_columns))
    # as bits: -inf is -inf, and no value is rounded on the way
    np.testing.assert_array_equal(np.asarray(values).view(np.uint32),
                                  np.asarray(want_values).view(np.uint32))


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("width", [256, 10677, 12000])
@pytest.mark.parametrize("rows", [128, 246])
@pytest.mark.parametrize("kind", KINDS)
def test_equals_lax_top_k(kind, rows, width, k):
    equal_bit_for_bit(scores(kind, rows, width), k)


@pytest.mark.parametrize("kind,width,k", [
    (kind, width, 10) for width in (256, 10677, 12000) for kind in KINDS
] + [("normal", 10677, 1), ("normal", 10677, 100)])
def test_equals_lax_top_k_at_a_block_of_4096(kind, width, k):
    equal_bit_for_bit(scores(kind, 4096, width), k)


@pytest.mark.parametrize("kind,rows,width,k", [
    # widths off 8 and off 128, rows off the tile and under it, k the
    # whole width and the most the kernel gives
    ("normal", 22, 200, 100), ("ties", 24, 100, 100),
    ("masked", 22, 1000, 10), ("one_class", 24, 1000, 7),
    ("normal", 40, 1000, 128), ("normal", 150, 300, 10),
    ("masked", 300, 200, 10), ("ties", 8, 640, 10), ("normal", 1, 384, 5)])
def test_equals_lax_top_k_off_the_tile(kind, rows, width, k):
    equal_bit_for_bit(scores(kind, rows, width), k)


def test_a_row_scanned_again_keeps_what_it_gave_before():
    """Ten best of which nine share a class, equal values among them: the
    rounds stop at the class's fourth entry, and the second scan starts
    after (value, column) of that entry, not after the value alone."""
    x = np.zeros((8, 1280), np.float32)
    x[:, 3:3 + 8 * 10:8] = 5.0      # ten equal entries of one class
    x[:, 200] = 9.0
    equal_bit_for_bit(jnp.asarray(x), 10)


def _counted(**labels):
    return get_registry().counter(
        "mmlspark_tpu_sar_topk_calls_total",
        labels=("kernel", "k")).labels(**labels).value


class TestShapeRule:
    def test_the_cpu_takes_lax_top_k(self):
        assert not topk.kernel_takes(4096, 10677, 10, jnp.float32)
        before = _counted(kernel="lax", k="10")
        x = scores("normal", 16, 300)
        values, columns = top_k_rows(x, 10)
        want = jax.lax.top_k(x, 10)
        np.testing.assert_array_equal(np.asarray(columns),
                                      np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(values),
                                      np.asarray(want[0]))
        assert _counted(kernel="lax", k="10") == before + 1

    def test_a_traced_kernel_call_is_counted_by_kernel_and_k(self):
        before = _counted(kernel="pallas", k="3")
        top_k_rows(scores("normal", 8, 256), 3, interpret=True)
        assert _counted(kernel="pallas", k="3") == before + 1

    @pytest.mark.parametrize("rows,width,k,dtype,ours", [
        (4096, 10677, 10, jnp.float32, True),     # the cell's block
        (246, 10677, 10, jnp.float32, True),      # a short catalogue's one
        (4096, 10677, 128, jnp.float32, True),
        (4096, 256, 10, jnp.float32, True),
        (4096, 12288, 10, jnp.float32, True),
        (4096, 12289, 10, jnp.float32, False),    # wider than VMEM takes
        (topk.TILE - 1, 10677, 10, jnp.float32, False),   # a serving rung
        (topk.TILE, 10677, 10, jnp.float32, True),
        (4096, 64, 65, jnp.float32, False),       # k over the width
        (4096, 10677, 129, jnp.float32, False),   # over 128 results a row
        (4096, 10677, 10, jnp.bfloat16, False)])
    def test_on_a_tpu_the_shape_decides(self, monkeypatch, rows, width, k,
                                        dtype, ours):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert topk.kernel_takes(rows, width, k, dtype) is ours


def test_importing_the_package_or_the_lax_side_imports_no_pallas():
    """`import jax.experimental.pallas` is a second of a start: paid where
    the kernel is first traced, not at `import mmlspark_tpu.recommendation`
    nor by a selection the rule gives to `lax.top_k`; and the serving
    package comes with `serve_recommender`, not with the scorer."""
    from conftest import subprocess_env

    code = (
        "import sys, numpy as np\n"
        "from mmlspark_tpu.recommendation import SARModel, SARTopKScorer\n"
        "from mmlspark_tpu.recommendation.topk import top_k_rows\n"
        "top_k_rows(np.ones((4, 16), np.float32), 2)\n"
        "print([m for m in ('jax.experimental.pallas',"
        " 'mmlspark_tpu.io_http') if m in sys.modules])\n"
        "top_k_rows(np.ones((4, 16), np.float32), 2, interpret=True)\n"
        "from mmlspark_tpu.recommendation import serve_recommender\n"
        "print([m for m in ('jax.experimental.pallas',"
        " 'mmlspark_tpu.io_http') if m in sys.modules])\n")
    proc = subprocess.run([sys.executable, "-c", code], env=subprocess_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-2:] == [
        "[]", "['jax.experimental.pallas', 'mmlspark_tpu.io_http']"]


class TestBlockProgram:
    def _model(self, users=70, items=300, seed=4):
        rng = np.random.default_rng(seed)
        model = SARModel()
        model.user_affinity = (rng.random((users, items))
                               * (rng.random((users, items)) < 0.1)
                               ).astype(np.float32)
        similarity = rng.random((items, items)).astype(np.float32)
        model.item_similarity = (similarity + similarity.T) / 2
        model.seen = model.user_affinity > 0
        # user 0 has seen all but four items: fewer than k left
        model.seen[0, 4:] = True
        return model

    @pytest.mark.parametrize("remove_seen", [True, False])
    def test_recommend_for_all_users_through_the_kernel(self, monkeypatch,
                                                        remove_seen):
        """Blocks of 32, 32 and the last 6 users (cut as the last 32)
        through `_block_topk_unseen` / `_block_topk` with the Pallas
        selection, against the same programs with `lax.top_k`."""
        model = self._model()
        want = model.recommend_for_all_users(10, remove_seen=remove_seen,
                                             user_block=32)
        for program in (sar._block_topk, sar._block_topk_unseen):
            program.clear_cache()
        monkeypatch.setattr(sar, "top_k_rows", functools.partial(
            top_k_rows, interpret=True))
        before = _counted(kernel="pallas", k="10")
        try:
            got = model.recommend_for_all_users(
                10, remove_seen=remove_seen, user_block=32)
        finally:
            for program in (sar._block_topk, sar._block_topk_unseen):
                program.clear_cache()
        # ONE shape traced: the last block is whole too
        assert _counted(kernel="pallas", k="10") == before + 1
        np.testing.assert_array_equal(got["recommendations"],
                                      want["recommendations"])
        # XLA:CPU sums a product it writes transposed in another order:
        # the last bit of a score is the product's, not the selection's
        np.testing.assert_allclose(got["ratings"], want["ratings"],
                                   rtol=1e-6)
        if remove_seen:
            assert (got["recommendations"][0, 4:] == -1).all()
