"""Attention stack: dense vs chunked vs Pallas flash (interpret mode).

The reference has no sequence-model family (SURVEY.md §5.7); these gates
pin the beyond-reference single-device attention tiers against each other
— the same strategy as the ring/Ulysses tests (test_parallel.py), which
pin the cross-device tiers against `dense_attention` too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import kernel_equations

from mmlspark_tpu.nn import attention
from mmlspark_tpu.nn.attention import (
    SelfAttention,
    chunked_attention,
    dense_attention,
    eva_attention,
    eva_summaries,
    flash_attention,
    flash_tiles,
)
from mmlspark_tpu.nn.models import make_model
from mmlspark_tpu.observability.metrics import get_registry

SHAPES = [
    # (B, Tq, Tk, H, D, causal, chunk)
    (2, 64, 64, 4, 32, False, 16),
    (1, 50, 50, 2, 16, True, 16),     # ragged: seq not a chunk multiple
    (2, 128, 128, 4, 64, True, 128),  # single chunk == full dense
    (1, 7, 7, 1, 8, False, 16),       # seq smaller than the chunk
    (1, 24, 40, 2, 16, False, 16),    # cross-attention Tq != Tk
    (1, 40, 24, 2, 16, True, 16),     # causal with fully-masked... no row
]


def _qkv(b, tq, tk, h, d, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, tq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, tk, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, tk, h, d)), jnp.float32)
    return q, k, v


class TestParity:
    @pytest.mark.parametrize("b,tq,tk,h,d,causal,chunk", SHAPES)
    def test_chunked_matches_dense(self, b, tq, tk, h, d, causal, chunk):
        q, k, v = _qkv(b, tq, tk, h, d)
        ref = dense_attention(q, k, v, causal=causal)
        got = chunked_attention(q, k, v, causal=causal,
                                q_chunk=chunk, k_chunk=chunk)
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)

    @pytest.mark.parametrize("b,tq,tk,h,d,causal,chunk", SHAPES)
    def test_flash_matches_dense(self, b, tq, tk, h, d, causal, chunk):
        q, k, v = _qkv(b, tq, tk, h, d)
        ref = dense_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, block_q=chunk,
                              block_k=chunk, interpret=True)
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)

    def test_chunked_grad_matches_dense(self):
        q, k, v = _qkv(1, 48, 48, 2, 16, seed=3)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        gd = jax.grad(loss(lambda q, k, v: dense_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        gc = jax.grad(loss(lambda q, k, v: chunked_attention(
            q, k, v, causal=True, q_chunk=16, k_chunk=16)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gd, gc):
            np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_grad_matches_dense(self, causal):
        """flash is differentiable: Pallas forward + custom_vjp backward
        (the XLA flash recomputation) must match dense grads."""
        q, k, v = _qkv(1, 48, 48, 2, 16, seed=6)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        gd = jax.grad(loss(lambda q, k, v: dense_attention(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16, bwd_chunk=16,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gd, gf):
            np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)

    def test_flash_grad_ragged_and_masked_rows(self):
        """Backward with sequence padding (Tq/Tk not multiples of the
        blocks) and causally fully-masked rows: grads must match dense,
        and masked rows contribute zero."""
        q, k, v = _qkv(2, 13, 19, 2, 8, seed=7)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        gd = jax.grad(loss(lambda q, k, v: dense_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=8, block_k=8, bwd_chunk=8,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gd, gf):
            np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)

    def test_bf16_inputs_keep_dtype_and_agree(self):
        q, k, v = _qkv(2, 32, 32, 2, 16, seed=4)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        ref = dense_attention(q, k, v)
        for fn in (
            lambda: chunked_attention(qb, kb, vb, q_chunk=16, k_chunk=16),
            lambda: flash_attention(qb, kb, vb, block_q=16, block_k=16,
                                    interpret=True),
        ):
            got = fn()
            assert got.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                got.astype(jnp.float32), ref, atol=3e-2, rtol=3e-2)

    def test_fully_masked_rows_are_zero(self):
        # causal cross-attention where late keys start beyond every query
        # never happens in self-attention; force it with Tk > Tq and an
        # all-masked construction instead: query block sees no key when
        # causal and the key positions all exceed the query positions.
        q, k, v = _qkv(1, 4, 8, 1, 8, seed=5)
        # dense reference defines masked-row output as exactly zero
        ref = dense_attention(q, k, v, causal=True)
        ch = chunked_attention(q, k, v, causal=True, q_chunk=4, k_chunk=4)
        fl = flash_attention(q, k, v, causal=True, block_q=4, block_k=4,
                             interpret=True)
        np.testing.assert_allclose(ch, ref, atol=2e-5)
        np.testing.assert_allclose(fl, ref, atol=2e-5)


def _flash_calls(tile, causal):
    return get_registry().counter(
        "mmlspark_tpu_flash_calls_total", labels=("tile", "causal")).labels(
            tile=tile, causal=str(causal).lower()).value


class TestFlashTiles:
    """The tile the flash forward works on is chosen inside
    `flash_attention` from the lengths and the dtype."""

    TABLE = {
        # tokens: (tile for 2-byte inputs, tile for float32)
        1: (1, 1), 100: (100, 100), 128: (128, 128), 512: (512, 512),
        514: (640, 128), 640: (640, 128), 1100: (640, 384),
        1408: (768, 512), 4096: (1024, 512),
    }

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("t", sorted(TABLE))
    def test_rule_as_a_table(self, t, dtype, causal):
        want = self.TABLE[t][jnp.dtype(dtype).itemsize > 2]
        assert flash_tiles(t, t, dtype) == (want, want)
        # keys and queries choose alone
        assert flash_tiles(t, 128, dtype) == (want, 128)
        padded = -(-t // want) * want
        if t < 128:
            assert padded == want == t        # the whole sequence, as ever
        else:
            aligned = -(-t // 128) * 128
            assert want % 128 == 0
            assert aligned <= padded <= aligned + aligned // 8
        assert want <= (1024 if jnp.dtype(dtype).itemsize <= 2 else 512)
        # and that is the tile the call is traced at, counted once
        tile = f"{want}x{want}"
        before = _flash_calls(tile, causal)
        x = jax.ShapeDtypeStruct((1, t, 2, 8), dtype)
        out = jax.eval_shape(
            lambda q, k, v: flash_attention(q, k, v, causal=causal), x, x, x)
        assert out.shape == x.shape and out.dtype == x.dtype
        assert _flash_calls(tile, causal) == before + 1

    @pytest.mark.parametrize("tq,tk,dtype,causal,key_blocks,masked", [
        (256, 256, jnp.float32, False, 1, False),   # the encoder's case
        (1024, 1024, jnp.float32, False, 2, False),
        (200, 200, jnp.float32, False, 1, True),    # padded: mask stays
        (600, 600, jnp.float32, False, 5, True),
        (130, 300, jnp.float32, False, 1, True),    # keys and queries differ
        (256, 256, jnp.bfloat16, True, 1, True),    # the decoder at one block
        (1024, 1024, jnp.float32, True, 2, True),   # ... and with the skip
        (1100, 1100, jnp.bfloat16, False, 2, True),
    ])
    def test_kernel_at_the_rules_tiles_matches_dense(
            self, tq, tk, dtype, causal, key_blocks, masked):
        q, k, v = _qkv(1, tq, tk, 2, 8, seed=tq + tk)
        ref = dense_attention(q, k, v, causal=causal)
        low = [x.astype(dtype) for x in (q, k, v)]

        def call(q, k, v):
            return flash_attention(q, k, v, causal=causal, interpret=True)

        got = call(*low)
        assert got.dtype == dtype
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(got.astype(jnp.float32), ref, atol=tol,
                                   rtol=tol)
        block_k = flash_tiles(tq, tk, dtype)[1]
        assert -(-tk // block_k) == key_blocks
        # a tile does no work the shapes rule out: no key positions where no
        # key is padding, and with one key block no correction to a running
        # maximum (its second exponential; the scale is inside it, so the
        # exponential is base 2: `_weigh`)
        kernel = str(jax.make_jaxpr(call)(*low))
        assert ("= iota[" in kernel) == masked
        assert (kernel.count("= exp2 ") == 1) == (key_blocks == 1)
        assert "= exp " not in kernel

    def test_backward_chunk_is_not_the_tile(self):
        """A caller that names nothing (`SelfAttention`) gets the rule's
        forward tile, 384 here, and the backward's key chunk of 128 it
        always had: the scan takes three steps, and the gradients are
        dense attention's."""
        q, k, v = _qkv(1, 300, 300, 2, 8, seed=11)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        flash = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, interpret=True)), argnums=(0, 1, 2))
        assert flash_tiles(300, 300, q.dtype) == (384, 384)
        text = str(jax.make_jaxpr(flash)(q, k, v))
        assert "length=3" in text and "length=1" not in text
        whole = str(jax.make_jaxpr(jax.grad(loss(lambda q, k, v:
            flash_attention(q, k, v, bwd_chunk=None, interpret=True)),
            argnums=(0, 1, 2)))(q, k, v))
        assert "length=1" in whole            # the decoder: a forward tile
        gd = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gd, flash(q, k, v)):
            np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)

    def test_self_attention_names_no_block(self, monkeypatch):
        """Off the CPU `SelfAttention(impl="flash")` calls the kernel and
        leaves tile and backward chunk to it; values and gradients through
        that call are the dense module's."""
        seen = []

        def recorded(q, k, v, **kw):
            seen.append(kw)
            return flash_attention(q, k, v, interpret=True, **kw)

        x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 150, 16)),
                        jnp.float32)
        dense = SelfAttention(num_heads=2, impl="dense")
        variables = dense.init(jax.random.PRNGKey(0), x)

        def loss(module):
            return lambda v, x: (module.apply(v, x) ** 2).sum()

        want = jax.value_and_grad(loss(dense))(variables, x)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(attention.flash, "flash_attention", recorded)
        got = jax.value_and_grad(loss(SelfAttention(
            num_heads=2, impl="flash")))(variables, x)
        assert seen == [{"causal": False}]
        for a, b_ in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


class TestSelfAttentionModule:
    KW = dict(num_layers=2, d_model=32, num_heads=4, d_ff=64,
              vocab_size=50, num_outputs=3)

    def test_param_tree_identical_across_impls(self):
        x = jnp.asarray(np.arange(20).reshape(2, 10) % 50)
        base = make_model("transformer", **self.KW)
        v0 = base.init(jax.random.PRNGKey(0), x)
        for impl in ("chunked", "flash"):
            m = make_model("transformer", attention_impl=impl, **self.KW)
            v1 = m.init(jax.random.PRNGKey(0), x)
            assert (jax.tree_util.tree_structure(v0)
                    == jax.tree_util.tree_structure(v1))
            assert (jax.tree.map(lambda a: a.shape, v0)
                    == jax.tree.map(lambda a: a.shape, v1))

    def test_encoder_outputs_agree_across_impls(self):
        x = jnp.asarray(np.arange(30).reshape(3, 10) % 50)
        base = make_model("transformer", **self.KW)
        v0 = base.init(jax.random.PRNGKey(0), x)
        ref = base.apply(v0, x)
        for impl in ("chunked", "flash"):  # flash falls back off-TPU
            m = make_model("transformer", attention_impl=impl, **self.KW)
            out = m.apply(v0, x)           # same params on purpose
            np.testing.assert_allclose(out, ref, atol=5e-5, rtol=1e-4)

    def test_dropout_rejected_off_dense(self):
        m = make_model("transformer", attention_impl="chunked",
                       dropout_rate=0.1, **self.KW)
        x = jnp.asarray(np.zeros((1, 4), np.int32))
        with pytest.raises(ValueError, match="dropout"):
            m.init(jax.random.PRNGKey(0), x)

    def test_unknown_impl_rejected(self):
        mod = SelfAttention(num_heads=2, impl="nope")
        x = jnp.zeros((1, 4, 8), jnp.float32)
        with pytest.raises(ValueError, match="unknown attention impl"):
            mod.init(jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("impl,backend,runs", [
    ("flash", "cpu", "chunked"), ("flash", "tpu", "flash"),
    ("chunked", "tpu", "chunked"), ("dense", "cpu", "dense"),
    ("sparse", "tpu", None)])
def test_the_tier_that_runs_has_one_owner(monkeypatch, impl, backend, runs):
    """`attention.tier`: what a model's name for a tier means on this
    backend, and the one refusal of a name that is none."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if runs is None:
        with pytest.raises(ValueError, match="'flash', 'chunked', 'dense'"):
            attention.tier(impl)
    else:
        assert attention.tier(impl) == runs


# --------------------------------------------------------------------- #
# grouped-query heads                                                   #
# --------------------------------------------------------------------- #

def _grouped_qkv(b, t, h, group, d, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, h // group, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, h // group, d)), dtype)
    return q, k, v


def _equations(fn, *args):
    """Every equation under `fn`, however deep; a `pallas_call`'s own
    body is not entered."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def _pallas_equation(fn, *args):
    """The one `pallas_call` equation under `fn`."""
    found = [eqn for eqn in _equations(fn, *args)
             if eqn.primitive.name == "pallas_call"]
    assert len(found) == 1
    return found[0]


class TestGroupedQueryHeads:
    """k and v with fewer heads than q (a divisor): query head j reads
    key/value head j // group, in every tier, and nothing is repeated."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("group", [1, 2, 4])
    def test_interpreted_kernel_matches_dense(self, group, d, causal):
        # 40 tokens in tiles of 16: two whole tiles and a padded third
        q, k, v = _grouped_qkv(2, 40, 4, group, d, seed=group + d)
        want = dense_attention(q, jnp.repeat(k, group, 2),
                               jnp.repeat(v, group, 2), causal=causal)
        np.testing.assert_allclose(
            dense_attention(q, k, v, causal=causal), want, atol=2e-5)
        np.testing.assert_allclose(
            chunked_attention(q, k, v, causal=causal, q_chunk=16,
                              k_chunk=16), want, atol=2e-5)
        got = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        assert got.shape == q.shape
        np.testing.assert_allclose(got, want, atol=2e-5)

    @pytest.mark.parametrize("group", [2, 4])
    def test_kernel_equals_repeated_heads_bit_for_bit(self, group):
        """The same tiles and the same arithmetic: only the index map
        differs from a call whose K and V were repeated."""
        q, k, v = _grouped_qkv(1, 48, 4, group, 64, seed=7)
        call = lambda q, k, v: flash_attention(     # noqa: E731
            q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
        assert np.array_equal(
            np.asarray(call(q, k, v)),
            np.asarray(call(q, jnp.repeat(k, group, 2),
                            jnp.repeat(v, group, 2))))

    @pytest.mark.parametrize("tile", [16, 32])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("group", [1, 4])
    def test_key_and_value_operands_keep_their_own_heads(self, group, d,
                                                         tile):
        q, k, v = _grouped_qkv(2, 32, 8, group, d)
        eqn = _pallas_equation(
            lambda q, k, v: flash_attention(q, k, v, block_q=tile,
                                            block_k=tile, interpret=True),
            q, k, v)
        # two key blocks: the walk comes first
        walked = eqn.params["grid_mapping"].num_index_operands
        assert walked == (tile == 16)
        # 64 channels: head-major rows; 128: the arrays as they lie
        assert [x.aval.shape for x in eqn.invars[walked:]] == (
            [(16, 32, 64), (16 // group, 32, 64), (16 // group, 32, 64)]
            if d == 64 else
            [(2, 32, 8 * 128)] + [(2, 32, 8 // group * 128)] * 2)
        # what an index map computes: in place nothing (it names its grid
        # indices, or reads the step's block from the walk: an add and a
        # load), head-major the row b x H + j; grouped heads add the one
        # division where a grid step is ONE query head (one key block), and
        # nothing where it is the key head's whole group (`heads_a_step`)
        maps = eqn.params["grid_mapping"].block_mappings
        computed = [len(m.index_map_jaxpr.jaxpr.eqns) for m in maps[:3]]
        row = (0 if d == 128 else 2) + 2 * walked
        divided = group > 1 and not walked
        assert computed == [row, row + divided, row + divided]
        # the step's blocks: a group's query heads, ONE key/value head
        heads = group if walked else 1
        assert eqn.params["grid_mapping"].grid[1] == 8 // heads
        assert [tuple(x.block_size for x in m.block_shape)
                for m in maps[:3]] == [
            (heads, tile, 64) if d == 64 else (1, tile, heads * 128),
            (1, tile, d), (1, tile, d)]

    @pytest.mark.parametrize("window", [None, 24])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("group", [2, 4])
    def test_a_groups_heads_in_one_step_equal_a_head_a_step_bit_for_bit(
            self, monkeypatch, group, d, window):
        """Several key blocks (40 tokens in tiles of 16: two whole tiles
        and a padded third), plain causal and banded, head-major (64) and
        in place (128): a step that folds the group's heads one after the
        other against the key block fetched once gives every head the folds
        it had, in their order."""
        q, k, v = _grouped_qkv(2, 40, 8, group, d, seed=group + d,
                               dtype=jnp.bfloat16)
        call = lambda: np.asarray(attention.causal_attention(  # noqa: E731
            q, k, v, "flash", window=window, block_q=16, block_k=16,
            interpret=True).astype(jnp.float32))
        steps = []
        real = attention.fold._flash_call
        monkeypatch.setattr(
            attention.fold, "_flash_call",
            lambda *a, **kw: steps.append(kw["heads"]) or real(*a, **kw))
        grouped = call()
        monkeypatch.setattr(attention.fold, "heads_a_step",
                            lambda *a, **kw: 1)
        jax.clear_caches()
        assert np.array_equal(grouped, call())
        assert steps == [group, 1]

    @pytest.mark.parametrize("group,blocks,d,heads", [
        # LFM2's long rows: 32 over 8 heads of 64, 16 key blocks; rows of
        # 2048 in tiles of 1024
        (4, 16, 64, 4), (4, 2, 64, 4),
        # SmallThinker's 28 over 4 and Falcon-H1's 20 over 4, of 128: the
        # whole group (7 and 5 have no divisor between), inside the 32 MB
        # the call states
        (7, 16, 128, 7), (5, 32, 128, 5),
        # a group of 16 passes it whole: its largest divisor that fits
        (16, 16, 128, 4), (8, 16, 64, 4), (6, 16, 128, 6),
        # Ouro's 16 over 16: nothing to share
        (1, 8, 128, 1),
        # ONE key block carries nothing from step to step: the program of
        # a head a step, whatever the group
        (4, 1, 64, 1), (7, 1, 128, 1)])
    def test_the_rule_of_heads_a_step_at_the_cells_shapes(
            self, group, blocks, d, heads):
        assert attention.fold.heads_a_step(
            group, blocks, 1024, 1024, d, d, 2) == heads

    def test_a_step_of_several_heads_is_counted_inside_the_stated_vmem(self):
        """The count a head (q, output, lse and the statistics, lane
        padded, two slots a block) and a step's own, at tiles of 1024: 3.5
        MB a head beside 7 MB, never under what the chip's compiler counts
        (`tests/test_chipless_compile.py` holds the limit at the cells'
        shapes and with a padded tail)."""
        count = attention.fold._step_bytes
        mb = 2 ** 20
        assert count(1, 1024, 1024, 64, 64, 2) == 10.5 * mb
        assert count(4, 1024, 1024, 64, 64, 2) == 21 * mb
        assert count(7, 1024, 1024, 128, 128, 2) == 31.5 * mb
        assert count(8, 1024, 1024, 128, 128, 2) > attention.fold.STEP_VMEM
        # small tiles (a test's): every divisor fits
        assert attention.fold.heads_a_step(4, 3, 16, 16, 64, 64, 4) == 4

    def test_heads_a_step_are_counted_by_kernel_group_and_width(self):
        def counted(kernel, group, heads):
            return get_registry().counter(
                "mmlspark_tpu_flash_heads_a_step_total",
                labels=("kernel", "group", "heads")).labels(
                    kernel=kernel, group=str(group), heads=str(heads)).value

        before = (counted("gqa", 4, 4), counted("gqa", 4, 1),
                  counted("swa", 7, 7), counted("attn", 1, 1))
        q = jax.ShapeDtypeStruct((1, 2048, 8, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16)
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       q, kv, kv)
        # one key block: a head a step
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       *(jax.ShapeDtypeStruct((1, 1024) + x.shape[2:],
                                              x.dtype) for x in (q, kv, kv)))
        wide = jax.ShapeDtypeStruct((1, 8192, 7, 128), jnp.bfloat16)
        one = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)
        jax.eval_shape(lambda q, k, v: attention.causal_attention(
            q, k, v, "flash", window=4096), wide, one, one)
        jax.eval_shape(flash_attention, one, one, one)
        assert (counted("gqa", 4, 4), counted("gqa", 4, 1),
                counted("swa", 7, 7), counted("attn", 1, 1)) == tuple(
                    n + 1 for n in before)

    def test_grouped_gradients_match_dense(self):
        q, k, v = _grouped_qkv(1, 24, 4, 2, 8, seed=3)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        got = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=8, block_k=8, bwd_chunk=8,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(lambda q, k, v: dense_attention(
            q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True)),
            argnums=(0, 1, 2))(q, k, v)
        assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=5e-5, rtol=1e-4)

    def test_heads_that_do_not_divide_are_refused(self):
        q, k, v = _grouped_qkv(1, 8, 4, 1, 8)
        for fn in (dense_attention, chunked_attention,
                   lambda q, k, v: flash_attention(q, k, v, interpret=True)):
            with pytest.raises(ValueError, match="divide"):
                fn(q, k[:, :, :3], v[:, :, :3])

    def test_a_grouped_call_is_counted(self):
        def counted(name, **labels):
            return get_registry().counter(
                name, labels=tuple(labels)).labels(**labels).value

        before = (counted("mmlspark_tpu_flash_grouped_calls_total",
                          group="4", tile="128x128"),
                  _flash_calls("128x128", True))
        x = jax.ShapeDtypeStruct((1, 128, 8, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.bfloat16)
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       x, kv, kv)
        # under both of the call counter's labels as ever, and by group
        assert _flash_calls("128x128", True) == before[1] + 1
        assert counted("mmlspark_tpu_flash_grouped_calls_total", group="4",
                       tile="128x128") == before[0] + 1

    @pytest.mark.parametrize("dtype,tile", [(jnp.bfloat16, 1024),
                                            (jnp.float32, 512)])
    def test_rule_at_16384_tokens(self, dtype, tile):
        """Read on a v5e at head width 64 (PERF.md, PR 31): 1024 x 1024
        stays the best tile at 16384 tokens, and the next larger one does
        not fit the default VMEM."""
        assert flash_tiles(16384, 16384, dtype) == (tile, tile)
        assert flash_tiles(1024, 1024, dtype) == (tile, tile)

    def test_kernel_at_head_width_64_and_the_rules_tile(self):
        q, k, v = _grouped_qkv(1, 300, 8, 4, 64, seed=5, dtype=jnp.bfloat16)
        assert flash_tiles(300, 300, q.dtype) == (384, 384)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = dense_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                               causal=True)
        np.testing.assert_allclose(got.astype(jnp.float32), want, atol=3e-2,
                                   rtol=3e-2)


# --------------------------------------------------------------------- #
# the layout the kernels speak                                          #
# --------------------------------------------------------------------- #

def _operands(kernel, layout):
    return get_registry().counter(
        "mmlspark_tpu_attention_operands_total",
        labels=("kernel", "layout")).labels(
            kernel=kernel, layout=layout).value


def _head_major(monkeypatch):
    """The copy path at ANY width, for a comparison: the rule is by shape
    and the program has no switch, so the test takes the rule away (and
    the jitted forwards' cached traces with it)."""
    monkeypatch.setattr(attention.layout, "_lanes_whole",
                        lambda *widths: False)
    jax.clear_caches()


def _transposed_lengths(fn, *args):
    """The extents of every operand a `transpose` equation under `fn`
    moves."""
    return [eqn.invars[0].aval.shape for eqn in _equations(fn, *args)
            if eqn.primitive.name == "transpose"]


def _latent_inputs(t, b=2, h=4, nope=128, rope=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, t, h, nope)), dtype),
            jnp.asarray(rng.normal(size=(b, t, h, rope)), dtype),
            jnp.asarray(rng.normal(size=(b, t, h, 2 * nope)), dtype),
            jnp.asarray(rng.normal(size=(b, t, rope)), dtype))


class TestOperandsInPlace:
    """Heads of whole lanes (multiples of 128 channels) are read as blocks
    of the (B, T, H x D) arrays around the call and written the same way;
    any other width is copied head-major. The same values in the same
    order in every block: bit for bit."""

    # 40 tokens in tiles of 16 pad to 48; 32 do not
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("t,group", [(32, 1), (40, 1), (40, 2), (32, 4)])
    def test_flash_in_place_equals_head_major(self, monkeypatch, t, group,
                                              causal):
        q, k, v = _grouped_qkv(2, t, 4, group, 128, seed=t + group)
        call = lambda: np.asarray(flash_attention(    # noqa: E731
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True))
        before = _operands("flash", "in_place"), _operands("flash",
                                                           "head_major")
        in_place = call()
        assert _operands("flash", "in_place") == before[0] + 1
        _head_major(monkeypatch)
        assert np.array_equal(in_place, call())
        assert _operands("flash", "head_major") == before[1] + 1
        np.testing.assert_allclose(
            in_place, dense_attention(q, k, v, causal=causal), atol=2e-5)

    def test_values_of_another_width_in_place(self, monkeypatch):
        q, k, _ = _qkv(1, 24, 24, 2, 128, seed=3)
        v = jnp.asarray(np.random.default_rng(4).normal(size=(1, 24, 2, 256)),
                        jnp.float32)
        call = lambda: np.asarray(flash_attention(    # noqa: E731
            q, k, v, causal=True, block_q=8, block_k=8, interpret=True))
        in_place = call()
        assert in_place.shape == (1, 24, 2, 256)
        _head_major(monkeypatch)
        assert np.array_equal(in_place, call())

    # window 32, chunk 4: 96 is three whole windows, 70 ends inside one
    # (and inside a chunk)
    @pytest.mark.parametrize("t", [96, 70])
    def test_eva_in_place_equals_head_major(self, monkeypatch, t):
        q, k, v, phi, mu = _eva_inputs(t, h=2, d=128)
        call = lambda: np.asarray(eva_attention(      # noqa: E731
            q, k, v, phi, mu, EVA_WINDOW, EVA_CHUNK, interpret=True))
        before = _operands("eva", "in_place"), _operands("eva", "head_major")
        in_place = call()
        assert _operands("eva", "in_place") == before[0] + 1
        _head_major(monkeypatch)
        assert np.array_equal(in_place, call())
        assert _operands("eva", "head_major") == before[1] + 1
        want, _kbar, _vbar = _eva_by_masks(q, k, v, phi, mu, EVA_WINDOW,
                                           EVA_CHUNK)
        assert np.abs(in_place - want).max() < 5 * EVA_LIMIT

    @pytest.mark.parametrize("d,moved", [(128, False), (64, True),
                                         (8, True)])
    @pytest.mark.parametrize("kernel", ["flash", "eva"])
    def test_the_rule_is_by_shape(self, kernel, d, moved):
        """No operand with the sequence's extent is transposed around the
        kernel at whole lanes; at 64 channels (and a test's 8) one is."""
        t = 96
        q, k, v, phi, mu = _eva_inputs(t, h=2, d=d)
        if kernel == "flash":
            fn = lambda q, k, v: flash_attention(     # noqa: E731
                q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
            args = (q, k, v)
        else:
            fn = lambda *a: eva_attention(            # noqa: E731
                *a, EVA_WINDOW, EVA_CHUNK, interpret=True)
            args = (q, k, v, phi, mu)
        layout = "head_major" if moved else "in_place"
        before = _operands(kernel, layout)
        moves = [s for s in _transposed_lengths(fn, *args) if t in s]
        assert bool(moves) == moved, moves
        assert _operands(kernel, layout) == before + 1

    @pytest.mark.parametrize("t,tile", [(48, 16), (40, 16), (24, 24)])
    def test_split_latent_score_equals_the_concatenated_one(self, t, tile):
        """The score over a head's own and its rotary channels as they
        lie (with the zeros beside the rotary key) against ONE product of
        192 concatenated channels: float32 rounding apart at most, and the
        concatenated path is `flash_attention` as ever."""
        parts = _latent_inputs(t, seed=t)
        before = _operands("mla", "in_place")
        got = attention.latent_attention(*parts, block_q=tile, block_k=tile,
                                         interpret=True)
        assert _operands("mla", "in_place") == before + 1
        q, k, v = attention.latent._latent_concatenated(*parts)
        assert q.shape[-1] == k.shape[-1] == 192 and v.shape[-1] == 128
        want = flash_attention(q, k, v, causal=True, block_q=tile,
                               block_k=tile, interpret=True)
        assert got.shape == want.shape == (2, t, 4, 128)
        np.testing.assert_allclose(got, want, atol=2e-6)
        np.testing.assert_allclose(
            got, dense_attention(q, k, v, causal=True), atol=2e-5)
        # nothing of the sequence's extent is transposed, sliced apart or
        # broadcast to the heads around the kernel
        fn = lambda *a: attention.latent_attention(   # noqa: E731
            *a, block_q=tile, block_k=tile, interpret=True)
        assert not [s for s in _transposed_lengths(fn, *parts) if t in s]
        eqn = _pallas_equation(fn, *parts)
        # (behind the walk's table where a row is several tiles)
        assert [x.aval.shape[-1] for x in eqn.invars if x.aval.ndim == 3] == [
            4 * 128, 4 * 64, 4 * 256, 2 * 128, 4 * 256]

    def test_split_latent_gradient_equals_todays(self):
        parts = _latent_inputs(24, seed=5)

        def loss(fn):
            return lambda *a: (fn(*a) ** 2).sum()

        got = jax.grad(loss(lambda *a: attention.latent_attention(
            *a, block_q=8, block_k=8, interpret=True)),
            argnums=(0, 1, 2, 3))(*parts)
        want = jax.grad(loss(lambda *a: flash_attention(
            *attention.latent._latent_concatenated(*a), causal=True, block_q=8,
            block_k=8, bwd_chunk=8, interpret=True)),
            argnums=(0, 1, 2, 3))(*parts)
        assert [g.shape for g in got] == [p.shape for p in parts]
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5)

    @pytest.mark.parametrize("nope,rope,heads,impl", [
        (8, 4, 2, "flash"),         # a test's widths: no lane block
        (128, 64, 3, "flash"),      # rotary channels that fill no block
        (128, 64, 4, "chunked"), (128, 64, 4, "dense")])
    def test_other_shapes_and_tiers_take_the_concatenated_path(
            self, nope, rope, heads, impl):
        parts = _latent_inputs(20, h=heads, nope=nope, rope=rope, seed=heads)
        before = _operands("mla", "head_major")
        got = attention.latent_attention(*parts, impl=impl, interpret=True)
        assert _operands("mla", "head_major") == before + (impl == "flash")
        np.testing.assert_allclose(
            got, dense_attention(
                *attention.latent._latent_concatenated(*parts), causal=True),
            atol=2e-5)

    # blocks of 16 positions: 40 pad to 48, 300 to 304, float32 in steps of 8
    @pytest.mark.parametrize("shape,dtype", [
        ((2, 40, 4, 128), jnp.bfloat16), ((2, 40, 4, 64), jnp.bfloat16),
        ((1, 300, 2, 128), jnp.bfloat16), ((1, 24, 2, 128), jnp.float32),
        ((1, 32, 2, 64), jnp.float32)])
    def test_rotary_in_lanes_is_the_models_rotary(self, monkeypatch, shape,
                                                  dtype):
        from mmlspark_tpu.nn.attention import rotary_xla as _rotary

        monkeypatch.setattr(attention.rotary, "_ROTARY_ROWS", 16)
        x = jnp.asarray(np.random.default_rng(1).normal(size=shape), dtype)
        got = attention.rotary_in_lanes(x, 1e5, interpret=True)
        want = _rotary(x, 1e5)
        assert got.shape == x.shape and got.dtype == x.dtype
        # the same float32 arithmetic, up to a multiply-add that XLA:CPU
        # may fuse on either side: a last bit, rarely
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
        np.testing.assert_allclose(got, want, rtol=ulp, atol=ulp)
        if dtype == jnp.bfloat16:      # the one rounding hides it, mostly
            assert (got != want).mean() < 0.02
        assert attention.rotary_lanes_whole(*shape[2:])
        assert not attention.rotary_lanes_whole(3, 64)
        assert not attention.rotary_lanes_whole(4, 32)

    def test_rotary_in_lanes_is_traced_once_a_shape(self, monkeypatch):
        """Jitted by itself: two tensors of one shape and one theta (a
        layer's q and k, and every layer's) share ONE trace of the kernel,
        whose grid walks the lane blocks a block of positions."""
        traced = []
        kernel = attention.rotary._rotary_kernel
        monkeypatch.setattr(
            attention.rotary, "_rotary_kernel",
            lambda *refs, **static: (traced.append(static),
                                     kernel(*refs, **static))[1])
        x = jnp.ones((1, 32, 4, 128), jnp.bfloat16)
        theta = 54321.0                  # no other test's: a trace of its own
        eqns = _equations(lambda x: attention.rotary_in_lanes(
            attention.rotary_in_lanes(x, theta, interpret=True), theta,
            interpret=True), x)
        assert traced == [{"width": 128}]
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert len(calls) == 2
        assert calls[0].params["grid_mapping"].grid == (1, 1, 4)
        assert [x.aval.shape for x in calls[0].invars] == [
            (1, 32, 512), (32, 128), (32, 128)]

    @pytest.mark.parametrize("bias,parts", [(True, ()), (False, ()),
                                            (False, (128, 64))])
    def test_heads_dense_is_dense_general(self, bias, parts):
        """The same parameters (names, shapes, initial values) and the same
        numbers as `nn.DenseGeneral((heads, width))`."""
        from flax import linen as nn

        heads, width = 4, sum(parts) or 128
        x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 10, 48)),
                        jnp.float32)
        ref = nn.DenseGeneral((heads, width), use_bias=bias)
        mod = attention.HeadsDense(heads, width, use_bias=bias, parts=parts)
        params = ref.init(jax.random.PRNGKey(0), x)
        ours = mod.init(jax.random.PRNGKey(0), x)
        assert jax.tree.map(jnp.shape, ours) == jax.tree.map(jnp.shape,
                                                             params)
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(ours), jax.tree.leaves(params)))
        if bias:
            params = jax.tree.map(lambda p: p + 0.5, params)
        got, want = mod.apply(params, x), ref.apply(params, x)
        if parts:
            got = jnp.concatenate(got, -1)
        np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------------------- #
# a window read exactly, the windows before it as chunk summaries       #
# --------------------------------------------------------------------- #

EVA_WINDOW, EVA_CHUNK = 32, 4
EVA_IMPLS = {"dense": {}, "chunked": {}, "flash": {"interpret": True}}
# float32 throughout on the CPU: the tiers differ in the order of their sums
# (online against whole softmax, blocks of keys), observed at most 8e-7 at
# values of spread 0.5
EVA_LIMIT = 1e-5


def _eva_inputs(t, b=2, h=3, d=8, seed=0):
    rng = np.random.default_rng([seed, t])
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
               for _ in range(3))
    phi = jnp.asarray(rng.normal(size=(h, d)), jnp.float32)
    mu = jnp.asarray(0.5 * rng.normal(size=(h, d)), jnp.float32)
    return q, k, v, phi, mu


def _eva_by_masks(q, k, v, phi, mu, window, chunk):
    """The equations as written, numpy float64: pooled keys and values a
    chunk, then ONE softmax over [summaries; keys] under two masks."""
    q, k, v, phi, mu = (np.asarray(x, np.float64) for x in (q, k, v, phi, mu))
    b, t, h, d = q.shape
    c, s = t // chunk, d ** -0.5
    kc = k[:, :c * chunk].reshape(b, c, chunk, h, d)
    vc = v[:, :c * chunk].reshape(b, c, chunk, h, d)
    a = np.einsum("bcmhd,hd->bcmh", kc, phi) * s
    a = np.exp(a - a.max(2, keepdims=True))
    a /= a.sum(2, keepdims=True)
    kbar = np.einsum("bcmh,bcmhd->bchd", a, kc) + mu
    vbar = np.einsum("bcmh,bcmhd->bchd", a, vc)
    pos = np.arange(t)
    first = (pos // window) * window              # a query's window starts
    seen = np.concatenate([
        (np.arange(c)[None, :] + 1) * chunk <= first[:, None],
        (pos[None, :] <= pos[:, None]) & (pos[None, :] >= first[:, None])], 1)
    scores = np.einsum("bqhd,bkhd->bhqk", q,
                       np.concatenate([kbar, k], 1)) * s
    scores = np.where(seen, scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return (np.einsum("bhqk,bkhd->bqhd", p, np.concatenate([vbar, v], 1)),
            kbar, vbar)


def _eva_calls(window, chunk, tile, prefixes=0) -> float:
    return get_registry().counter(
        "mmlspark_tpu_eva_calls_total",
        labels=("window", "chunk", "tile", "prefixes")).labels(
            window=str(window), chunk=str(chunk), tile=tile,
            prefixes=str(prefixes)).value


class TestEvaAttention:
    """`eva_attention`: window 32 and chunk 4 unless said."""

    # 20: inside one window; 96: three whole windows; 80: a ragged last
    # window; 70: a ragged last chunk too
    @pytest.mark.parametrize("impl", sorted(EVA_IMPLS))
    @pytest.mark.parametrize("t", [20, 96, 80, 70])
    def test_three_tiers_match_the_masks(self, t, impl):
        q, k, v, phi, mu = _eva_inputs(t)
        want, _kbar, _vbar = _eva_by_masks(q, k, v, phi, mu, EVA_WINDOW,
                                           EVA_CHUNK)
        got = eva_attention(q, k, v, phi, mu, EVA_WINDOW, EVA_CHUNK,
                            impl=impl, **EVA_IMPLS[impl])
        assert got.shape == q.shape and got.dtype == q.dtype
        assert np.abs(np.asarray(got) - want).max() < EVA_LIMIT

    @pytest.mark.parametrize("impl", sorted(EVA_IMPLS))
    def test_one_window_is_plain_causal_attention(self, impl):
        q, k, v, phi, mu = _eva_inputs(20)
        got = eva_attention(q, k, v, phi, mu, EVA_WINDOW, EVA_CHUNK,
                            impl=impl, **EVA_IMPLS[impl])
        # the same tier of plain causal attention, bit for bit: no summary
        # exists, and phi and mu play no part
        plain = {"dense": lambda: dense_attention(q, k, v, causal=True),
                 "chunked": lambda: chunked_attention(q, k, v, causal=True),
                 "flash": lambda: flash_attention(q, k, v, causal=True,
                                                  interpret=True)}[impl]()
        assert np.array_equal(np.asarray(got), np.asarray(plain))
        np.testing.assert_allclose(
            got, dense_attention(q, k, v, causal=True), atol=EVA_LIMIT)

    def test_the_pooling_is_the_equations(self):
        q, k, v, phi, mu = _eva_inputs(70)
        _out, kbar, vbar = _eva_by_masks(q, k, v, phi, mu, EVA_WINDOW,
                                         EVA_CHUNK)
        got_k, got_v = eva_summaries(k, v, phi, mu, EVA_CHUNK)
        assert got_k.shape == (2, 17, 3, 8)        # 70 // 4 whole chunks
        assert np.abs(np.asarray(got_k) - kbar).max() < EVA_LIMIT
        assert np.abs(np.asarray(got_v) - vbar).max() < EVA_LIMIT
        # only what is read: the windows before the last
        short_k, _ = eva_summaries(k, v, phi, mu, EVA_CHUNK, upto=64)
        assert np.array_equal(np.asarray(short_k), np.asarray(got_k[:, :16]))

    # one tile a window (PR 28's lesson 4: the interpreted kernel finds a
    # wrong count of steps), then tiles that cross: two key blocks a window,
    # summaries in blocks that end inside a window's share (8 a window)
    @pytest.mark.parametrize("tiles", [(32, 32, 16), (16, 16, 16),
                                       (32, 16, 8), (16, 32, 24),
                                       (8, 8, 3)])
    @pytest.mark.parametrize("t", [96, 70])
    def test_the_interpreted_kernel_at_tiles_that_cross(self, t, tiles):
        q, k, v, phi, mu = _eva_inputs(t, seed=3)
        want, _kbar, _vbar = _eva_by_masks(q, k, v, phi, mu, EVA_WINDOW,
                                           EVA_CHUNK)
        block_q, block_k, block_s = tiles
        got = eva_attention(q, k, v, phi, mu, EVA_WINDOW, EVA_CHUNK,
                            impl="flash", block_q=block_q, block_k=block_k,
                            block_s=block_s, interpret=True)
        assert np.abs(np.asarray(got) - want).max() < EVA_LIMIT

    @pytest.mark.parametrize("impl", sorted(EVA_IMPLS))
    def test_a_change_at_p_moves_nothing_before_p(self, impl):
        q, k, v, phi, mu = _eva_inputs(96, seed=1)
        p = 70                      # in the third window, inside a chunk

        def run(q, k, v):
            return np.asarray(eva_attention(
                q, k, v, phi, mu, EVA_WINDOW, EVA_CHUNK, impl=impl,
                **EVA_IMPLS[impl]))

        base = run(q, k, v)
        moved = run(q.at[:, p].add(1.0), k.at[:, p].add(1.0),
                    v.at[:, p].add(1.0))
        assert np.array_equal(moved[:, :p], base[:, :p])
        assert not np.array_equal(moved[:, p:], base[:, p:])

    @pytest.mark.parametrize("impl", sorted(EVA_IMPLS))
    def test_an_earlier_window_is_seen_through_its_summaries_only(self,
                                                                  impl):
        """The 4 keys and values of chunk 3 (positions 12 .. 15, window 0)
        replaced by any others under the SAME kbar, vbar: every query of a
        later window reads the same, bit for bit; the chunk's own window,
        which reads the keys, does not."""
        q, k, v, phi, mu = _eva_inputs(96, seed=2)
        summaries = eva_summaries(k, v, phi, mu, EVA_CHUNK)

        def run(k, v):
            return np.asarray(eva_attention(
                q, k, v, None, None, EVA_WINDOW, EVA_CHUNK, impl=impl,
                summaries=summaries, **EVA_IMPLS[impl]))

        other = jnp.asarray(np.random.default_rng(9).normal(
            size=(2, 4, 3, 8)), jnp.float32)
        base = run(k, v)
        moved = run(k.at[:, 12:16].set(other), v.at[:, 12:16].set(-other))
        assert np.array_equal(moved[:, 32:], base[:, 32:])
        assert not np.array_equal(moved[:, 16:32], base[:, 16:32])
        # and positions before the chunk see nothing of it
        assert np.array_equal(moved[:, :12], base[:, :12])

    def test_too_few_summaries_and_a_window_of_broken_chunks_are_refused(
            self):
        q, k, v, phi, mu = _eva_inputs(96)
        few = eva_summaries(k, v, phi, mu, EVA_CHUNK, upto=32)
        with pytest.raises(ValueError, match="reads 16 summaries"):
            eva_attention(q, k, v, phi, mu, EVA_WINDOW, EVA_CHUNK,
                          impl="dense", summaries=few)
        with pytest.raises(ValueError, match="whole"):
            eva_attention(q, k, v, phi, mu, 30, EVA_CHUNK, impl="dense")

    def test_a_call_is_counted_by_window_chunk_and_tile(self):
        x = jax.ShapeDtypeStruct((1, 4096, 2, 128), jnp.bfloat16)
        vec = jax.ShapeDtypeStruct((2, 128), jnp.float32)
        before = (_eva_calls(2048, 16, "1024x1024x128"),
                  _flash_calls("1024x1024", True))
        out = jax.eval_shape(
            lambda q, k, v, phi, mu: eva_attention(q, k, v, phi, mu, 2048,
                                                   16), x, x, x, vec, vec)
        assert out.shape == x.shape and out.dtype == x.dtype
        assert _eva_calls(2048, 16, "1024x1024x128") == before[0] + 1
        # the plain forward's counter keeps its two labels and counts what
        # it counted: not this call, and a row of one window as ever
        assert _flash_calls("1024x1024", True) == before[1]
        short = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
        jax.eval_shape(
            lambda q, k, v, phi, mu: eva_attention(q, k, v, phi, mu, 2048,
                                                   16),
            short, short, short, vec, vec)
        assert _flash_calls("1024x1024", True) == before[1] + 1
        assert _eva_calls(2048, 16, "1024x1024x128") == before[0] + 1


class TestFlashTilesToldTheWindow:
    @pytest.mark.parametrize("window,dtype,tile", [
        (2048, jnp.bfloat16, 1024), (2048, jnp.float32, 512),
        (32, jnp.float32, 32), (128, jnp.bfloat16, 128),
        (384, jnp.bfloat16, 384), (1536, jnp.bfloat16, 768)])
    def test_tiles_divide_the_window(self, window, dtype, tile):
        assert flash_tiles(32768, 32768, dtype, window=window) == (tile, tile)
        assert window % tile == 0

    def test_a_window_no_tile_divides_is_refused(self):
        with pytest.raises(ValueError, match="divides a window"):
            flash_tiles(4096, 4096, jnp.bfloat16, window=1100)

    # every (tq, tk, dtype) the three neural cells of PR 32 call the plain
    # forward at: `xlmr_xxl.score_table`, `moonlight_16b_a3b.score_loglik`,
    # `lfm2_8b_a1b.score_long_docs`, and the answers they got there
    @pytest.mark.parametrize("t,tile", [(512, 512), (128, 128), (4096, 1024),
                                        (16384, 1024), (1024, 1024)])
    def test_without_a_window_the_answers_are_the_parents(self, t, tile):
        assert flash_tiles(t, t, jnp.bfloat16) == (tile, tile)
        assert flash_tiles(t, t, jnp.bfloat16, window=None) == (tile, tile)


# --------------------------------------------------------------------- #
# a window that slides with the query (`causal_attention(window=...)`)   #
# --------------------------------------------------------------------- #

BAND = 16                       # the window, unless said
BAND_LIMIT = 2e-6               # float32 against float32: the sums' order
BAND_IMPLS = {"dense": {}, "chunked": {},
              "flash": {"block_q": 8, "block_k": 8, "interpret": True}}


def _band_inputs(t: int, heads=(4, 2), d: int = 8, seed: int = 0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(2, t, h, d)), jnp.float32)
                 for h in (heads[0], heads[1], heads[1]))


def _band_by_mask(q, k, v, window: int) -> np.ndarray:
    """One softmax over a mask built here: u <= t and t - u < window,
    query head j reading key/value head j // group, in float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, group, 2), np.repeat(v, group, 2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    pos = np.arange(q.shape[1])
    behind = pos[:, None] - pos[None, :]
    s = np.where((behind >= 0) & (behind < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)


def _window_calls(window: int, tile: str) -> float:
    return get_registry().counter(
        "mmlspark_tpu_attention_window_calls_total",
        labels=("window", "tile")).labels(window=str(window),
                                          tile=tile).value


class TestSlidingWindow:
    """`causal_attention(q, k, v, impl, window=16)`: 4 query heads over 2
    key/value heads of 8 channels unless said."""

    # 12: under the window; 16: the window itself; 17: one past it; 40: two
    # and a half; 37: no multiple of any tile
    @pytest.mark.parametrize("impl", sorted(BAND_IMPLS))
    @pytest.mark.parametrize("t", [12, 16, 17, 40, 37])
    def test_three_tiers_match_the_mask(self, t, impl):
        q, k, v = _band_inputs(t)
        got = attention.causal_attention(q, k, v, impl, window=BAND,
                                         **BAND_IMPLS[impl])
        assert got.shape == q.shape and got.dtype == q.dtype
        assert np.abs(np.asarray(got) - _band_by_mask(q, k, v, BAND)).max() \
            < BAND_LIMIT

    @pytest.mark.parametrize("impl", sorted(BAND_IMPLS))
    def test_a_row_of_one_window_is_plain_causal_attention(self, impl):
        q, k, v = _band_inputs(BAND)
        got = attention.causal_attention(q, k, v, impl, window=BAND,
                                         **BAND_IMPLS[impl])
        plain = attention.causal_attention(q, k, v, impl, **BAND_IMPLS[impl])
        assert np.array_equal(np.asarray(got), np.asarray(plain))

    # a window that is no multiple of the tile, tiles that differ, a tile
    # larger than the window, one tile a window, queries in one block
    @pytest.mark.parametrize("window,tiles", [
        (16, (8, 8)), (16, (16, 16)), (16, (16, 8)), (16, (8, 16)),
        (20, (8, 8)), (17, (16, 16)), (24, (32, 8)), (12, (32, 32)),
        (16, (64, 8))])
    @pytest.mark.parametrize("t", [64, 50])
    def test_the_interpreted_kernel_at_several_tilings(self, t, window,
                                                       tiles):
        q, k, v = _band_inputs(t, seed=3)
        got = attention.causal_attention(
            q, k, v, "flash", window=window, block_q=tiles[0],
            block_k=tiles[1], interpret=True)
        assert np.abs(np.asarray(got) - _band_by_mask(q, k, v, window)).max() \
            < BAND_LIMIT

    @pytest.mark.parametrize("impl", sorted(BAND_IMPLS))
    def test_a_change_at_p_moves_nothing_before_p(self, impl):
        q, k, v = _band_inputs(48, seed=1)
        p = 29

        def run(q, k, v):
            return np.asarray(attention.causal_attention(
                q, k, v, impl, window=BAND, **BAND_IMPLS[impl]))

        base = run(q, k, v)
        moved = run(q.at[:, p].add(1.0), k.at[:, p].add(1.0),
                    v.at[:, p].add(1.0))
        assert np.array_equal(moved[:, :p], base[:, :p])     # bit for bit
        assert not np.array_equal(moved[:, p], base[:, p])

    @pytest.mark.parametrize("impl", sorted(BAND_IMPLS))
    def test_the_key_a_window_behind_is_unseen_and_the_one_after_seen(
            self, impl):
        """Window 16: query 40 reads keys 25 .. 40. Key and value 24 (16
        behind) replaced: query 40 reads the same, bit for bit, query 39
        does not; key 25 (15 behind) replaced: query 40 changes."""
        q, k, v = _band_inputs(48, seed=2)

        def run(k, v):
            return np.asarray(attention.causal_attention(
                q, k, v, impl, window=BAND, **BAND_IMPLS[impl]))

        base = run(k, v)
        unseen = run(k.at[:, 24].add(3.0), v.at[:, 24].add(3.0))
        assert np.array_equal(unseen[:, 40:], base[:, 40:])
        assert not np.array_equal(unseen[:, 39], base[:, 39])
        seen = run(k.at[:, 25].add(3.0), v.at[:, 25].add(3.0))
        assert not np.array_equal(seen[:, 40], base[:, 40])
        assert np.array_equal(seen[:, 41:], base[:, 41:])

    def test_the_published_edge_4095_behind_seen_4096_behind_not(self):
        """The model's own window on the chunked tier (the CPU's): one
        head, 4200 positions; query 4199 reads key 104 (4095 behind) and
        not key 103."""
        rng = np.random.default_rng(5)
        q, k, v = (jnp.asarray(rng.normal(size=(1, 4200, 1, 8)), jnp.float32)
                   for _ in range(3))

        def last(k, v):
            return np.asarray(attention.causal_attention(
                q, k, v, "chunked", window=4096))[:, -1]

        base = last(k, v)
        assert np.array_equal(last(k.at[:, 103].add(9.0),
                                   v.at[:, 103].add(9.0)), base)
        assert not np.array_equal(last(k.at[:, 104].add(9.0),
                                       v.at[:, 104].add(9.0)), base)

    def test_the_chunked_tier_has_the_backward(self):
        q, k, v = _band_inputs(40, seed=4)

        def loss(tier):
            return lambda q, k, v: (attention.causal_attention(
                q, k, v, tier, window=BAND) ** 2).sum()

        got = jax.grad(loss("chunked"), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=2e-5)

    def test_bfloat16_heads_of_whole_lanes_in_place(self):
        """Heads of 128 channels, bfloat16, 7 query heads to a key/value
        head: the banded kernel reads them in place, counted so."""
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.normal(size=(1, 48, 7, 128)), jnp.bfloat16)
        k, v = (jnp.asarray(rng.normal(size=(1, 48, 1, 128)), jnp.bfloat16)
                for _ in range(2))
        before = _operands("swa", "in_place")
        got = attention.causal_attention(q, k, v, "flash", window=BAND,
                                         block_q=16, block_k=16,
                                         interpret=True)
        assert _operands("swa", "in_place") == before + 1
        want = _band_by_mask(q, k, v, BAND)
        assert np.abs(np.asarray(got, np.float64) - want).max() < 0.03

    def test_a_call_is_counted_by_window_and_tile_and_named_by_shape(self):
        x = jax.ShapeDtypeStruct((2, 16384, 28, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((2, 16384, 4, 128), jnp.bfloat16)
        before = (_window_calls(4096, "1024x1024"),
                  _flash_calls("1024x1024", True))

        def banded(q, k, v):
            return attention.causal_attention(q, k, v, "flash", window=4096)

        parts = attention.fold._edge_parts(1024, 1024, 5, 4096)
        split = _edge_parts_calls("1024x1024", parts)
        jaxpr = str(jax.make_jaxpr(banded)(x, kv, kv))
        assert "swa_attn_w4096" in jaxpr
        assert _window_calls(4096, "1024x1024") == before[0] + 1
        # and by the parts its edge tiles are folded in, beside the plain
        # causal calls
        assert parts > 1
        assert _edge_parts_calls("1024x1024", parts) == split + 1
        # the plain forward's counter counts what it counted: not this
        # call, and a row inside the window as ever
        assert _flash_calls("1024x1024", True) == before[1]
        short = [jax.ShapeDtypeStruct((2, 2048, h, 128), jnp.bfloat16)
                 for h in (28, 4, 4)]
        jax.eval_shape(banded, *short)
        assert _flash_calls("1024x1024", True) == before[1] + 1
        assert _window_calls(4096, "1024x1024") == before[0] + 1

    def test_the_band_is_five_key_blocks_a_query_block(self):
        """Tiles of 1024 over a window of 4096 (`band_tiles`): the grid's
        key axis is 5 blocks long whatever the row's length; a row of
        16384 visits 70 block pairs a head where the band holds 56, and
        28 of the 70 are edge tiles (16 on the diagonal, 12 on the
        trailing edge) of which the fold computes `edge_tile_share`."""
        assert attention.band_tiles(16384, 4096, jnp.bfloat16) == (1024,
                                                                   1024)
        assert attention.band_tiles(16384, 4096, jnp.float32) == (512, 512)
        assert attention.fold._band_steps(16384, 1024, 1024, 4096) == 5
        assert attention.fold._band_steps(8192, 1024, 1024, 4096) == 5
        computed, needed = attention.band_tile_pairs(16384, 4096, 1024, 1024)
        share = attention.fold.edge_tile_share(
            attention.fold._edge_parts(1024, 1024, 5, 4096))
        assert share < 1 and isinstance(computed, float)
        assert computed == 1 + 2 + 3 + 4 + 12 * 5 - (16 + 12) * (1 - share)
        assert computed == {0.75: 63.0, 0.625: 59.5}[share]
        assert needed == pytest.approx(
            (4096 * 4097 / 2 + 12288 * 4096) / 1024 ** 2)
        assert computed / needed < 1.126
        # tiles the fold leaves whole count whole: unequal ones, and equal
        # ones a window of 1100 does not divide
        assert attention.band_tile_pairs(16384, 4096, 1024, 512)[0] == 140
        assert attention.band_tile_pairs(16384, 1100, 1024, 1024)[0] == (
            1 + 2 + 14 * 3)
        # a window no multiple of 128 divides: the lengths' own tiles
        assert attention.band_tiles(4096, 1100, jnp.bfloat16) == (1024, 1024)

    def test_without_a_window_the_program_is_the_parents(self):
        """`window=None` is the call it was: the same jaxpr as the plain
        tier's own function, for every tier."""
        q, k, v = _band_inputs(40)
        for impl, plain in (
                ("dense", lambda q, k, v: dense_attention(
                    q, k, v, causal=True).astype(q.dtype)),
                ("chunked", lambda q, k, v: chunked_attention(
                    q, k, v, causal=True)),
                ("flash", lambda q, k, v: flash_attention(
                    q, k, v, causal=True, bwd_chunk=None))):
            got = jax.make_jaxpr(lambda q, k, v: attention.causal_attention(
                q, k, v, impl, window=None))(q, k, v)
            assert str(got) == str(jax.make_jaxpr(plain)(q, k, v)), impl


# --------------------------------------------------------------------- #
# an edge tile folded in parts (`_edge_parts`)                           #
# --------------------------------------------------------------------- #

PARTS = 2
EDGE_TILE = PARTS * attention.fold._PART_ROWS   # the smallest tile that splits
EDGE_LIMIT = 2e-6               # float32 against float32: the sums' order


def _whole_tiles(monkeypatch):
    """Every edge tile folded whole, as the parent folds it, for a
    comparison at EQUAL tiles: the rule is by shape and the program has no
    switch, so the test takes the rule away (and the jitted forwards'
    cached traces with it), as `_head_major` does."""
    monkeypatch.setattr(attention.fold, "_edge_parts", lambda *a, **kw: 1)
    jax.clear_caches()


def _kernel_equations(fn, *args) -> int:
    """The equations of the one Pallas kernel's body under `fn`."""
    (count,) = kernel_equations(jax.make_jaxpr(fn)(*args).jaxpr)
    return count


def _edge_parts_calls(tile: str, parts: int) -> float:
    return get_registry().counter(
        "mmlspark_tpu_attention_edge_parts_total",
        labels=("tile", "parts")).labels(tile=tile, parts=str(parts)).value


class TestEdgeTilesInParts:
    """Where the tiles are equal and a step is one of several, the
    diagonal's block and the band's trailing block are folded in parts
    along the queries, each against the keys its mask leaves: the same
    numbers as the whole tile masked, up to the order of a float32 row
    sum. Tiles of `EDGE_TILE`, the smallest that split, on the CPU's
    interpreted kernel; 2 rows unless said."""

    T = 2 * EDGE_TILE + EDGE_TILE // 2 + 24     # padded keys in an edge tile
    TILES = {"block_q": EDGE_TILE, "block_k": EDGE_TILE, "interpret": True}
    # a test-only tile choice the rule leaves whole: unequal tiles
    UNEQUAL = {"block_q": EDGE_TILE, "block_k": EDGE_TILE // 2,
               "interpret": True}

    def test_the_rule_by_shape(self):
        parts = attention.fold._edge_parts
        # the cells' tiles: plain causal, latent and the band of 4096
        assert parts(1024, 1024, 16) == parts(1024, 1024, 4) == PARTS
        assert parts(1024, 1024, 2) == PARTS              # rows of 2048
        assert parts(1024, 1024, 5, window=4096) == PARTS
        assert parts(EDGE_TILE, EDGE_TILE, 3) == PARTS
        # one tile a row; unequal tiles; a window the tile does not divide;
        # parts of fewer rows than a pass is worth, or of no lane blocks
        assert parts(1024, 1024, 1) == parts(512, 512, 1) == 1
        assert parts(1024, 512, 8) == parts(512, 1024, 4) == 1
        assert parts(640, 640, 2, window=1100) == 1
        assert parts(EDGE_TILE // 2, EDGE_TILE // 2, 4) == 1
        assert parts(8, 8, 4) == parts(640, 640, 2) == 1
        assert attention.fold.edge_tile_share(1) == 1.0
        assert attention.fold.edge_tile_share(2) == 0.75
        assert attention.fold.edge_tile_share(4) == 0.625

    @pytest.mark.parametrize("heads,d", [((2, 1), 64), ((2, 1), 128),
                                         ((4, 2), 8)])
    @pytest.mark.parametrize("window", [None, EDGE_TILE, 2 * EDGE_TILE])
    def test_the_split_fold_matches_the_mask_and_the_whole_tile(
            self, monkeypatch, window, heads, d):
        """The plain triangle and the band, grouped key heads, heads of 64
        and of 128 (in place), keys padded inside the last diagonal tile:
        against one masked softmax in float64, against the whole-tile fold
        at a test's unequal tiles, and against the whole-tile fold at the
        SAME tiles (the rule taken away)."""
        q, k, v = _band_inputs(self.T, heads, d, seed=7)
        steps = -(-self.T // EDGE_TILE) if window is None else (
            attention.fold._band_steps(self.T, EDGE_TILE, EDGE_TILE, window))
        assert attention.fold._edge_parts(EDGE_TILE, EDGE_TILE, steps,
                                     window) == PARTS

        def call(q, k, v, tiles=self.TILES):
            return attention.causal_attention(q, k, v, "flash",
                                              window=window, **tiles)

        split = np.asarray(call(q, k, v))
        program = str(jax.make_jaxpr(call)(q, k, v))
        want = _band_by_mask(q, k, v, window or self.T)
        assert np.abs(split - want).max() < EDGE_LIMIT
        assert np.abs(split - np.asarray(call(q, k, v, self.UNEQUAL))).max() \
            < EDGE_LIMIT
        _whole_tiles(monkeypatch)
        assert np.abs(split - np.asarray(call(q, k, v))).max() < EDGE_LIMIT
        # it did engage (the CPU may well sum a row to the same bits)
        assert str(jax.make_jaxpr(call)(q, k, v)) != program

    def test_the_latent_score_in_parts(self, monkeypatch):
        t = self.T
        operands = _latent_inputs(t, b=1, h=2, seed=8)

        def call(*operands, tiles=self.TILES):
            return attention.latent_attention(
                *operands, block_q=tiles["block_q"],
                block_k=tiles["block_k"], interpret=True)

        split = np.asarray(call(*operands))
        program = str(jax.make_jaxpr(call)(*operands))
        want = np.asarray(attention.causal_attention(
            *attention.latent._latent_concatenated(*operands), "dense"))
        assert np.abs(split - want).max() < 2e-5
        assert np.abs(split - np.asarray(
            call(*operands, tiles=self.UNEQUAL))).max() < EDGE_LIMIT
        _whole_tiles(monkeypatch)
        assert np.abs(split - np.asarray(call(*operands))).max() < EDGE_LIMIT
        assert str(jax.make_jaxpr(call)(*operands)) != program

    @pytest.mark.parametrize("window", [None, EDGE_TILE])
    def test_the_log_sum_exp_is_the_whole_tiles(self, monkeypatch, window):
        """What the backward reads: equal to float32 rounding, +inf on no
        row (every query sees itself)."""
        q, k, v = _band_inputs(self.T, (2, 1), 64, seed=9)

        def run():
            return attention.flash._flash_fwd_lse(
                q, k, v, True, EDGE_TILE, EDGE_TILE, True, window=window)

        out, lse = run()
        _whole_tiles(monkeypatch)
        out_whole, lse_whole = run()
        assert np.isfinite(np.asarray(lse)).all()
        np.testing.assert_allclose(lse, lse_whole, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(out, out_whole, atol=EDGE_LIMIT)

    def test_the_gradient_through_the_split_forward_is_unchanged(
            self, monkeypatch):
        q, k, v = _band_inputs(self.T, (2, 1), 64, seed=10)

        def grads(**tiles):
            return jax.grad(lambda q, k, v: (flash_attention(
                q, k, v, causal=True, bwd_chunk=128, **tiles) ** 2).sum(),
                argnums=(0, 1, 2))(q, k, v)

        split = grads(**self.TILES)
        dense = jax.grad(lambda q, k, v: (dense_attention(
            q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(dense, split):
            np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)
        _whole_tiles(monkeypatch)
        for a, b_ in zip(grads(**self.TILES), split):
            np.testing.assert_allclose(a, b_, atol=2e-6, rtol=1e-5)

    # where the edge tiles stay whole: one tile a row (the cells' rows of
    # 512 and 1024 tokens), unequal tiles, tiles under the threshold, a
    # window no multiple of 128 divides, and every non-causal call; the
    # equations are PR 41's kernels' at these shapes (49, 68, 120, 120,
    # 261, 243: 120 plain causal, 243 banded, 18 more where keys are
    # padded) and what PR 44's step adds to them: 1 where one tile holds
    # no running statistics (the log-sum-exp's scale), 3 to 49 elsewhere
    # (a fold's row sum by lanes, the maximum laid over the tile, the
    # exponent's constant twice, less the scale's pass; a second fold for
    # the `_row_parts` halves of a tile of 1024 rows that nothing masks);
    # PR 47's walk of a list takes 1 from a causal kernel of several key
    # blocks and 21 from a band's (`needed` and its conjunctions gone, three
    # offsets into the walk's table come), and 16 from a band of one tile
    # (its one key block is block 0: no first block to reckon)
    @pytest.mark.parametrize("case,t,window,tiles,equations", [
        ("one_tile", 512, None, {}, 50),
        ("one_tile_band", 1024, 512, {"block_q": 1024, "block_k": 1024},
         53),
        ("unequal", 2048, None, {"block_q": 1024, "block_k": 512}, 160),
        ("small", 512, None, {"block_q": 128, "block_k": 128}, 122),
        ("window_1100", 2200, 1100, {}, 267),
        ("band_unequal", 4096, 1024, {"block_q": 1024, "block_k": 512},
         271),
    ])
    def test_where_the_split_does_not_engage_the_program_is_the_parents(
            self, monkeypatch, case, t, window, tiles, equations):
        x = jax.ShapeDtypeStruct((1, t, 2, 128), jnp.bfloat16)

        def call(q, k, v):
            return attention.causal_attention(q, k, v, "flash",
                                              window=window, **tiles)

        with_rule = str(jax.make_jaxpr(call)(x, x, x))
        assert _kernel_equations(call, x, x, x) == equations
        _whole_tiles(monkeypatch)
        assert str(jax.make_jaxpr(call)(x, x, x)) == with_rule, case

    @pytest.mark.parametrize("causal", [False, True])
    def test_a_call_that_is_not_causal_or_one_tile_is_bit_for_bit(
            self, monkeypatch, causal):
        """Run, not only traced: two key blocks not causal (no edge tile),
        and one causal tile (`num_kv == 1`)."""
        t = 2 * EDGE_TILE if not causal else EDGE_TILE
        q, k, v = _band_inputs(t, (2, 1), 64, seed=11)

        def run():
            return np.asarray(flash_attention(
                q, k, v, causal=causal, block_q=EDGE_TILE,
                block_k=EDGE_TILE, interpret=True))

        with_rule = run()
        _whole_tiles(monkeypatch)
        assert np.array_equal(run(), with_rule)

    def test_a_traced_call_is_counted_by_tile_and_parts(self):
        x = jax.ShapeDtypeStruct((2, 4096, 4, 128), jnp.bfloat16)
        short = jax.ShapeDtypeStruct((2, 512, 4, 128), jnp.bfloat16)
        before = (_edge_parts_calls("1024x1024", PARTS),
                  _edge_parts_calls("512x512", 1))
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       x, x, x)
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       short, short, short)
        # not causal: no edge tile, not counted
        jax.eval_shape(flash_attention, x, x, x)
        assert _edge_parts_calls("1024x1024", PARTS) == before[0] + 1
        assert _edge_parts_calls("512x512", 1) == before[1] + 1


# --------------------------------------------------------------------- #
# the windowed-and-summarised kernel's edge tiles (`_eva_kernel`)        #
# --------------------------------------------------------------------- #

def _whole_blocks(monkeypatch):
    """`_whole_tiles`, and every block of summaries that ends past the ones
    seen folded whole, masked by column, as the parent folds it."""
    monkeypatch.setattr(attention.eva, "_edge_prefixes", lambda *a, **kw: ())
    _whole_tiles(monkeypatch)


class TestEvaEdgeTiles:
    """`_eva_kernel` folds an edge tile over what its mask leaves: the
    diagonal's tile in `_edge_parts` parts, and a block of summaries that
    ends past the ones its queries see over a key prefix
    (`_edge_prefixes`). Tiles of `EDGE_TILE`, the smallest that split, a
    window of one such tile with 128 summaries (chunks of 8), summaries in
    blocks of 512: the prefixes are 128, 256 and 384, and window w's
    queries see 128 x w. On the CPU's interpreted kernel; float32."""

    WINDOW, CHUNK = EDGE_TILE, EDGE_TILE // 128
    TILES = {"block_q": EDGE_TILE, "block_k": EDGE_TILE, "block_s": 512,
             "interpret": True}

    @pytest.fixture(autouse=True)
    def _no_trace_outlives_its_rule(self):
        """`_eva_flash` is jitted by itself: a trace made with the rule
        taken away would serve the next test of the same shapes."""
        yield
        jax.clear_caches()

    def call(self, q, k, v, phi, mu, **more):
        return eva_attention(q, k, v, phi, mu, self.WINDOW, self.CHUNK,
                             impl="flash", **self.TILES, **more)

    def test_the_rules_by_shape(self):
        prefixes = attention.eva._edge_prefixes
        # the cell's long rows, and this class's blocks
        assert prefixes(1024, 128) == (128, 256, 384, 512, 640, 768, 896)
        assert prefixes(512, 128) == (128, 256, 384)
        # a block that ends with a window's share (the cell's rows of 4096);
        # shares of no lane blocks; a block of broken shares; the tests'
        assert prefixes(128, 128) == prefixes(1024, 64) == ()
        assert prefixes(1000, 128) == prefixes(24, 8) == prefixes(3, 8) == ()
        # the diagonal's parts are `_flash_fold`'s rule: two key blocks of
        # the window and two of summaries are four steps
        assert attention.fold._edge_parts(1024, 1024, 4, 2048) == PARTS
        assert attention.fold._edge_parts(512, 512, 5, 2048) == 1

    # the last window sees 128 x `windows` summaries: a last block of
    # summaries that is an edge in EACH prefix class (1, 2, 3; 5: the
    # second block's first), and one that ends where they do (4)
    @pytest.mark.parametrize("windows", [1, 2, 3, 4, 5])
    def test_the_cut_edges_match_the_masks_and_the_whole_tiles(
            self, monkeypatch, windows):
        t = windows * self.WINDOW + EDGE_TILE // 2 + 24
        operands = _eva_inputs(t, b=1, h=2, seed=windows)
        want, _kbar, _vbar = _eva_by_masks(*operands, self.WINDOW,
                                           self.CHUNK)
        cut = np.asarray(self.call(*operands))
        program = str(jax.make_jaxpr(self.call)(*operands))
        assert np.abs(cut - want).max() < EVA_LIMIT
        _whole_blocks(monkeypatch)
        assert np.abs(cut - np.asarray(self.call(*operands))).max() \
            < EDGE_LIMIT
        # it did engage (the CPU may well sum a row to the same bits)
        assert str(jax.make_jaxpr(self.call)(*operands)) != program

    def test_heads_of_whole_lanes_read_in_place(self, monkeypatch):
        t = 3 * self.WINDOW
        operands = _eva_inputs(t, b=1, h=1, d=128, seed=6)
        before = _operands("eva", "in_place")
        cut = np.asarray(self.call(*operands))
        assert _operands("eva", "in_place") == before + 1
        want, _kbar, _vbar = _eva_by_masks(*operands, self.WINDOW,
                                           self.CHUNK)
        assert np.abs(cut - want).max() < EVA_LIMIT
        _whole_blocks(monkeypatch)
        assert np.abs(cut - np.asarray(self.call(*operands))).max() \
            < EDGE_LIMIT

    # what a start lowers: the pooling's body and the attention's, at the
    # cell's three batches (PR 42's: 193 and 149; PR 43's: 396 and 181;
    # PR 44's lane-dense step, and every fold that nothing masks in two
    # row halves, the seven prefixes among them: 730 and 263) and at this
    # class's tiles; a later prefix class or part shows here first
    @pytest.mark.parametrize("rows,t,window,chunk,tiles,equations", [
        (2, 32768, 2048, 16, {}, [43, 730]),
        (2, 4096, 2048, 16, {}, [43, 263]),
        (1, 4096, 2048, 16, {}, [43, 263]),
        (1, 5 * EDGE_TILE, EDGE_TILE, EDGE_TILE // 128,
         {"block_q": EDGE_TILE, "block_k": EDGE_TILE, "block_s": 512},
         [43, 454]),
    ])
    def test_the_kernels_equations_are_what_a_start_was_budgeted(
            self, monkeypatch, rows, t, window, chunk, tiles, equations):
        x = jax.ShapeDtypeStruct((rows, t, 2, 128), jnp.bfloat16)
        vec = jax.ShapeDtypeStruct((2, 128), jnp.float32)

        def call(q, k, v, phi, mu):
            return eva_attention(q, k, v, phi, mu, window, chunk, **tiles)

        assert kernel_equations(
            jax.make_jaxpr(call)(x, x, x, vec, vec).jaxpr) == equations
        _whole_blocks(monkeypatch)
        whole = kernel_equations(
            jax.make_jaxpr(call)(x, x, x, vec, vec).jaxpr)
        assert whole[0] == equations[0] and whole[1] < equations[1]

    @pytest.mark.parametrize("planted", ["diagonal", "summaries"])
    def test_what_the_mask_erases_is_not_read(self, monkeypatch, planted):
        """NaN where a mask erases: keys and values in the upper corner of
        a diagonal tile (the LAST window's, which nobody pools), summaries
        past the prefix that a window sees. A masked entry's weight is 0,
        and 0 x NaN in the values' product is NaN: the whole tile lets it
        through, the cut one never multiplies it."""
        t = 4 * self.WINDOW
        q, k, v, phi, mu = _eva_inputs(t, b=1, h=2, seed=12)
        summaries = eva_summaries(k, v, phi, mu, self.CHUNK)
        nan = jnp.nan
        if planted == "diagonal":
            # the second half of the last tile's keys: its first half of
            # queries lies above them
            dirty = slice(t - EDGE_TILE // 2, t)
            k, v = k.at[:, dirty].set(nan), v.at[:, dirty].set(nan)
            clean = slice(0, t - EDGE_TILE // 2)
        else:
            # window 2's summaries, 256 .. 383: windows 1 and 2 see 128
            # and 256 of the block they lie in, window 3 sees them
            summaries = tuple(x.at[:, 256:384].set(nan) for x in summaries)
            clean = slice(0, 3 * self.WINDOW)

        def run():
            return np.asarray(self.call(q, k, v, None, None,
                                        summaries=summaries))

        out = run()
        assert np.isfinite(out[:, clean]).all()
        assert np.isnan(out[:, clean.stop:]).all()
        _whole_blocks(monkeypatch)
        assert np.isnan(run()[:, clean]).any()

    def test_a_traced_call_is_counted_with_its_parts_and_prefixes(self):
        vec = jax.ShapeDtypeStruct((2, 128), jnp.float32)

        def trace(t):
            x = jax.ShapeDtypeStruct((1, t, 2, 128), jnp.bfloat16)
            jax.eval_shape(
                lambda q, k, v, phi, mu: eva_attention(q, k, v, phi, mu,
                                                       2048, 16),
                x, x, x, vec, vec)

        def counts():
            return (_eva_calls(2048, 16, "1024x1024x1024", prefixes=7),
                    _eva_calls(2048, 16, "1024x1024x128"),
                    _edge_parts_calls("1024x1024", PARTS))

        before = counts()
        trace(32768)
        assert counts() == (before[0] + 1, before[1], before[2] + 1)
        # a block of summaries that ends with a window's share has no edge
        trace(4096)
        assert counts() == (before[0] + 1, before[1] + 1, before[2] + 2)


def _eva_pairs_by_masks(t, window, chunk) -> int:
    """The (query, key) and (query, summary) pairs the two masks leave."""
    pos = np.arange(t)
    start = (pos // window) * window
    local = (pos[None, :] <= pos[:, None]) & (pos[None, :] >= start[:, None])
    remote = ((np.arange(t // chunk)[None, :] + 1) * chunk
              <= start[:, None])
    return int(local.sum() + remote.sum())


class TestEvaTilePairs:
    """`eva_tile_pairs`: what the kernel computes against what the masks
    leave, counted without a chip."""

    CELL = (2048, 16, 1024, 1024)

    def test_the_cells_long_rows(self, monkeypatch):
        computed, needed = attention.eva_tile_pairs(32768, *self.CELL, 1024)
        # 32 diagonal tiles at 3/4, 16 whole; 16 whole blocks of
        # summaries and 28 prefixes of 128 .. 896 columns
        assert computed == 24 + 16 + 16 + 14
        assert needed == pytest.approx(62, abs=0.02)
        monkeypatch.setattr(attention.eva, "_edge_prefixes", lambda *a: ())
        assert attention.eva_tile_pairs(32768, *self.CELL, 1024)[0] == 84
        monkeypatch.setattr(attention.fold, "_edge_parts", lambda *a, **kw: 1)
        assert attention.eva_tile_pairs(32768, *self.CELL, 1024) == (
            92, needed)

    def test_the_cells_rows_of_4096(self, monkeypatch):
        # two windows: 4 diagonal tiles of 6, one block of 128 summaries
        # for each of the second window's two query blocks
        computed, needed = attention.eva_tile_pairs(4096, *self.CELL, 128)
        assert computed == 3 + 2 + 2 * 128 / 1024
        assert needed == pytest.approx(4.25, abs=0.01)
        monkeypatch.setattr(attention.fold, "_edge_parts", lambda *a, **kw: 1)
        assert attention.eva_tile_pairs(4096, *self.CELL, 128)[0] == 6.25

    @pytest.mark.parametrize("t,tiles", [
        (96, (32, 32, 16)), (70, (16, 16, 16)), (80, (32, 16, 8)),
        (96, (16, 32, 24)), (70, (8, 8, 3))])
    def test_needed_is_the_masks_and_computed_covers_it(self, t, tiles):
        computed, needed = attention.eva_tile_pairs(t, EVA_WINDOW,
                                                    EVA_CHUNK, *tiles)
        assert needed * tiles[0] * tiles[1] == _eva_pairs_by_masks(
            t, EVA_WINDOW, EVA_CHUNK)
        assert computed >= needed


# --------------------------------------------------------------------- #
# the fold's statistics kept lane-dense, the scale in the exponent, an   #
# unmasked tile in two row halves (`_fold_tile`, `_weigh`, `_row_parts`) #
# --------------------------------------------------------------------- #

def _whole_rows(monkeypatch):
    """Every unmasked tile folded whole: `_whole_tiles`' way of taking a
    rule by shape away for a comparison at EQUAL tiles."""
    monkeypatch.setattr(attention.fold, "_row_parts", lambda block_q: 1)
    jax.clear_caches()


def _fold_rows_calls(kernel: str, rows: str) -> float:
    return get_registry().counter(
        "mmlspark_tpu_attention_fold_rows_total",
        labels=("kernel", "rows")).labels(kernel=kernel, rows=rows).value


def _scaled_scores(q, k, causal: bool, tk: int | None = None):
    """(B, H, Tq, Tk) float64 scores over sqrt(D), -inf where a key is
    ahead of its query (causal) or at or past `tk` (padding)."""
    q, k = (np.asarray(x, np.float64) for x in (q, k))
    k = np.repeat(k, q.shape[2] // k.shape[2], 2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    qpos, kpos = np.arange(q.shape[1])[:, None], np.arange(k.shape[1])[None]
    seen = kpos < (k.shape[1] if tk is None else tk)
    if causal:
        seen = seen & (kpos <= qpos)
    return np.where(seen, s, -np.inf)


def _log_sum_exp(s):
    m = s.max(-1, keepdims=True)
    return (m + np.log(np.exp(s - m).sum(-1, keepdims=True)))[..., 0]


class TestLaneDenseFold:
    """The running maximum and sum of a fold are (block_q, 128): the
    maximum of the RAW products replicated across the lanes, the sum as 128
    per-lane partial sums that the finalisation adds up; the weights are
    exp2((s - m) x scale x log2 e). Tiles of 128 and 256 (whole lane
    blocks, several steps a row) on the CPU's interpreted kernel, float32
    inputs; today's tolerances."""

    def test_the_rules_by_shape(self):
        lanes, parts = attention.fold._stat_lanes, attention.fold._row_parts
        # every tile the rule chooses past 128 keys; EvaByte's two sources
        assert lanes(1024) == lanes(512) == lanes(640) == lanes(128) == 128
        assert lanes(1024, 1024) == lanes(1024, 128) == 128
        # a test's small tiles: the widest block that divides them
        assert lanes(8) == 8 and lanes(64) == 64 and lanes(192) == 64
        assert lanes(32, 16) == 16 and lanes(200) == 8
        # an unmasked tile of 1024 in two halves of 512 rows; tiles of 512
        # and under (float32 inputs, short rows) whole
        assert parts(1024) == 2
        assert parts(512) == parts(640) == parts(128) == parts(8) == 1

    @pytest.mark.parametrize("heads,d", [((4, 2), 128), ((4, 1), 64)])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("tile", [128, 256])
    def test_the_plain_fold_matches_dense(self, heads, d, causal, tile):
        """Heads of 128 in place and of 64 head-major, grouped key heads,
        3 to 5 steps a row, keys padded inside the last block."""
        rng = np.random.default_rng(d + tile)
        q, k, v = (jnp.asarray(rng.normal(size=(1, 600, h, d)), jnp.float32)
                   for h in (heads[0], heads[1], heads[1]))
        got = flash_attention(q, k, v, causal=causal, block_q=tile,
                              block_k=tile, interpret=True)
        np.testing.assert_allclose(
            got, dense_attention(q, k, v, causal=causal), atol=2e-5,
            rtol=2e-5)

    @pytest.mark.parametrize("window", [128, 200, 384])
    def test_the_banded_fold_matches_the_mask(self, window):
        q, k, v = _band_inputs(600, (4, 2), 128, seed=window)
        got = attention.causal_attention(
            q, k, v, "flash", window=window, block_q=128, block_k=128,
            interpret=True)
        assert np.abs(np.asarray(got) - _band_by_mask(q, k, v, window)
                      ).max() < 2e-5

    def test_the_latent_fold_matches_dense(self):
        operands = _latent_inputs(600, b=1, h=2, seed=3)
        got = attention.latent_attention(*operands, block_q=128, block_k=256,
                                         interpret=True)
        want = attention.causal_attention(
            *attention.latent._latent_concatenated(*operands), "dense")
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("d", [128, 8])
    def test_the_windowed_and_summarised_fold_matches_the_masks(self, d):
        """Two sources in one running maximum: keys in blocks of 128,
        summaries in blocks of 128 (a window of 256 positions, 32 a
        window)."""
        t, window, chunk = 900, 256, 8
        operands = _eva_inputs(t, b=1, h=2, d=d, seed=4)
        want, _kbar, _vbar = _eva_by_masks(*operands, window, chunk)
        got = eva_attention(*operands, window, chunk, impl="flash",
                            block_q=128, block_k=128, block_s=128,
                            interpret=True)
        assert np.abs(np.asarray(got) - want).max() < EVA_LIMIT

    @pytest.mark.parametrize("kind", ["plain", "banded", "latent", "eva"])
    def test_two_row_halves_are_the_whole_tiles_fold_bit_for_bit(
            self, monkeypatch, kind):
        """A tile of 1024 that nothing masks is folded as two halves of
        512 rows: every row against the same keys in the same order, so
        the same bits (the CPU's product sums a row's channels alike at
        512 and at 1024 rows). Whole tiles of keys: a padded length masks
        its keys in every step."""
        t = 3 * EDGE_TILE
        if kind == "latent":
            operands = _latent_inputs(t, b=1, h=2, seed=5)

            def call():
                return attention.latent_attention(
                    *operands, block_q=EDGE_TILE, block_k=EDGE_TILE,
                    interpret=True)
        elif kind == "eva":
            operands = _eva_inputs(3 * EDGE_TILE + 24, b=1, h=2, seed=5)

            def call():
                return eva_attention(
                    *operands, EDGE_TILE, EDGE_TILE // 128, impl="flash",
                    block_q=EDGE_TILE, block_k=EDGE_TILE, block_s=512,
                    interpret=True)
        else:
            q, k, v = _band_inputs(t, (2, 1), 64, seed=5)

            def call():
                return attention.causal_attention(
                    q, k, v, "flash", block_q=EDGE_TILE, block_k=EDGE_TILE,
                    window=EDGE_TILE if kind == "banded" else None,
                    interpret=True)

        halves = np.asarray(call())
        program = str(jax.make_jaxpr(call)())
        _whole_rows(monkeypatch)
        assert np.array_equal(np.asarray(call()), halves)
        # it did engage
        assert str(jax.make_jaxpr(call)()) != program
        jax.clear_caches()      # the forwards jitted by themselves

    @pytest.mark.parametrize("steps", [1, 2])
    def test_a_row_of_padding_is_zero_and_a_padded_block_sums_real_keys(
            self, steps):
        """`_flash_fold` told that NO key is real (every row empty: output
        0, log-sum-exp +inf, the backward's exp(s - lse) = 0) and that the
        LAST key block holds 3 real keys (a row sums those and no
        padding), at one key block a row and at two."""
        block, d = 128, 64
        tk = steps * block
        rng = np.random.default_rng(steps)
        q, k, v = (jnp.asarray(rng.normal(size=(1, block, 1, d)),
                               jnp.float32),
                   *(jnp.asarray(rng.normal(size=(1, tk, 1, d)), jnp.float32)
                     for _ in range(2)))
        at = attention.layout._block_at(False, 1)

        def fold(real):
            return attention.fold._flash_call(
                attention.flash._flash_kernel, [(q[:, :, 0], d, at)],
                [(k[:, :, 0], d, at)], (v[:, :, 0], d, at), at,
                jax.ShapeDtypeStruct((1, block, d), jnp.float32), b=1, h=1,
                tk=real, causal=False, scale=d ** -0.5, block_q=block,
                block_k=block, interpret=True)

        out, lse = fold(0)
        assert np.array_equal(np.asarray(out), np.zeros((1, block, d)))
        assert np.isposinf(np.asarray(lse)).all()
        real = tk - block + 3
        out, lse = fold(real)
        s = _scaled_scores(q, k, False, tk=real)
        np.testing.assert_allclose(lse[0, :, 0], _log_sum_exp(s)[0, 0],
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(
            out[:, :, None], dense_attention(q, k[:, :real], v[:, :real]),
            atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_one_tile_and_two_of_half_the_size_agree(self, causal):
        """`num_kv == 1` holds no running statistics and takes the step's
        exponent: a row as one tile of 256 keys and as two of 128 agree to
        float32 rounding, outputs and log-sum-exp."""
        q, k, v = _qkv(1, 256, 256, 2, 64, seed=6)
        one = attention.flash._flash_fwd_lse(q, k, v, causal, 256, 256, True)
        two = attention.flash._flash_fwd_lse(q, k, v, causal, 256, 128, True)
        np.testing.assert_allclose(one[0], two[0], atol=EDGE_LIMIT)
        np.testing.assert_allclose(one[1], two[1], rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("tile", [128, 640])
    def test_the_log_sum_exp_is_of_the_scaled_scores(self, tile):
        """What the backward reads, m x scale + log l with m the RAW
        maximum, against the log-sum-exp of the scores over sqrt(D) in
        float64: over five steps, and over one."""
        q, k, v = _qkv(1, 600, 600, 2, 128, seed=7)
        _out, lse = attention.flash._flash_fwd_lse(q, k, v, True, tile, tile,
                                                   True)
        np.testing.assert_allclose(
            lse, _log_sum_exp(_scaled_scores(q, k, True)), rtol=1e-6,
            atol=2e-6)

    def test_the_gradients_through_the_lane_dense_forward_are_denses(self):
        """The backward is the XLA recomputation from the kernel's
        log-sum-exp: grouped heads of 128, three steps a row."""
        q, k, v = _grouped_qkv(1, 384, 4, 2, 128, seed=8)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        dense = jax.grad(loss(lambda q, k, v: dense_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        flash = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(dense, flash):
            np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)

    def test_a_traced_fold_is_counted_by_kernel_and_row_parts(self):
        """Once a shape, where the call is traced: tiles of 1024 (inputs
        of 2 bytes) report two parts of 512 rows, float32 inputs (tiles of
        512) one, one tile a row the whole tile."""
        def counts():
            return {key: _fold_rows_calls(*key) for key in [
                ("flash", "512x2"), ("flash", "512x1"), ("flash", "1024x1"),
                ("swa", "512x2"), ("mla", "512x2"), ("eva", "512x2")]}

        def x(t, dtype=jnp.bfloat16, d=128):
            return jax.ShapeDtypeStruct((1, t, 2, d), dtype)

        before = counts()
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       x(4096), x(4096), x(4096))
        jax.eval_shape(flash_attention, x(2048), x(2048), x(2048))
        jax.eval_shape(flash_attention, *[x(2048, jnp.float32)] * 3)
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                       x(1024), x(1024), x(1024))
        jax.eval_shape(lambda q, k, v: attention.causal_attention(
            q, k, v, "flash", window=2048), x(4096), x(4096), x(4096))
        jax.eval_shape(
            attention.latent_attention, x(2048), x(2048, d=64),
            x(2048, d=256), jax.ShapeDtypeStruct((1, 2048, 64), jnp.bfloat16))
        vec = jax.ShapeDtypeStruct((2, 128), jnp.float32)
        jax.eval_shape(lambda q, k, v, phi, mu: eva_attention(
            q, k, v, phi, mu, 2048, 16), x(4096), x(4096), x(4096), vec, vec)
        after = counts()
        assert {key: after[key] - before[key] for key in after} == {
            ("flash", "512x2"): 2, ("flash", "512x1"): 1,
            ("flash", "1024x1"): 1, ("swa", "512x2"): 1,
            ("mla", "512x2"): 1, ("eva", "512x2"): 1}


STEP_TILE = 128                 # whole lane blocks: the statistics lane-dense
# the forwards `TestFoldSteps` runs: channels a head (the latent one's are
# `_latent_inputs`')
STEP_KINDS = {
    "plain": 128, "heads_of_64": 64, "band_of_tiles": 128, "band_of_200": 64,
    "latent": None}


def _grid_steps(kernel: str) -> tuple:
    counter = get_registry().counter(
        "mmlspark_tpu_flash_grid_steps_total", labels=("kernel", "kind"))
    return tuple(counter.labels(kernel=kernel, kind=kind).value
                 for kind in ("visited", "square"))


def _by_one_softmax(q, k, v, window=None):
    """(out (B, T, H, Dv), log-sum-exp (B, H, T)) of causal attention as
    ONE softmax over masked float64 scores, a band where told."""
    s = _scaled_scores(q, k, True)
    if window is not None:
        pos = np.arange(s.shape[-1])
        s = np.where(pos[:, None] - pos[None, :] < window, s, -np.inf)
    v = np.repeat(np.asarray(v, np.float64), q.shape[2] // v.shape[2], 2)
    lse = _log_sum_exp(s)
    return np.einsum("bhqk,bkhd->bqhd", np.exp(s - lse[..., None]), v), lse


class TestFoldSteps:
    """A forward of several key blocks walks the LIST of the (query block,
    key block) pairs that fold something, read from a scalar-prefetch
    operand, not the square: the folds of a query block and their order
    are what they were, so are the numbers. Tiles of `STEP_TILE` on the
    CPU's interpreted kernel, float32 inputs; today's tolerances."""

    @pytest.mark.parametrize("shape,steps", [
        ((16384, 1024, 1024, None), 136), ((8192, 1024, 1024, None), 36),
        ((32768, 1024, 1024, None), 528), ((4096, 1024, 1024, None), 10),
        ((2048, 1024, 1024, None), 3), ((1024, 1024, 1024, None), 1),
        ((16384, 1024, 1024, 4096), 70), ((16384, 1024, 1024, 1024), 31),
        # tiles that do not divide the length, unequal tiles, a window no
        # tile divides
        ((2600, 1024, 1024, None), 6), ((600, 128, 256, None), 9),
        ((600, 256, 128, None), 11), ((2600, 640, 640, 1100), 12),
        ((600, 128, 128, 200), 12)])
    def test_the_list_is_the_pairs_that_fold_in_todays_order(self, shape,
                                                             steps):
        t, block_q, block_k, window = shape
        pairs = attention.fold._fold_steps(t, t, block_q, block_k, True,
                                           window)
        assert len(pairs) == steps
        assert list(pairs) == sorted(pairs)  # query blocks, then key blocks
        nq, nk = -(-t // block_q), -(-t // block_k)
        for qi in range(nq):
            mine = [kv for q_, kv in pairs if q_ == qi]
            # from the first block the mask leaves to the diagonal's
            first = 0 if window is None else (
                max(qi * block_q - window + 1, 0) // block_k)
            last = min((qi * block_q + block_q - 1) // block_k, nk - 1)
            assert mine == list(range(first, last + 1))
        if window is not None:
            # what `band_tile_pairs` visits (an edge tile whole: its share
            # of a tile taken back out)
            share = attention.fold.edge_tile_share(attention.fold._edge_parts(
                block_q, block_k,
                attention.fold._band_steps(t, block_q, block_k, window),
                window))
            computed, _needed = attention.flash.band_tile_pairs(
                t, window, block_q, block_k)
            if share == 1.0:
                assert len(pairs) == computed
            assert len(pairs) <= nq * attention.fold._band_steps(
                t, block_q, block_k, window)

    def test_no_mask_is_the_whole_rectangle(self):
        pairs = attention.fold._fold_steps(600, 900, 128, 256, False)
        assert list(pairs) == [
            (qi, kv) for qi in range(5) for kv in range(4)]

    # (the latent forward's heads share ONE rotary key, no key head)
    @pytest.mark.parametrize("kind,grouped", [
        (kind, grouped) for kind in STEP_KINDS for grouped in (False, True)
        if not (kind == "latent" and grouped)])
    @pytest.mark.parametrize("padded", [False, True])
    @pytest.mark.parametrize("blocks", [1, 2, 3, 5])
    def test_the_walked_fold_matches_one_softmax(self, kind, grouped, blocks,
                                                 padded):
        """The plain causal forward (heads of 128 in place, heads of 64
        head-major), the band (a window of whole tiles, four of them at
        five key blocks as 4096 is of 1024; a window of 200, which no
        multiple of 128 divides) and the latent forward at 1, 2, 3 and 5
        key blocks, the keys padded inside the last block or not, grouped
        key heads or not: output and log-sum-exp."""
        t = blocks * STEP_TILE - (37 if padded else 0)
        tiles = (STEP_TILE, STEP_TILE, True)
        window = {"band_of_tiles": STEP_TILE * (4 if blocks == 5 else 1),
                  "band_of_200": 200}.get(kind)
        if kind == "latent":
            operands = _latent_inputs(t, b=1, h=2, seed=blocks)
            got = attention.latent._latent_fwd_lse(*operands, *tiles)
            q, k, v = attention.latent._latent_concatenated(*operands)
        else:
            q, k, v = _grouped_qkv(1, t, 4, 2 if grouped else 1,
                                   STEP_KINDS[kind], seed=blocks)
            got = attention.flash._flash_fwd_lse(q, k, v, True, *tiles,
                                                 window=window)
        out, lse = _by_one_softmax(q, k, v, window)
        np.testing.assert_allclose(got[0], out, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got[1], lse, rtol=1e-6, atol=2e-6)

    def test_a_band_of_4096_in_tiles_of_1024_matches_the_dense_tier(self):
        """The cell's own window and tiles at five key blocks, the keys
        padded inside the last: 15 of 25 pairs, the edge tiles in parts."""
        t, window = 5 * 1024 - 40, 4096
        q, k, v = _band_inputs(t, (2, 1), 64, seed=9)
        q, k, v = q[:1], k[:1], v[:1]
        got = attention.causal_attention(q, k, v, "flash", window=window,
                                         block_q=1024, block_k=1024,
                                         interpret=True)
        want = attention.causal_attention(q, k, v, "dense", window=window)
        assert len(attention.fold._fold_steps(t, t, 1024, 1024, True,
                                              window)) == 15
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("call,prefetched", [
        ("not causal one tile", 0), ("one tile", 0), ("one key block", 0),
        ("not causal", 1), ("causal", 1), ("banded", 1), ("latent", 1),
        ("latent one tile", 0)])
    def test_only_a_call_of_several_key_blocks_brings_the_walk(
            self, call, prefetched):
        """A call of ONE key block (the encoder's 512 tokens, a decoder's
        row of one tile) is the parent's: a grid of (row, head, query
        block, 1) and no operand beside the arrays. Every call of several
        key blocks takes ONE operand more, the walk (-1, the steps' query
        blocks, -1, their key blocks), and a grid of (row, head, step): a
        causal call, a band and the latent forward of the pairs their
        masks leave, a call without a mask of its whole rectangle."""
        x = jax.ShapeDtypeStruct((2, 600, 2, 128), jnp.float32)
        tiles = {"block_q": 128, "block_k": 128, "interpret": True}
        if call.startswith("latent"):
            one = call.endswith("one tile")
            t = 128 if one else 600
            shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
                (1, t, 2, 128), (1, t, 2, 64), (1, t, 2, 256), (1, t, 64))]
            eqn = _pallas_equation(
                lambda *o: attention.latent_attention(
                    *o, block_q=128, block_k=128, interpret=True), *shapes)
            arrays, steps = 5, (1 if one else 15)      # kv twice: k and v
        else:
            if call.endswith("one tile"):
                tiles.update(block_q=640, block_k=640)
            if call == "one key block":
                tiles.update(block_k=640)
            if call == "banded":
                fn = lambda q, k, v: attention.causal_attention(  # noqa: E731
                    q, k, v, "flash", window=200, **tiles)
            else:
                fn = lambda q, k, v: flash_attention(             # noqa: E731
                    q, k, v, causal=not call.startswith("not causal"),
                    **tiles)
            eqn = _pallas_equation(fn, x, x, x)
            arrays = 3
            steps = {"causal": 15, "banded": 12, "not causal": 25}.get(call)
        mapping = eqn.params["grid_mapping"]
        assert mapping.num_index_operands == prefetched
        assert len(eqn.invars) == arrays + prefetched
        if prefetched:
            assert mapping.grid[2:] == (steps,)
            walk = eqn.invars[0].aval
            assert walk.shape == (2 * steps + 2,) and walk.dtype == jnp.int32
        else:
            assert mapping.grid[3:] == (1,)

    @pytest.mark.parametrize("window", [1, 2, 129])
    def test_a_band_of_one_key_block_a_query_block_still_walks(self, window):
        """A window of ONE key: every query block reads its own diagonal
        block and no other, so the most a query block reads is one block
        (no scratch, the one-tile step) of several: the walk names it."""
        t = 3 * STEP_TILE
        q, k, v = _grouped_qkv(1, t, 2, 1, 128, seed=window)
        got = attention.flash._flash_fwd_lse(
            q, k, v, True, STEP_TILE, STEP_TILE, True, window=window)
        out, lse = _by_one_softmax(q, k, v, window)
        np.testing.assert_allclose(got[0], out, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got[1], lse, rtol=1e-6, atol=2e-6)

    @pytest.mark.parametrize("blocks", [(2, 3), (3, 2), (5, 5)])
    @pytest.mark.parametrize("padded", [False, True])
    def test_the_walked_rectangle_matches_one_softmax(self, blocks, padded):
        """A forward without a mask of several key blocks walks its whole
        rectangle from the list: queries and keys of different lengths, the
        keys padded inside the last block or not."""
        tq, tk = (n * STEP_TILE for n in blocks)
        tk -= 37 if padded else 0
        q, _k, _v = _grouped_qkv(1, tq, 4, 2, 128, seed=tq)
        _q, k, v = _grouped_qkv(1, tk, 4, 2, 128, seed=tk + 1)
        got = attention.flash._flash_fwd_lse(q, k, v, False, STEP_TILE,
                                             STEP_TILE, True)
        want = attention.dense_attention(
            q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2))
        np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)

    def test_a_traced_forward_counts_the_steps_it_walks(self):
        """Once a traced call, beside the edge parts' counter: the steps a
        head of a row visits and those of the square (a band: of its
        rectangle), by kernel."""
        def x(t, d=128, h=2):
            return jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16)

        kernels = ("gqa", "attn", "swa", "mla")
        before = {kernel: _grid_steps(kernel) for kernel in kernels}
        for t in (16384, 1024):
            jax.eval_shape(lambda q, k, v: attention.causal_attention(
                q, k, v, "flash"), x(t), x(t), x(t))
        jax.eval_shape(flash_attention, x(512), x(512), x(512))
        jax.eval_shape(lambda q, k, v: attention.causal_attention(
            q, k, v, "flash", window=4096), x(16384), x(16384), x(16384))
        jax.eval_shape(
            attention.latent_attention, x(4096), x(4096, 64), x(4096, 256),
            jax.ShapeDtypeStruct((1, 4096, 64), jnp.bfloat16))
        after = {kernel: _grid_steps(kernel) for kernel in kernels}
        assert {kernel: tuple(a - b for a, b in zip(after[kernel],
                                                    before[kernel]))
                for kernel in kernels} == {
            "gqa": (136 + 1, 256 + 1), "attn": (1, 1), "swa": (70, 80),
            "mla": (10, 16)}
