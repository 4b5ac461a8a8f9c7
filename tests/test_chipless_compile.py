"""The kernels of the two decoder families compiled for a DESCRIBED
TPU v5e at the benchmark cells' own widths: the chip's compiler runs here
without a chip, and refuses what interpret mode lets through (a block not
aligned to the tiling, more fast memory than a kernel may use). Nothing
runs, so nothing here is a time or a result.

The topology is described inside a fixture (never at import: only one
process at a time may load the TPU's library, and a test file is imported
by every worker), and every such test lives in this one file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("length,dtype", [
    (4096, jnp.bfloat16), (512, jnp.bfloat16),
    # float32 inputs: a tile of 1024 passes the scoped VMEM (refused on the
    # chip, PR 27), so the rule stops at 512 there
    (4096, jnp.float32)])
def test_latent_attention_kernel_compiles(one_chip, length, dtype):
    """192 channels for scores, 128 for values, causal with the block
    skip, 8 rows x 16 heads, at the tiles `flash_attention` chooses."""
    from mmlspark_tpu.nn.attention import flash_attention

    q = jax.ShapeDtypeStruct((8, length, 16, 192), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((8, length, 16, 128), dtype, sharding=one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, v)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("length,dtype", [
    (512, jnp.bfloat16), (128, jnp.bfloat16), (512, jnp.float32),
    # the configuration's `max_len`: a tile of 640, one key block, masked
    (514, jnp.bfloat16)])
def test_encoder_attention_kernel_compiles(one_chip, length, dtype):
    """`xlmr_xxl.score_table`'s calls: 32 rows x 32 heads x 128 channels,
    not causal, at the tiles `flash_attention` chooses (one step a row and
    head at every length here)."""
    from mmlspark_tpu.nn.attention import flash_attention

    q = jax.ShapeDtypeStruct((32, length, 32, 128), dtype, sharding=one_chip)
    compiled = _compile(flash_attention, q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


EXPERT_LAYERS = {
    # tokens a batch, routed, held, a token's picks, the experts' width:
    # the five buffers of the two decoder cells
    "moonlight_8x4096": (8 * 4096, 64, 16, 6, 1408),
    "moonlight_8x512": (8 * 512, 64, 16, 6, 1408),
    "lfm2_2x16384": (2 * 16384, 32, 8, 4, 1792),
    "lfm2_2x1024": (2 * 1024, 32, 8, 4, 1792),
    "lfm2_1x1024": (1024, 32, 8, 4, 1792),
    # every expert held: no switch, and the combine reads the experts' rows
    # in two groups of 32
    "all_64_held": (8 * 512, 64, 64, 6, 1408),
}


@pytest.mark.parametrize("case", list(EXPERT_LAYERS))
def test_dropless_expert_layer_compiles_to_grouped_kernels(
        one_chip, case, monkeypatch):
    """The expert layer of `moonlight_16b_a3b.score_loglik` and of
    `lfm2_8b_a1b.score_long_docs` at every buffer the cells have: both
    grouped products are the Pallas calls, named as the benchmark's readers
    select them (`ragged-dot*`), each result with the buffer's rows as its
    first extent; the weights are read where they lie (no (held, d, 2w)
    array) and the hidden values are written once ((rows, w), never
    (rows, 2w)); the combine is the Pallas call (its VMEM and its table in
    SMEM fit); all in both branches of the buffer-size switch; no array of
    one row a pick, (k, T, d), is left in either."""
    import re

    from mmlspark_tpu.parallel.moe import (dropless_buffer_rows,
                                           moe_ffn_dropless)

    tokens, routed, held, top_k, w = EXPERT_LAYERS[case]
    # the layer asks the backend whether its kernels can run: here the
    # chip is described, not attached, so the test answers for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda x, router, bias, gate, up, down: moe_ffn_dropless(
            x, router, bias, gate, up, down, n_routed_experts=routed,
            experts_held=(0, held), top_k=top_k, scaling=2.446,
            dtype=jnp.bfloat16),
        spec(tokens, 2048), spec(2048, routed), spec(routed),
        spec(held, 2048, w), spec(held, 2048, w), spec(held, w, 2048))
    text = compiled.as_text()
    whole = tokens * top_k
    buffers = sorted({dropless_buffer_rows(tokens, top_k, held, routed),
                      whole})
    assert ("conditional" in text) == (len(buffers) == 2)
    assert text.count("ragged-dot") >= 2 * len(buffers)
    for rows in buffers:
        assert re.search(rf"%ragged-dot-gated[.\d]* = bf16\[{rows},{w}\]",
                         text)
        assert re.search(rf"%ragged-dot-down[.\d]* = bf16\[{rows},2048\]",
                         text)
        assert f"[{rows},{2 * w}]" not in text
    assert f"[{held},2048,{2 * w}]" not in text
    assert text.count("moe_combine") >= len(buffers)
    assert f"[{top_k},{tokens},2048]" not in text
    assert f"[{tokens},{top_k},2048]" not in text


@pytest.mark.parametrize("rows,length", [(2, 32768), (2, 4096), (1, 4096)])
def test_windowed_and_summarised_attention_kernels_compile(one_chip, rows,
                                                           length):
    """`evabyte_6_5b.score_byte_docs`'s three batches: 32 heads of 128
    channels, a window of 2048 and chunks of 16, bfloat16, at the tiles
    the rule chooses: two Pallas calls, the pooling and the attention,
    inside the default scoped VMEM."""
    from mmlspark_tpu.nn.attention import eva_attention

    q = jax.ShapeDtypeStruct((rows, length, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    phi = jax.ShapeDtypeStruct((32, 128), jnp.float32, sharding=one_chip)
    text = _compile(
        lambda q, k, v, phi, mu: eva_attention(q, k, v, phi, mu, 2048, 16),
        q, q, q, phi, phi).as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "eva_attn_w2048c16" in text and "eva_pool_w2048c16" in text
