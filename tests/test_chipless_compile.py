"""The kernels of the two decoder families compiled for a DESCRIBED
TPU v5e at the benchmark cells' own widths: the chip's compiler runs here
without a chip, and refuses what interpret mode lets through (a block not
aligned to the tiling, more fast memory than a kernel may use). Nothing
runs, so nothing here is a time or a result.

The topology is described inside a fixture (never at import: only one
process at a time may load the TPU's library, and a test file is imported
by every worker), and every such test lives in this one file."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import kernel_equations
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
    # no array of T x T or T x T / 16 (told apart from the hidden width,
    # 4096, at the long rows alone)
    if length == 32768:
        for found in re.findall(r"\[([\d,]+)\]", text):
            extents = [int(n) for n in found.split(",")]
            assert extents.count(length) + extents.count(length // 16) < 2


@pytest.mark.parametrize("length,name", [
    (16384, "swa_attn_w4096"),
    # a row inside the window takes the plain causal forward, named by the
    # scope around it
    (2048, "swa_attn_7")])
def test_sliding_window_attention_kernel_compiles(one_chip, length, name):
    """`smallthinker_21b_a3b.score_mixed_context`'s two batches: 28 query
    heads over 4 key/value heads of 128 channels, bfloat16, a window of
    4096 at the tiles the rule chooses (1024 x 1024: five key blocks a
    query block), inside the default scoped VMEM; q, k and v read in place
    as (B, T, heads x 128)."""
    from mmlspark_tpu.nn.attention import causal_attention

    def spec(heads):
        return jax.ShapeDtypeStruct((2, length, heads * 128), jnp.bfloat16,
                                    sharding=one_chip)

    def attend(q, k, v):
        with jax.named_scope("swa_attn_7"):
            return causal_attention(
                q.reshape(2, length, 28, 128), k.reshape(2, length, 4, 128),
                v.reshape(2, length, 4, 128), "flash",
                window=4096).reshape(2, length, 28 * 128)

    text = _compile(attend, spec(28), spec(4), spec(4)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(rf"%{name}[.\d]* = ", text)
    entry = text[text.index("ENTRY"):]
    assert not re.search(r" (copy|transpose)\(", entry)


@pytest.mark.parametrize("rows,length,heads,key_heads,width", [
    # `smallthinker_21b_a3b.score_mixed_context`'s global layers, in place
    (2, 16384, 28, 4, 128),
    # `ouro_2_6b.score_reasoning_traces`' long batch, in place
    (2, 8192, 16, 16, 128),
    # `lfm2_8b_a1b.score_long_docs`' long batch: heads of 64, head-major
    (2, 16384, 32, 8, 64)])
def test_plain_causal_fold_compiles_with_lane_dense_statistics(
        one_chip, rows, length, heads, key_heads, width):
    """The plain forward over many steps a row at the cells' widths, tiles
    of 1024: the running maximum and sum as (1024, 128) float32 scratch
    beside the accumulator (as (1024, 1) columns they were padded to as
    much), an unmasked tile in two row halves, inside the default 16 MB of
    scoped VMEM (the chip's compiler refuses a kernel past it). The
    banded, latent and windowed-and-summarised folds share the step and
    are compiled above at their cells' shapes."""
    from mmlspark_tpu.nn.attention import flash, flash_attention

    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((rows, length, heads, width), bf,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((rows, length, key_heads, width), bf,
                             sharding=one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True)

    # a step of several query heads holds a set of statistics a head
    a_step = flash._heads_a_step(q, k, k, 1024, 1024)
    assert a_step == heads // key_heads
    stat = "1024,128" if a_step == 1 else f"{a_step},1024,128"
    kernel = str(jax.make_jaxpr(attend)(q, k, k))
    assert kernel.count(f"Ref<vmem>{{f32[{stat}]}}") >= (
        3 if width == 128 else 2)
    assert "Ref<vmem>{f32[1024,1]}" not in kernel
    assert "tpu_custom_call" in _compile(attend, q, k, k).as_text()


def test_a_groups_query_heads_lower_in_one_grid_step_inside_the_scoped_vmem(
        one_chip):
    """`lfm2_8b_a1b.score_long_docs`' long batch (2 x 16384, 32 query heads
    over 8 key/value heads of 64, head-major) at the rule's heads a step,
    a key head's whole group of 4: the grid is (row, 8, the 136 steps that
    fold), a step's q, output and lse blocks are 4 rows of axis 0, its key
    and value blocks ONE head's, and the chip's compiler takes the kernel
    inside the scoped VMEM the call states (`lanes.STEP_VMEM`, 32 MB: a
    whole group passes the 16 MB a call gets without asking by 0.45 MB).
    The counter says what the rule chose."""
    from mmlspark_tpu.nn import attention
    from mmlspark_tpu.observability.metrics import get_registry

    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((2, 16384, 32, 64), bf, sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 16384, 8, 64), bf, sharding=one_chip)

    def attend(q, k, v):
        return attention.flash_attention(q, k, v, causal=True)

    counter = get_registry().counter(
        "mmlspark_tpu_flash_heads_a_step_total",
        labels=("kernel", "group", "heads")).labels(
            kernel="gqa", group="4", heads="4")
    before = counter.value
    (call,) = [e for e in _deep_equations(jax.make_jaxpr(attend)(q, k, k))
               if e.primitive.name == "pallas_call"]
    assert counter.value == before + 1
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (2, 8, 136)
    assert [tuple(x.block_size for x in m.block_shape)
            for m in mapping.block_mappings] == [
        (4, 1024, 64), (1, 1024, 64), (1, 1024, 64), (4, 1024, 64),
        (4, 1024, 1)]
    stated = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert stated == attention.fold.STEP_VMEM == 32 << 20
    assert attention.fold._step_bytes(4, 1024, 1024, 64, 64, 2) <= stated
    assert "tpu_custom_call" in _compile(attend, q, k, k).as_text()


@pytest.mark.parametrize("heads,key_heads,width,dtype,length,window,a_step", [
    # a padded tail (3000 in tiles of 1024: the key mask's tile on top) at
    # the three grouped cells' heads, and the band
    (32, 8, 64, jnp.bfloat16, 3000, None, 4),
    (28, 4, 128, jnp.bfloat16, 3000, None, 7),
    (20, 4, 128, jnp.bfloat16, 3000, None, 5),
    (28, 4, 128, jnp.bfloat16, 9000, 1024, 7),
    # float32 heads of 256 in tiles of 512: a whole group of 8 was refused
    # by 0.2 MB at a count of two tiles a step; three hold it to 4
    (16, 2, 256, jnp.float32, 3000, None, 4),
    # a group of 16: its largest divisor that fits
    (16, 1, 128, jnp.bfloat16, 5000, None, 4)])
def test_the_rule_of_heads_a_step_stays_inside_the_vmem_the_call_states(
        one_chip, heads, key_heads, width, dtype, length, window, a_step):
    """`fold.heads_a_step` is a count made here, not the compiler's: where
    it errs it must err towards fewer heads, because a kernel past the
    scoped VMEM its call states is refused at a start. The shapes where the
    count came closest, compiled for the chip."""
    from mmlspark_tpu.nn import attention

    q = jax.ShapeDtypeStruct((1, length, heads, width), dtype,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, length, key_heads, width), dtype,
                             sharding=one_chip)
    tile = (attention.flash.band_tiles(length, window, dtype) if window
            else attention.flash_tiles(length, length, dtype))
    assert attention.flash._heads_a_step(q, k, k, *tile) == a_step
    compiled = _compile(lambda q, k, v: attention.causal_attention(
        q, k, v, "flash", window=window), q, k, k)
    assert "tpu_custom_call" in compiled.as_text()


def _deep_equations(closed):
    """Every equation under a traced program, however deep."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)

    return list(walk(closed.jaxpr))


@pytest.mark.parametrize("tokens", [2 * 16384, 2 * 2048])
def test_relu_expert_layer_routed_early_compiles_to_grouped_kernels(
        one_chip, tokens, monkeypatch):
    """The expert layer of `smallthinker_21b_a3b.score_mixed_context` at
    its two batches: 16 of 64 experts of width 768 on a hidden width of
    2560, 6 picks a token made by a router that reads ANOTHER input
    (softmax over the picked logits), the gate's activation ReLU: both
    grouped products are the Pallas calls under the names the accepted
    readers select, each result with the buffer's rows as its first
    extent, in both branches of the buffer-size switch; the combine is the
    Pallas call."""
    from mmlspark_tpu.parallel.moe import (dropless_buffer_rows,
                                           moe_ffn_dropless, route_top_k)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(y, a, router, gate, up, down):
        routed = route_top_k(a, router, None, 6, scoring="softmax")
        return moe_ffn_dropless(
            y, None, None, gate, up, down, n_routed_experts=64,
            experts_held=(0, 16), top_k=6, dtype=jnp.bfloat16,
            activation="relu", routed=routed)

    text = _compile(
        layer, spec(tokens, 2560), spec(tokens, 2560),
        spec(2560, 64, dtype=jnp.float32), spec(16, 2560, 768),
        spec(16, 2560, 768), spec(16, 768, 2560)).as_text()
    assert "conditional" in text
    for rows in (dropless_buffer_rows(tokens, 6, 16, 64), tokens * 6):
        assert re.search(rf"%ragged-dot-gated[.\d]* = bf16\[{rows},768\]",
                         text)
        assert re.search(rf"%ragged-dot-down[.\d]* = bf16\[{rows},2560\]",
                         text)
        assert f"[{rows},1536]" not in text
    assert "[16,2560,1536]" not in text
    assert text.count("moe_combine") >= 2


def _entry_operations(text: str):
    """(operation, result's type, whether it is computed from the
    argument `y`) of every instruction of the optimised program's entry
    computation: what follows from `y` is an activation, the rest are
    weights and tables."""
    entry = text[text.index("ENTRY"):]
    found, from_y = [], set()
    for line in entry.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)",
            line)
        if not m:
            continue
        name, kind_, op, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        if re.fullmatch(r"y(\.\d+)?", name) or from_y & set(operands):
            from_y.add(name)
        found.append((op, kind_, name in from_y))
    return found


LAYERS = {
    # module, its input's width, (rows, length) of a cell's batch
    "eva_2x32768": ("eva", 4096, (2, 32768)),
    "eva_1x4096": ("eva", 4096, (1, 4096)),
    "latent_8x4096": ("latent", 2048, (8, 4096)),
    "latent_8x512": ("latent", 2048, (8, 512)),
    # TWO layers: with the walk's tables as four operands of the call the
    # compiler wrote the second layer's q_nope and kv positions-minor and
    # copied both before the kernel (PR 47: 0.6 ms a long layer on the chip)
    "latent_8x4096_two_layers": ("latent", 2048, (8, 4096), 2),
    "encoder_32x512": ("encoder", 4096, (32, 512)),
    "encoder_32x128": ("encoder", 4096, (32, 128)),
    # `smallthinker_21b_a3b.score_mixed_context`: 28 query heads over 4
    # key/value heads of 128 on a hidden width of 2560
    "sliding_2x16384": ("sliding", 2560, (2, 16384)),
    "sliding_2x2048": ("sliding", 2560, (2, 2048)),
    "global_2x16384": ("global", 2560, (2, 16384)),
    "sliding_2x16384_two_layers": ("sliding", 2560, (2, 16384), 2),
    "global_2x16384_two_layers": ("global", 2560, (2, 16384), 2),
    # `lfm2_8b_a1b.score_long_docs`: 32 query heads over 8 key/value heads
    # of 64 on a hidden width of 2048, head-major, a grid step two query
    # heads of a group (PR 48); two layers, for what an operand's new shape
    # can move from the second layer on (PR 47)
    "lfm2_2x16384": ("lfm2", 2048, (2, 16384)),
    "lfm2_2x16384_two_layers": ("lfm2", 2048, (2, 16384), 2),
}

# what is left, by the traces of PR 35 (PERF.md section 5): at ONE row of
# 4096 the compiler contracts the output projection's (heads, width) as
# two dimensions and copies the kernel's output heads-in-sublanes first,
# 0.1 ms a layer (0.03% of EvaByte's call); at two rows it does not
KNOWN_MOVES = {"eva_1x4096": [("copy", "bf16[512,8,32,128]")]}
# heads of 64 are no whole lane blocks: q is copied head-major in and the
# output back, a layer (ROADMAP D17); the parent's program holds the same
_HEAD_MAJOR = [("copy", "bf16[2,32,16384,64]")] * 2
KNOWN_MOVES["lfm2_2x16384"] = _HEAD_MAJOR
KNOWN_MOVES["lfm2_2x16384_two_layers"] = 2 * _HEAD_MAJOR


def _attention_layer(kind: str, i: int = 0):
    """A cell's attention module at its published widths, bfloat16."""
    from mmlspark_tpu.nn import attention, models

    bf = jnp.bfloat16
    if kind == "eva":
        return models.EvaAttention(num_heads=32, dtype=bf,
                                   name=f"eva_attn_{i}")
    if kind in ("sliding", "global"):
        sliding = kind == "sliding"
        return models.GroupedQueryAttention(
            28, 4, 1.5e6, 1e-6, "flash", bf, head_dim=128, qk_norm=False,
            rotary=sliding, window=4096 if sliding else None,
            name=f"swa_attn_{i}" if sliding else f"gqa_attn_{i}")
    if kind == "latent":
        return models.LatentAttention(
            num_heads=16, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, rope_theta=50000.0,
            dtype=bf, name=f"mla_attn_{i}")
    if kind in GROUPED:
        heads, key_heads, head = GROUPED[kind]
        return models.GroupedQueryAttention(
            heads, key_heads, 1e6, 1e-6, "flash", bf, head_dim=head,
            name=f"gqa_attn_{i}")
    return attention.SelfAttention(num_heads=32, dtype=bf, impl="flash",
                                   name=f"attn_{i}")


# the other three decoder cells' grouped-query layers: query heads, key
# heads, channels a head (`lfm2_8b_a1b`, `ouro_2_6b`, `falcon_h1_34b`)
GROUPED = {"lfm2": (32, 8, 64), "ouro": (16, 16, 128),
           "falcon": (20, 4, 128)}


def _layers_for_the_chip(one_chip, monkeypatch, kind, width, rows, length,
                         layers=1):
    """(function of (params, y), their shapes on the described chip) for
    `layers` attention layers of a cell in a row, each with its residual
    sum, projections to output projection."""
    from flax import linen as nn

    from mmlspark_tpu.nn import attention, models

    # the package asks the backend which tier runs; here it is the CPU's
    monkeypatch.setattr(attention.layout.jax, "default_backend",
                        lambda: "tpu")

    class Stack(nn.Module):
        @nn.compact
        def __call__(self, y):
            for i in range(layers):
                y = y + _attention_layer(kind, i)(y)
            return y

    stack = Stack()
    bf = jnp.bfloat16
    y = jax.ShapeDtypeStruct((rows, length, width), bf, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(
            p.shape, bf if p.ndim == 3 or p.shape[-1] > 128 else p.dtype,
            sharding=one_chip),
        jax.eval_shape(lambda: stack.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, width), bf))))
    return (lambda p, y: stack.apply(p, y)), params, y


@pytest.mark.parametrize("case", list(LAYERS))
def test_no_activation_is_laid_out_again_around_the_kernels(
        one_chip, monkeypatch, case):
    """ONE attention layer of a cell, projections to output projection,
    compiled for the chip: between the product fusions and the Pallas
    calls the optimised program holds no copy, transpose or reshape of an
    array with the batch's extents (PERF.md, PR 34: a projection to four
    dimensions is written positions-minor and copied; XLA's rotary too)."""
    kind, width, (rows, length), *layers = LAYERS[case]
    text = _compile(*_layers_for_the_chip(one_chip, monkeypatch, kind, width,
                                          rows, length, *layers)).as_text()
    assert "tpu_custom_call" in text

    # an activation at least as large as the smallest the kernels read:
    # latent attention's rotary query channels, 1024 a position (the ONE
    # rotary key, 64 a position, is not)
    def large(kind_):
        return any(
            math.prod(int(n) for n in found.split(","))
            >= rows * length * 1024
            for found in re.findall(r"\[([\d,]+)\]", kind_))

    moved = [(op, kind_.split("{")[0])
             for op, kind_, activation in _entry_operations(text)
             if op in ("copy", "transpose", "reshape") and activation
             and large(kind_)]
    assert moved == KNOWN_MOVES.get(case, []), moved


@pytest.mark.parametrize("kind,width,rows,length,kernel,visited,square", [
    # the five decoder cells' long batches: the triangle of a square, the
    # band of its rectangle
    ("lfm2", 2048, 2, 16384, "gqa", 136, 256),
    ("global", 2560, 2, 16384, "gqa", 136, 256),
    ("sliding", 2560, 2, 16384, "swa", 70, 80),
    ("ouro", 2048, 2, 8192, "gqa", 36, 64),
    ("falcon", 5120, 1, 32768, "gqa", 528, 1024),
    ("latent", 2048, 8, 4096, "mla", 10, 16),
    # their short ones: rows of 2048 are three steps of four, a sliding
    # layer's row inside its window is plain causal; one tile is one step
    ("global", 2560, 2, 2048, "gqa", 3, 4),
    ("sliding", 2560, 2, 2048, "gqa", 3, 4),
    ("falcon", 5120, 1, 2048, "gqa", 3, 4),
    ("lfm2", 2048, 2, 1024, "gqa", 1, 1),
    ("ouro", 2048, 2, 1024, "gqa", 1, 1),
    ("latent", 2048, 8, 512, "mla", 1, 1),
    # the encoder's rectangle is all steps that compute
    ("encoder", 4096, 32, 512, "attn", 1, 1),
    ("encoder", 4096, 32, 128, "attn", 1, 1)])
def test_a_causal_forward_of_several_key_blocks_walks_fewer_steps(
        one_chip, monkeypatch, kind, width, rows, length, kernel, visited,
        square):
    """`mmlspark_tpu_flash_grid_steps_total` where a cell's attention
    layer is traced at the cell's own batch: a head of a row visits fewer
    steps than the square (the band's rectangle) holds in every causal
    kernel of several key blocks, and every one of them where the list is
    the rectangle (one tile a row; the encoder). Traced only."""
    from mmlspark_tpu.observability.metrics import get_registry

    counter = get_registry().counter(
        "mmlspark_tpu_flash_grid_steps_total", labels=("kernel", "kind"))

    def read():
        return {(k, kind_): counter.labels(kernel=k, kind=kind_).value
                for k in ("gqa", "swa", "mla", "attn")
                for kind_ in ("visited", "square")}

    # the parameters' shapes come from a trace of their own, at 8 tokens
    layer = _layers_for_the_chip(one_chip, monkeypatch, kind, width, rows,
                                 length)
    before = read()
    jax.eval_shape(*layer)
    moved = {key: value - before[key] for key, value in read().items()
             if value != before[key]}
    assert moved == {(kernel, "visited"): visited, (kernel, "square"): square}
    assert (visited < square) == (length > 1024)


@pytest.mark.parametrize("kind,width,rows,length,bodies", [
    # rotary (q and k share it), the pooling, the attention
    ("eva", 4096, 2, 32768, 3), ("eva", 4096, 1, 4096, 3),
    # rotary of the queries' rotary channels, the latent forward
    ("latent", 2048, 8, 4096, 2), ("latent", 2048, 8, 512, 2),
    # rotary (q's 28 heads and k's 4 are two shapes), the banded forward
    ("sliding", 2560, 2, 16384, 3)])
def test_a_kernel_is_lowered_once_a_shape_not_once_a_layer(
        one_chip, monkeypatch, kind, width, rows, length, bodies):
    """The guard PR 34 lacked (its rotary call was lowered once a tensor,
    layer and shape: 66 times in EvaByte's cell, 4 s of every warm start):
    the lowered text of THREE layers holds as many Pallas bodies as that
    of ONE, every Pallas call of these families being jitted by itself.
    Traced and lowered only: nothing is compiled."""
    def pallas_bodies(layers):
        fn, params, y = _layers_for_the_chip(
            one_chip, monkeypatch, kind, width, rows, length, layers)
        return jax.jit(fn).lower(params, y).as_text().count(
            "tpu_custom_call")

    assert pallas_bodies(1) == bodies
    assert pallas_bodies(3) == bodies


# (rows, tokens, query heads, key heads, channels, window), the parent's
# count at PR 43, the budget. A cell lowers the plain fold once a LAYER
# and shape (96 kernels in `ouro_2_6b.score_reasoning_traces`, 24 in
# `smallthinker_21b_a3b.score_mixed_context`, 18 in
# `lfm2_8b_a1b.score_long_docs`), at 0.3 to 0.65 ms an equation on the
# chip's host (0.83 trace and lowering together, PR 41). PR 41's edge
# tiles in two parts added 27 to each kernel where they engage; PR 44's
# step (the statistics lane-dense, the scale in the exponent, an
# unmasked tile as two row halves, a fold written out for each) adds 57
# to 61 more to a long rows' plain kernel (ISSUE 44's budget: 60; 18 ms
# a kernel traced and lowered on the chip's host, PERF.md section 6),
# and 1 where one tile holds no running statistics. The banded and the
# latent forward are jitted by themselves, lowered once a SHAPE: 69 and
# 61 more, once
FOLDS = {
    # a grid step a key head's whole group of query heads (PR 48): ONE
    # traced body under a loop over the step's heads, three to six
    # equations (the step read once, the loop, a head's place in the
    # step's blocks); written out a head it was 825 and 1,415
    "global_2x16384": ((2, 16384, 28, 4, 128, None), 147, 211),
    "short_rows_2x2048": ((2, 2048, 28, 4, 128, None), 147, 211),
    "lfm2_2x16384": ((2, 16384, 32, 8, 64, None), 147, 211),
    "banded_2x16384": ((2, 16384, 28, 4, 128, 4096), 256, 330),
    # one tile a row: the parent's kernel and the log-sum-exp's scale
    "lfm2_2x1024": ((2, 1024, 32, 8, 64, None), 50, 50),
    "encoder_32x512": ((32, 512, 32, 32, 128, "not causal"), 35, 35),
}


def _fold_jaxpr(case):
    """The traced program of a cell's flash forward (`FOLDS`' shapes and
    the two latent ones), as `make_jaxpr` prints it."""
    from mmlspark_tpu.nn import attention

    bf = jnp.bfloat16
    if case.startswith("latent"):
        t = int(case.split("x")[1])
        return jax.make_jaxpr(attention.latent_attention)(
            jax.ShapeDtypeStruct((8, t, 16, 128), bf),
            jax.ShapeDtypeStruct((8, t, 16, 64), bf),
            jax.ShapeDtypeStruct((8, t, 16, 256), bf),
            jax.ShapeDtypeStruct((8, t, 64), bf))
    (rows, t, heads, key_heads, d, window), _was, _limit = FOLDS[case]
    q = jax.ShapeDtypeStruct((rows, t, heads, d), bf)
    k = jax.ShapeDtypeStruct((rows, t, key_heads, d), bf)
    if window == "not causal":
        return jax.make_jaxpr(attention.flash_attention)(q, k, k)
    return jax.make_jaxpr(lambda q, k, v: attention.causal_attention(
        q, k, v, "flash", window=window))(q, k, k)


@pytest.mark.parametrize("case", [*FOLDS, "latent_8x4096", "latent_8x512"])
def test_the_folds_equations_stay_inside_what_a_start_was_budgeted(case):
    """A later edit cannot buy speed with a start unseen: the body of
    every flash forward the cells lower, counted at the cells' shapes
    (traced on the CPU, nothing lowered), stays within its budget (the
    parent's count and 60), and is the parent's own and one where one tile
    a row holds no running statistics."""
    if case.startswith("latent"):
        was, limit = (159, 225) if case.endswith("4096") else (54, 54)
    else:
        _shape, was, limit = FOLDS[case]
    jaxpr = _fold_jaxpr(case)
    (equations,) = kernel_equations(jaxpr.jaxpr)
    assert was <= equations <= limit


def _parents_step(s, ok, v_ref, scratch, exponent, rows=None, keys=None):
    """The step as PRs 41 and 43 held it (`_fold_tile` until PR 44), for
    the lane-dense scratch to carry: the scores SCALED in a pass of their
    own, exp of the difference, the running maximum and sum one value a
    row (the maximum, of the scaled scores, in every lane; the sum in lane
    0, zeros beside it, so that the finalisation's sum across lanes is
    it). The outputs are the parent's; the log-sum-exp is not compared
    (the finalisation scales this maximum again)."""
    import jax.experimental.pallas as pl
    from mmlspark_tpu.nn.attention.fold import _block

    m_sc, l_sc, acc_sc = scratch
    mine = ... if rows is None else (pl.ds(*rows), slice(None))
    s = s * (exponent / math.log2(math.e))
    m_prev = m_sc[mine][:, :1]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if ok is not None:
        p = jnp.where(ok, p, 0.0)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), _block(v_ref, keys),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    first_lane = jax.lax.broadcasted_iota(
        jnp.int32, m_sc[mine].shape, 1) == 0
    l_sc[mine] = l_sc[mine] * corr + jnp.where(
        first_lane, p.sum(-1, keepdims=True), 0.0)
    acc_sc[mine] = acc_sc[mine] * corr + pv
    m_sc[mine] = jnp.broadcast_to(m_new, m_sc[mine].shape)


def _small_fold(case):
    """A cell's flash forward (`FOLDS`' and the two latent ones) cut to
    what the CPU's interpreted kernel runs: one row, two query heads (the
    cell's width, its grouping where it has one, its window) and three of
    the rule's tiles (one where the cell has one), float32 values in the
    cell's bfloat16 so that the tiles are the cell's 1024."""
    from mmlspark_tpu.nn import attention

    rng = np.random.default_rng(len(case))

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    if case.startswith("latent"):
        t = 3072 if case.endswith("4096") else 512
        operands = (normal(1, t, 2, 128), normal(1, t, 2, 64),
                    normal(1, t, 2, 256), normal(1, t, 64))
        return lambda: attention.latent_attention(*operands, interpret=True)
    (_rows, tokens, heads, key_heads, d, window), _was, _limit = FOLDS[case]
    t = 3072 if tokens > 2048 else tokens
    group = heads // key_heads
    q = normal(1, t, 2, d)
    k, v = (normal(1, t, 2 // min(group, 2), d) for _ in range(2))
    if window == "not causal":
        return lambda: attention.flash_attention(q, k, v, interpret=True)
    window = None if window is None else 1024
    return lambda: attention.causal_attention(q, k, v, "flash",
                                              window=window, interpret=True)


@pytest.mark.parametrize("case", [*FOLDS, "latent_8x4096", "latent_8x512"])
def test_the_lane_dense_step_gives_the_column_steps_outputs(
        monkeypatch, case):
    """Until PR 44 this test held the folds' traced programs to the
    parent's WORD FOR WORD (`_fold_tile` lifted out of `_flash_fold`). PR 44
    changes the step itself (the statistics lane-dense, the scale in the
    exponent, the row sum by lanes first), so what is held now is the
    NUMBERS: every cell's fold, cut to three tiles on the CPU's interpreted
    kernel, against the same fold with the step as the parent wrote it, to
    float32 rounding carried through bfloat16 outputs (one tile a row runs
    no step: the same program twice, kept for the count)."""
    from mmlspark_tpu.nn import attention

    call = _small_fold(case)
    lane_dense = np.asarray(call().astype(jnp.float32))
    monkeypatch.setattr(attention.fold, "_fold_tile", _parents_step)
    jax.clear_caches()          # the forwards jitted by themselves
    columns = np.asarray(call().astype(jnp.float32))
    jax.clear_caches()
    assert np.isfinite(lane_dense).all()
    # bfloat16's last digit at most, and that in few places
    assert np.abs(lane_dense - columns).max() <= 2.0 ** -7
    assert (lane_dense != columns).mean() < 0.01


def _sar_shapes(one_chip, users=69878, items=10677):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (spec((users, items)), spec((items, items)),
            spec((users, items), jnp.bool_), spec((), jnp.int32))


@pytest.mark.parametrize("rows", [
    3328,       # every block of `sar_recommend_all`'s pass, the last too
    246])       # a table of fewer users than a block: its one short block
def test_sar_selection_kernel_compiles_and_takes_the_top_k_call(
        one_chip, monkeypatch, rows):
    """(rows, 10677) float32 scores, k = 10: the selection alone compiles
    inside the default scoped VMEM (no limit is asked for) under its name,
    and the whole `_block_topk_unseen` program, rows cut, product, mask
    and selection, holds the kernel and no `TopK` custom call."""
    from mmlspark_tpu.recommendation import sar, topk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shapes = _sar_shapes(one_chip)
    items = shapes[1].shape[0]
    assert topk.kernel_takes(rows, items, 10, jnp.float32)
    alone = _compile(lambda s: topk.top_k_rows(s, 10),
                     jax.ShapeDtypeStruct((rows, items), jnp.float32,
                                          sharding=one_chip)).as_text()
    assert "tpu_custom_call" in alone and "sar_topk_k10" in alone
    try:
        text = _compile(
            lambda a, s, seen, start: sar._block_topk_unseen(
                a, s, seen, start, rows, 10), *shapes).as_text()
    finally:
        sar._block_topk_unseen.clear_cache()
    assert "sar_topk_k10" in text
    assert 'custom_call_target="TopK"' not in text
    # the kernel reads the scores where the product, still ending in the
    # mask, writes them, columns-major: nothing lays them out again
    entry = text[text.index("ENTRY"):]
    assert re.search(rf"f32\[{items},{rows}\]\S* fusion\(.*kind=kOutput",
                     entry)
    assert not re.search(rf"\[({rows},{items}|{items},{rows})\]\S* "
                         r"(copy|transpose)\(", entry)


def test_a_pass_lowers_one_selection_body_its_last_block_included(
        one_chip, monkeypatch):
    """69,878 users under 4096 a block: `recommend_for_all_users` asks the
    block program for ONE shape, 21 blocks of 3328 rows (17 of 4096 would
    leave 246: a second program; 18 of 4096 compute 3,850 rows twice), the
    last block's cut starting ten rows early, and the lowered text of a
    program that holds a block and that last one holds ONE `sar_topk_k10`
    body, the call being jitted by itself. Traced and lowered only:
    nothing is compiled."""
    from mmlspark_tpu.recommendation import SARModel, sar

    affinity, similarity, seen, start = _sar_shapes(one_chip)
    asked = []

    def recorded(a, s, seen_, start, rows, k):
        asked.append((int(start), rows, k))
        return jnp.zeros((rows, k)), jnp.zeros((rows, k), jnp.int32)

    model = SARModel()
    model.user_affinity = affinity          # the loop reads its shape
    model._device_cache = {"affinity": affinity, "similarity": similarity,
                           "seen": seen}
    monkeypatch.setattr(sar, "_block_topk_unseen", recorded)
    model.recommend_for_all_users(10)
    assert len(asked) == 21 and {a[1:] for a in asked} == {(3328, 10)}
    assert [a[0] for a in asked[-2:]] == [19 * 3328, 69878 - 3328]
    monkeypatch.undo()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        text = jax.jit(lambda a, s, seen_, whole, last: (
            sar._block_topk_unseen(a, s, seen_, whole, 3328, 10),
            sar._block_topk_unseen(a, s, seen_, last, 3328, 10))
        ).lower(affinity, similarity, seen, start, start).as_text()
    finally:
        sar._block_topk_unseen.clear_cache()
    assert text.count("tpu_custom_call") == 1
    assert "sar_topk_k10" in text


# --------------------------------------------------------------------- #
# the looped stack (`ouro_2_6b.score_reasoning_traces`)                 #
# --------------------------------------------------------------------- #

def _looped_for_the_chip(one_chip, monkeypatch, steps, rows, length,
                         layers=2):
    """(function of (variables, ids), their shapes on the described chip)
    for `layers` layers of the cell's model at its published widths,
    bfloat16, run `steps` times, embedding to log-probabilities."""
    from mmlspark_tpu.nn import attention, models

    monkeypatch.setattr(attention.layout.jax, "default_backend",
                        lambda: "tpu")
    module = models.make_model(
        "looped_decoder", num_layers=layers, total_ut_steps=steps,
        d_model=2048, num_heads=16, num_kv_heads=16, head_dim=128,
        d_ff_dense=5632, vocab_size=49152, dtype=jnp.bfloat16)
    variables = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    ids = jax.ShapeDtypeStruct((rows, length), jnp.int32, sharding=one_chip)

    def forward(v, x):
        return module.apply(v, x, capture_intermediates=True,
                            mutable=["intermediates"])

    return forward, variables, ids


@pytest.mark.parametrize("rows,length", [(2, 8192), (2, 1024)])
def test_a_looped_stack_lowers_every_layer_once(one_chip, monkeypatch, rows,
                                                length):
    """The cell's two batches at its published widths: FOUR steps lower as
    many Pallas bodies as ONE (the steps are a scan over one body whose
    constants are the parameters), under the name the accepted reader
    selects; the compiled program is a loop, both sown outputs come out of
    it, and no activation is laid out again around the kernel. Two layers
    stand for the cell's 44: a layer more is a body more at any step
    count."""
    def lowered(steps):
        fn, variables, ids = _looped_for_the_chip(one_chip, monkeypatch,
                                                  steps, rows, length)
        return jax.jit(fn).lower(variables, ids)

    one, four = lowered(1).as_text(), lowered(4).as_text()
    assert one.count("tpu_custom_call") == four.count("tpu_custom_call")
    assert "stablehlo.while" in four
    text = _compile(*_looped_for_the_chip(one_chip, monkeypatch, 4, rows,
                                          length)).as_text()
    for layer in (0, 1):
        assert re.search(rf"%gqa_attn_{layer}[.\d]* = ", text)
    assert f"f32[{rows},{length},4]" in text            # the exit distribution
    assert f"f32[{rows},{length - 1}]" in text          # the log-probabilities
    body = text[:text.index("ENTRY")]
    # inside the loop's body (the embedding's lookup, before it, is not)
    moved = [line for line in body.splitlines()
             if re.search(r" (copy|transpose)\(", line) and "/while/" in line
             and f"[{rows},{length}," in line.split("=")[1].split("(")[0]]
    assert not moved, moved


def test_the_looped_references_layer_program_fits_beside_the_trees(one_chip):
    """`benchmark/reference/looped_decoder.py`'s largest program, one
    layer over one row of 8192 at hidden 2048 in float32: its temporaries
    stay under 0.35 GB (read 0.270) beside 0.27 GB of arguments, which is
    what `check` has to find free beside the served copy and the float32
    tree (PERF.md section 4: 0.9 GB are left at 48 layers)."""
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "reference"
            / "looped_decoder.py")
    spec = importlib.util.spec_from_file_location("ref_looped_chip", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    config = {"model": dict(
        num_layers=44, total_ut_steps=4, d_model=2048, num_heads=16,
        num_kv_heads=16, head_dim=128, d_ff_dense=5632, vocab_size=49152)}
    frozen = tuple(sorted(ref.sizes(config).items()))

    def spec_of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    weights = {name: spec_of(*shape) for name, shape in dict(
        ln_attn_scale=(2048,), wq=(2048, 16, 128), wk=(2048, 16, 128),
        wv=(2048, 16, 128), wo=(16, 128, 2048), ln_attn_post_scale=(2048,),
        ln_mlp_scale=(2048,), gate=(2048, 5632), up=(2048, 5632),
        down=(5632, 2048), ln_mlp_post_scale=(2048,)).items()}
    assert set(weights) == set(ref.LAYER_NAMES)
    with jax.default_matmul_precision("highest"):
        compiled = _compile(lambda h, w: ref._layer(h, w, frozen),
                            spec_of(1, 8192, 2048), weights)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.35e9
    assert memory.argument_size_in_bytes < 0.3e9


# --------------------------------------------------------------------- #
# the state-space hybrid (`falcon_h1_34b.score_long_context`)           #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("length", [32768, 2048])
def test_state_space_scan_kernel_compiles_at_the_cells_shapes(one_chip,
                                                              length):
    """`ssd_scan_<i>` at the published widths, a batch of one row: 32 heads
    of 128 channels, 2 groups of 256 state channels, read in place from the
    convolution's (1, T, 5120) array, a group's 16 heads a grid step; their
    states, (16, 256, 128) float32, are the kernel's one scratch (2 MB) and
    nothing the size of a state leaves it."""
    from mmlspark_tpu.nn import scan

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def scanned(xbc, dt, a, d):
        return scan.ssd_kernel(xbc, dt, a, d, heads=32, width=128, groups=2,
                               state=256, name="ssd_scan_3")

    shapes = (spec((1, length, 5120), jnp.bfloat16),
              spec((1, length, 32), jnp.float32), spec((32,), jnp.float32),
              spec((32,), jnp.float32))
    kernel = str(jax.make_jaxpr(scanned)(*shapes))
    assert "Ref<vmem>{f32[16,256,128]}" in kernel
    compiled = _compile(scanned, *shapes)
    text = compiled.as_text()
    assert re.search(r"%ssd_scan_3[.\d]* = ", text)
    assert f"bf16[1,{length},4096]" in text
    # the running decays in both layouts and the padded step: a few
    # float32 numbers a token a head, and no copy of x, B or C
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 32 * length


def test_state_space_scan_kernel_is_differentiated_for_the_chip(one_chip):
    """`jax.grad` through `ssd_scan_<i>` compiles for the described v5e at
    the published widths: the forward is the kernel, by name, and the
    backward the plain tier's chunks in XLA (no Pallas backward exists to
    fail)."""
    from mmlspark_tpu.nn import scan

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xbc, dt, a, d):
        return scan.ssd_kernel(xbc, dt, a, d, heads=32, width=128, groups=2,
                               state=256, name="ssd_scan_0").astype(
                                   jnp.float32).sum()

    shapes = (spec((1, 2048, 5120), jnp.bfloat16),
              spec((1, 2048, 32), jnp.float32), spec((32,), jnp.float32),
              spec((32,), jnp.float32))
    both = _compile(jax.value_and_grad(loss, (0, 1, 2, 3)), *shapes)
    text = both.as_text()
    assert re.search(r"%ssd_scan_0[.\d]* = ", text)
    _value, grads = both.out_info
    assert [g.shape for g in grads] == [s.shape for s in shapes]


@pytest.mark.parametrize("length", [32768, 2048])
def test_grouped_query_attention_compiles_at_five_heads_a_group(one_chip,
                                                                length):
    """`gqa_attn_<i>` as the state-space hybrid's cell runs it: 20 query
    heads over 4 key/value heads of 128 channels, in place, a batch of one
    row."""
    from mmlspark_tpu.nn.attention import flash_attention

    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, length, 20, 128), bf, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, length, 4, 128), bf, sharding=one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True)

    kernel = str(jax.make_jaxpr(attend)(q, k, k))
    # a key/value head serves five query heads by the index map: K and V
    # go in as they lie, four heads of them
    assert f"bf16[1,{length},512]" in kernel
    assert "tpu_custom_call" in _compile(attend, q, k, k).as_text()


def test_the_state_space_hybrid_lowers_both_kernels_a_layer_by_name(
        one_chip, monkeypatch):
    """Two layers of the cell's model at its published widths, a row of
    2048 tokens, embedding to log-probabilities: every layer's scan and
    attention are in the compiled program under the names the readers
    select, and no activation of the convolution's or the scan's width is
    laid out again around the scan."""
    from mmlspark_tpu.nn import attention, models

    monkeypatch.setattr(attention.layout.jax, "default_backend",
                        lambda: "tpu")
    module = models.make_model(
        "ssm_hybrid_decoder", num_layers=2, d_model=5120, num_heads=20,
        num_kv_heads=4, head_dim=128, ssm_heads=32, ssm_head_dim=128,
        ssm_groups=2, ssm_state=256, d_ff_dense=21504, vocab_size=32640,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        lm_head_multiplier=0.0078125, dtype=jnp.bfloat16)
    variables = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    ids = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)

    def forward(v, x):
        _out, state = module.apply(v, x, capture_intermediates=True,
                                   mutable=["intermediates"])
        return state["intermediates"]["token_logprobs"][0]

    text = _compile(forward, variables, ids).as_text()
    for layer in (0, 1):
        assert re.search(rf"%ssd_scan_{layer}[.\d]* = ", text)
        assert re.search(rf"%gqa_attn_{layer}[.\d]* = ", text)
    assert "f32[1,2047]" in text
    moved = [line for line in text[text.index("ENTRY"):].splitlines()
             if re.search(r" (copy|transpose)\(", line)
             and re.search(r"\[1,2048,(5120|4096|9248)\]",
                           line.split("=")[1].split("(")[0])]
    assert not moved, moved


# --------------------------------------------------------------------- #
# the decoder-hybrid-decoder (`phi4_mini_flash.score_long_traces`)      #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("length", [32768, 4096])
def test_channel_decay_scan_kernel_compiles_at_the_cells_shapes(one_chip,
                                                                length):
    """`sel_scan_<i>` at the published widths, a batch of one row: 5120
    channels in ten blocks of 512 lanes, 16 states on sublanes; the block's
    state, (16, 512) float32, is scratch, and nothing of (tokens, channels,
    states) is in the compiled program."""
    from mmlspark_tpu.nn import scan

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def scanned(x, dt, a, bm, cm, d):
        return scan.sel_kernel(x, dt, a, bm, cm, d, name="sel_scan_8")

    shapes = (spec((1, length, 5120), jnp.bfloat16),
              spec((1, length, 5120), jnp.float32),
              spec((5120, 16), jnp.float32),
              spec((1, length, 16), jnp.bfloat16),
              spec((1, length, 16), jnp.bfloat16), spec((5120,), jnp.float32))
    kernel = str(jax.make_jaxpr(scanned)(*shapes))
    assert "Ref<vmem>{f32[16,512]}" in kernel
    compiled = _compile(scanned, *shapes)
    text = compiled.as_text()
    assert re.search(r"%sel_scan_8[.\d]* = ", text)
    assert f"bf16[1,{length},5120]" in text
    assert not re.search(rf"\[(1,)?{length},5120,16\]|\[(1,)?{length},16,5120\]",
                         text)
    # B and C as (16, Q) tiles and A transposed: a few numbers a token, and
    # no copy of x, dt or y
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * length


@pytest.mark.parametrize("length", [32768, 4096])
@pytest.mark.parametrize("window,name", [(None, "diff_attn_9"),
                                         (512, "diff_swa_w512")])
def test_differential_forwards_compile_at_the_cells_shapes(one_chip, length,
                                                           window, name):
    """Both softmaxes of a differential layer at the published widths (40
    query over 20 key/value heads of 64: 20 pairs over 10, scores 64 wide,
    values 128), plain causal and behind the band of 512, under the names
    the readers select."""
    from mmlspark_tpu.nn.attention import diff

    def spec(width):
        return jax.ShapeDtypeStruct((1, length, width), jnp.bfloat16,
                                    sharding=one_chip)

    def attend(q, k, v):
        return diff.differential_attention(
            q, diff.key_pairs(k, v, 20), 0.5, "flash", window, "diff_attn_9")

    text = _compile(attend, spec(2560), spec(1280), spec(1280)).as_text()
    assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 2
    assert f"f32[1,{length},20,128]" in text


def test_the_decoder_hybrid_decoder_lowers_its_kernels_by_name(one_chip,
                                                               monkeypatch):
    """Eight layers of the cell's model at its published widths (every kind
    of layer), a row of 4096 tokens, embedding to log-probabilities: every
    Mamba layer's scan and every differential layer's forwards are in the
    compiled program under the names the readers select; layer 5's keys and
    values are projected ONCE and the cross layer lays out none (the three
    self layers' pair-major copies are the only ones); nothing of (tokens,
    channels, states) exists."""
    from mmlspark_tpu.nn import attention, models

    monkeypatch.setattr(attention.layout.jax, "default_backend",
                        lambda: "tpu")
    module = models.make_model(
        "decoder_hybrid_decoder", num_layers=8, d_model=2560, num_heads=40,
        num_kv_heads=20, mamba_inner=5120, mamba_state=16, mamba_dt_rank=160,
        window_size=512, d_ff_dense=10240, vocab_size=200064,
        dtype=jnp.bfloat16)
    variables = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    ids = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)

    def forward(v, x):
        _out, state = module.apply(v, x, capture_intermediates=True,
                                   mutable=["intermediates"])
        return state["intermediates"]["token_logprobs"][0]

    traced = str(jax.make_jaxpr(forward)(variables, ids))
    # keys and values: three self layers project them, the cross layer not
    assert traced.count("bf16[1,4096,1280] = dot_general") == 2 * 3
    text = _compile(forward, variables, ids).as_text()
    for layer in (0, 2, 4):
        assert re.search(rf"%sel_scan_{layer}[.\d]* = ", text)
    for layer in (5, 7):
        assert len(re.findall(rf"%diff_attn_{layer}[.\d]* = ", text)) == 2
    assert len(re.findall(r"%diff_swa_w512[.\d]* = ", text)) == 2 * 2
    assert "f32[1,4095]" in text
    entry = text[text.index("ENTRY"):].splitlines()
    laid_out = [line for line in entry
                if re.search(r" (copy|transpose)\(", line)
                and re.search(r"bf16\[1,4096,10,(2,64|128)\]",
                              line.split("=")[1].split("(")[0])]
    assert len(laid_out) == 2 * 3, laid_out
    assert not re.search(r"\[(1,)?4096,5120,16\]|\[(1,)?4096,16,5120\]", text)


# the five decoder cells whose head the rule takes: the long batch's tokens,
# d, the vocabulary held, tied, `lm_head_multiplier`
HEADS = {
    "phi4_mini_flash": (32768, 2560, 200064, True, 1.0),
    "lfm2_8b_a1b": (32768, 2048, 65536, True, 1.0),
    "moonlight_16b_a3b": (32768, 2048, 40960, False, 1.0),
    "smallthinker_21b_a3b": (32768, 2560, 37984, False, 1.0),
    "falcon_h1_34b": (32768, 5120, 32640, False, 0.0078125),
}


@pytest.mark.parametrize("cell", list(HEADS))
def test_loglik_head_compiles_at_the_cells_head_shapes(one_chip, cell):
    """`loglik_head` at the tile `head_tiles` gives each cell's head: the
    chip's compiler takes the kernel inside the VMEM the rule counted (a
    call whose count passes the 16 MB a custom call gets states
    `lanes.STEP_VMEM`, another states nothing), the grid walks the vocabulary
    last, and a tied head's operand is the embedding as it lies, (V, d)."""
    from mmlspark_tpu.nn import loglik

    tokens, d, vocab, tied, multiplier = HEADS[cell]
    bf = jnp.bfloat16
    tm, tn = tiles = loglik.head_tiles(tokens, d, vocab, 2)
    counted = loglik._head_bytes(tm, tn, d, 2)
    assert counted <= loglik.STEP_VMEM
    flat = jax.ShapeDtypeStruct((tokens, d), bf, sharding=one_chip)
    target = jax.ShapeDtypeStruct((tokens,), jnp.int32, sharding=one_chip)
    head = jax.ShapeDtypeStruct((vocab, d) if tied else (d, vocab), bf,
                                sharding=one_chip)

    def score(flat, target, head):
        return loglik.loglik_head(flat, target, head, tied=tied, tiles=tiles,
                                  multiplier=multiplier)

    (call,) = [e for e in _deep_equations(jax.make_jaxpr(score)(
        flat, target, head)) if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (tokens // tm, -(-vocab // tn))
    assert [tuple(x.block_size for x in m.block_shape)
            for m in mapping.block_mappings] == [
        (tm, 128), (tm, d), (tn, d) if tied else (d, tn), (tm, 1)]
    stated = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert stated == (loglik.STEP_VMEM if counted > 16 << 20 else None)
    text = _compile(score, flat, target, head).as_text()
    assert "tpu_custom_call" in text
    assert f"[{tm},{vocab}]" not in text
    if tied:
        assert f"[{d},{vocab}]" not in text


@pytest.mark.parametrize("cell,rows,length", [
    ("lfm2_8b_a1b", 2, 16384), ("phi4_mini_flash", 1, 32768),
    ("lfm2_8b_a1b", 2, 1024), ("phi4_mini_flash", 1, 4096)])
def test_a_tied_decoder_holds_no_logits_and_no_copy_of_its_embedding(
        one_chip, monkeypatch, cell, rows, length):
    """A decoder with a tied head at a tied cell's width and vocabulary,
    one layer, ids to log-probabilities, compiled for the chip at the
    cell's batches: the head is the ONE call `loglik_head`, and the
    optimised program holds no float32 array of (tokens of a tile, V), in
    any order, and no array of (d, V): the embedding is read where it
    lies."""
    from mmlspark_tpu.nn import models

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _tokens, d, vocab, _tied, _multiplier = HEADS[cell]
    module = models.make_model(
        "hybrid_moe_decoder", d_model=d, num_heads=d // 64, num_kv_heads=8,
        layer_types=("conv",), num_dense_layers=1, d_ff_dense=1024,
        vocab_size=vocab, max_len=length, dtype=jnp.bfloat16)
    assert module.tie_embeddings
    variables = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    ids = jax.ShapeDtypeStruct((rows, length), jnp.int32, sharding=one_chip)
    text = _compile(lambda v, x: module.apply(v, x), variables, ids).as_text()
    assert len(re.findall(r"%loglik_head[.\d]* = ", text)) == 1
    # the call sits inside the decoder's `jax.named_scope("loglik.head")`
    assert re.search(r'%loglik_head[.\d]* = .*op_name="[^"]*/loglik\.head/',
                     text)
    assert f"bf16[{vocab},{d}]" in text
    shapes = set(re.findall(r"\w+\[[\d,]+\]", text))
    assert not [s for s in shapes if re.fullmatch(
        rf"f32\[(\d+,)?(\d+,{vocab}|{vocab},\d+)\]", s)], shapes
    assert not [s for s in shapes if re.fullmatch(
        rf"\w+\[{d},{vocab}\]", s)], shapes
