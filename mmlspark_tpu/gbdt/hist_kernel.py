"""Gradient/hessian histogram build — the GBDT hot kernel.

Reference semantics: lib_lightgbm's per-feature histogram construction over
local rows inside LGBM_BoosterUpdateOneIter (TrainUtils.scala:74-121 drives
it; the C++ does a scatter-add into per-feature bin arrays). SURVEY.md §7
names this the core Pallas engineering: TPUs have no fast random scatter,
so the bin accumulation is a compare-and-matmul.

Two implementations behind the kernel registry (core/kernels.py):

- "xla": one-hot matmul with row chunking via `lax.scan`. Correct
  everywhere, but each (chunk, F·B) one-hot operand is materialized through
  HBM before the dot — at Adult-Census scale that is ~0.5 GB of HBM traffic
  per split and dominates fit time.
- "pallas" / "pallas_interpret": a Pallas TPU kernel with a sequential grid
  over row chunks. The one-hot compare mask lives ONLY in VMEM (never hits
  HBM), each feature's (chunk, B) mask feeds the MXU against the (chunk, C)
  stats block, and the (C, F·B) accumulator is revisited across grid steps.
  HBM traffic per split drops to reading bins+stats once (~2 MB vs ~0.5 GB).

Both return identical (F, B, C) float32 histograms (dot in HIGHEST
precision: near-tied split gains must not flip vs the committed parity
gates).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.kernels import register_kernel, resolve

__all__ = ["histogram", "histogram_xla", "histogram_xla_scatter",
           "histogram_pallas"]

_XLA_CHUNK = 1024
_PALLAS_CHUNK = 1024


# --------------------------------------------------------------------- #
# XLA fallback (any backend)                                            #
# --------------------------------------------------------------------- #

def histogram_xla(bins, stats, num_bins):
    """bins: (n, F) int32; stats: (n, C) float32 (already masked; padded
    rows must carry zero stats). Returns (F, B, C) float32."""
    n, f = bins.shape
    c = stats.shape[1]
    chunk = min(_XLA_CHUNK, n)
    pad = (-n) % chunk
    if pad:
        # padded rows carry all-zero stats: they land in bin 0 with weight 0
        bins = jnp.concatenate([bins, jnp.zeros((pad, f), bins.dtype)])
        stats = jnp.concatenate([stats, jnp.zeros((pad, c), stats.dtype)])
    nc = (n + pad) // chunk

    def body(acc, xs):
        b_chunk, s_chunk = xs                                   # (ch,F), (ch,C)
        oh = jax.nn.one_hot(b_chunk, num_bins, dtype=s_chunk.dtype)  # (ch,F,B)
        # (C, ch) @ (ch, F·B): the wide F·B dim sits on the MXU lane axis
        # (output N), so lanes are fully used; C only wastes sublanes.
        # Precision.HIGHEST: default TPU matmul rounds f32 inputs to bf16 —
        # grad/hess sums must be exact-ish or near-tied split gains flip
        # versus the host path (parity gates compare against fixed CSVs)
        h = jax.lax.dot_general(
            s_chunk, oh.reshape(chunk, f * num_bins), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # (C, F·B)
        return acc + h, None

    # + 0*stats[0,0]: under shard_map the per-shard inputs carry a
    # "varying over the data axis" type; the scan carry must match, and
    # depending on stats gives acc0 that type without naming the axis here
    acc0 = jnp.zeros((c, f * num_bins), jnp.float32) + 0.0 * stats[0, 0]
    acc, _ = jax.lax.scan(
        body,
        acc0,
        (bins.reshape(nc, chunk, f), stats.reshape(nc, chunk, c)),
    )
    return acc.reshape(c, f, num_bins).transpose(1, 2, 0)  # (F, B, C)


def histogram_xla_scatter(bins, stats, num_bins):
    """Scatter-add (segment_sum) histogram: 30x faster than the one-hot
    matmul on CPU (XLA:CPU lowers scatter to vectorized adds), pathological
    on TPU (serialized scatter) — the registry only auto-selects it on
    non-TPU backends."""
    n, f = bins.shape
    c = stats.shape[1]
    bins = bins.astype(jnp.int32)   # id arithmetic overflows narrow dtypes
    ids = (bins + jnp.arange(f, dtype=bins.dtype)[None, :] * num_bins).reshape(-1)
    data = jnp.broadcast_to(stats[:, None, :], (n, f, c)).reshape(-1, c)
    seg = jax.ops.segment_sum(data, ids, num_segments=f * num_bins)
    return seg.reshape(f, num_bins, c)


# --------------------------------------------------------------------- #
# Pallas TPU kernel                                                     #
# --------------------------------------------------------------------- #

def _hist_kernel(num_features, num_bins, chunk, bins_ref, stats_ref, out_ref):
    """One grid step = one row chunk. out_ref (C, F·B) is revisited by every
    step (sequential TPU grid): zeroed on the first, accumulated after."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    stats = stats_ref[:]                                        # (ch, C)
    for f in range(num_features):
        # cast IN VMEM: uint8 bin blocks read 4x less HBM than int32 —
        # the dominant stream of every split's histogram pass
        col = bins_ref[:, f : f + 1].astype(jnp.int32)          # (ch, 1)
        iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, num_bins), 1)
        mask = (col == iota).astype(jnp.float32)                # (ch, B) VMEM-only
        h = jax.lax.dot_general(
            stats, mask, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )                                                       # (C, B)
        out_ref[:, f * num_bins : (f + 1) * num_bins] += h


def _hist_kernel_grouped(group, num_features, num_bins, chunk,
                         bins_ref, stats_ref, out_ref):
    """Middle ground between per-feature and fused: G features share one
    dot, so each matmul's lane axis is G·B wide (e.g. 1024 at G=4, B=256 —
    vs 256 per-feature) without the fused variant's full F·B VMEM mask.
    A pre-round chip sweep (source removed in PR 21; a hypothesis until a
    ledger line) showed per-feature beating both chunk=2048 and the XLA
    scan; this variant probes whether the win was dot width or VMEM
    pressure. All-f32 operands — the Mosaic mixed-dtype constraint
    observed on v5e rules out a bf16 mask."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    stats = stats_ref[:]                                        # (ch, C)
    for g0 in range(0, num_features, group):
        g = min(group, num_features - g0)                       # static
        col = bins_ref[:, g0 : g0 + g].astype(jnp.int32)        # (ch, g)
        iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, g, num_bins), 2)
        mask = (col[:, :, None] == iota).astype(jnp.float32)
        mask = mask.reshape(chunk, g * num_bins)                # VMEM-only
        h = jax.lax.dot_general(
            stats, mask, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )                                                       # (C, g·B)
        out_ref[:, g0 * num_bins : (g0 + g) * num_bins] += h


def _hist_kernel_fused(num_features, num_bins, chunk, bins_ref, stats_ref, out_ref):
    """Fused variant: ONE (chunk, F·B) one-hot mask in VMEM and ONE dot per
    grid step, instead of F small dots. Small matmuls leave the MXU idle
    between issues; the fused dot amortizes that fixed cost over the whole
    F·B lane axis."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    stats = stats_ref[:]                                        # (ch, C)
    col = bins_ref[:].astype(jnp.int32)                         # (ch, F), VMEM cast
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (chunk, num_features, num_bins), 2
    )
    # f32, not bf16: Mosaic rejects mixed f32×bf16 tpu.matmul operands on
    # real hardware ("Bad rhs type", observed v5e), and the 0/1 mask is
    # exact in either dtype — only the VMEM budget changes (_fused_chunk).
    mask = (col[:, :, None] == iota).astype(jnp.float32)
    mask = mask.reshape(chunk, num_features * num_bins)         # VMEM-only
    h = jax.lax.dot_general(
        stats, mask, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                                           # (C, F·B)
    out_ref[:] += h


# Budget for the fused kernel's VMEM-resident mask (chunk × F·B f32). VMEM
# is ~16 MB less double-buffered inputs/outputs; 4 MB leaves ample room.
_FUSED_MASK_VMEM_BYTES = 4 * 2**20


def _fused_chunk(f: int, num_bins: int) -> int:
    """Largest power-of-two chunk whose mask fits the VMEM budget."""
    limit = _FUSED_MASK_VMEM_BYTES // (f * num_bins * 4)
    chunk = 1 << max(int(limit).bit_length() - 1, 0)
    return min(chunk, 2048)


def _hist_group() -> int:
    """Feature-group width for the grouped kernel (MMLSPARK_TPU_HIST_GROUP).
    1 (default) = the proven per-feature kernel; >1 widens each dot's lane
    axis to G·B. Opt-in until a chip sweep picks a winner."""
    import os

    try:
        return max(int(os.environ.get("MMLSPARK_TPU_HIST_GROUP", "1")), 1)
    except ValueError:
        return 1


def _fused_enabled() -> bool:
    """The fused variant is opt-in (MMLSPARK_TPU_FUSED_HIST=1) until a chip
    sweep proves it beats the per-feature kernel; per-feature chunk=1024
    is the default."""
    import os

    return os.environ.get("MMLSPARK_TPU_FUSED_HIST", "0") == "1"


def _histogram_pallas(bins, stats, num_bins, interpret):
    import jax.experimental.pallas as pl

    n, f = bins.shape
    c = stats.shape[1]
    # fused needs the lane axis (F·B) 128-aligned and a sublane-aligned chunk
    fused_chunk = _fused_chunk(f, num_bins)
    use_fused = (_fused_enabled()
                 and (f * num_bins) % 128 == 0 and fused_chunk >= 32)
    # rows pad up to a whole chunk (zero stats land in bin 0 with weight 0),
    # so tiny n still runs the tile-aligned chunk shape
    chunk = fused_chunk if use_fused else min(_PALLAS_CHUNK, max(n, 8))
    group = min(_hist_group(), f)
    # same lane-alignment discipline as the fused gate: every grouped dot's
    # lane axis (g·B, including the ragged tail group f%group) must be
    # 128-aligned or Mosaic can reject the kernel at fit time on real TPU —
    # fall back to the proven per-feature kernel instead of failing the fit.
    # Real-Mosaic only: interpret mode has no lane constraint, and the CPU
    # parity tests rely on it to exercise the ragged-tail grouped path.
    if group > 1 and not interpret:
        tail = f % group
        aligned = (group * num_bins) % 128 == 0 and (
            tail == 0 or (tail * num_bins) % 128 == 0)
        if not aligned:
            group = 1
    if use_fused:
        kernel = _hist_kernel_fused
    elif group > 1:
        kernel = functools.partial(_hist_kernel_grouped, group)
        # same VMEM discipline as the fused path: the (chunk, G·B) f32
        # mask must fit the budget, or Mosaic blows VMEM at fit time
        mask_limit = _FUSED_MASK_VMEM_BYTES // (group * num_bins * 4)
        mask_chunk = 1 << max(int(mask_limit).bit_length() - 1, 3)
        chunk = min(chunk, mask_chunk)
    else:
        kernel = _hist_kernel

    pad = (-n) % chunk
    if pad:
        bins = jnp.concatenate([bins, jnp.zeros((pad, f), bins.dtype)])
        stats = jnp.concatenate([stats, jnp.zeros((pad, c), stats.dtype)])
    nc = (n + pad) // chunk

    out = pl.pallas_call(
        functools.partial(kernel, f, num_bins, chunk),
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((chunk, f), lambda i: (i, 0)),
            pl.BlockSpec((chunk, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((c, f * num_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, f * num_bins), jnp.float32),
        interpret=interpret,
        # bins pass through in their STORAGE dtype (uint8 under
        # bin_dtype="uint8"): the int32 cast happens inside the kernel on
        # VMEM blocks, so the HBM read stays narrow
    )(bins, stats.astype(jnp.float32))
    return out.reshape(c, f, num_bins).transpose(1, 2, 0)       # (F, B, C)


def histogram_pallas(bins, stats, num_bins):
    return _histogram_pallas(bins, stats, num_bins, interpret=False)


def histogram_pallas_interpret(bins, stats, num_bins):
    return _histogram_pallas(bins, stats, num_bins, interpret=True)


register_kernel("gbdt_histogram", "xla", histogram_xla)
register_kernel("gbdt_histogram", "xla_scatter", histogram_xla_scatter)
register_kernel("gbdt_histogram", "pallas", histogram_pallas)
register_kernel("gbdt_histogram", "pallas_interpret", histogram_pallas_interpret)


def histogram(bins, stats, num_bins):
    """Registry-resolved histogram (resolution happens at trace time; the
    chosen variant is baked into the enclosing jit — change kernel mode
    before building a fit, not during)."""
    return resolve("gbdt_histogram")(bins, stats, num_bins)
