"""SAR — Smart Adaptive Recommendations.

Reference: src/recommendation/src/main/scala/SAR.scala:36-205 —
user-item affinity with time decay (:82-117: affinity = rating ×
2^(-Δt_minutes / (time_decay_coeff·24·60)), summed per (user, item)) and
item-item similarity from distinct-user co-occurrence with
cooccurrence/jaccard/lift normalization and a support threshold (:119-205);
SARModel scoring (SARModel.scala:95-130) = user-affinity × item-similarity
matrix product + top-k.

TPU redesign: the reference builds these with Spark groupBys, per-row UDFs
and a breeze BlockMatrix multiply. Here the whole computation is three dense
device ops — a scatter-add affinity build, ONE (I×U)@(U×I) matmul on the MXU
for co-occurrence, and ONE (U×I)@(I×I) matmul + a row's top k
(`topk.top_k_rows`) for recommendations.
"""

from __future__ import annotations

from datetime import datetime
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dataplane import AsyncReadback
from ..core.params import Param
from ..core.pipeline import Estimator, Model
from ..core.schema import Table
from ..core.serialize import register_stage
from ..observability.tracing import get_tracer
from .topk import top_k_rows

__all__ = ["SAR", "SARModel"]


# Module-level jitted scoring programs: jax.jit caches the executable per
# input shape, so repeated SARModel calls neither re-trace nor recompile.
@jax.jit
def _affinity_scores(affinity, similarity):
    return affinity @ similarity


# A block's rows are cut inside the program (the whole resident arrays go
# in, `start` is traced, `rows` static), so a block is one enqueue. The
# barrier keeps the affinity rows an operand of their own: XLA then writes
# them once, already rounded for the MXU, and the product runs at 93% of
# its roofline on a v5e; cut inside the product's fusion the same rows
# cost it 13% (PERF.md, PR 25).
def _block_scores(affinity, similarity, start, rows):
    block = jax.lax.dynamic_slice_in_dim(affinity, start, rows)
    return jax.lax.optimization_barrier(block) @ similarity


def _block_rows(n_users: int, block: int) -> int:
    """Rows EVERY block of a pass takes, at most `block`: one shape a pass
    is one trace, lowering, cache read and first run of the block program
    (a second, small program compiles in under the second JAX asks of an
    executable it keeps, so every start compiled it anew). The last block
    is cut whole too, over rows the one before it already gave; so of the
    sizes from `block` down to half of it, in the steps of 256 rows the
    product pads a block to on the MXU, the one a pass computes the fewest
    rows with: 69,878 users under 4096 a block are 21 blocks of 3328, ten
    rows twice, where 18 of 4096 would be 3,850 (PERF.md, PR 39). Fewer
    users than a block are one short block."""
    if n_users <= block:
        return n_users
    return min(range(block, block // 2, -256),
               key=lambda rows: -(-n_users // rows) * rows)


@partial(jax.jit, static_argnames=("rows", "k"))
def _block_topk(affinity, similarity, start, rows, k):
    return top_k_rows(_block_scores(affinity, similarity, start, rows), k)


@partial(jax.jit, static_argnames=("rows", "k"))
def _block_topk_unseen(affinity, similarity, seen, start, rows, k):
    scores = _block_scores(affinity, similarity, start, rows)
    seen_rows = jax.lax.dynamic_slice_in_dim(seen, start, rows)
    return top_k_rows(jnp.where(seen_rows, -jnp.inf, scores), k)


def _to_minutes(values, fmt: str | None) -> np.ndarray:
    """Timestamps (epoch seconds, numpy datetimes, or strings with fmt) ->
    float minutes."""
    vals = list(values)
    if not vals:
        return np.zeros(0)
    v0 = vals[0]
    if isinstance(v0, (int, float, np.number)):
        return np.asarray(vals, np.float64) / 60.0
    if isinstance(v0, np.datetime64):
        return np.asarray(vals).astype("datetime64[s]").astype(np.float64) / 60.0
    fmt = fmt or "%Y-%m-%d %H:%M:%S"
    return np.asarray(
        [datetime.strptime(str(v), fmt).timestamp() for v in vals], np.float64
    ) / 60.0


@register_stage
class SAR(Estimator):
    """Reference params: SARParams (SAR.scala:39-56) + Spark ALS-style cols."""

    user_col = Param("user", "indexed user id column", ptype=str)
    item_col = Param("item", "indexed item id column", ptype=str)
    rating_col = Param(None, "rating column (optional)", ptype=str)
    time_col = Param(None, "activity timestamp column (optional)", ptype=str)
    similarity_function = Param("jaccard", "jaccard | lift | cooccurrence", ptype=str)
    support_threshold = Param(4, "min co-occurrence to keep a similarity", ptype=int)
    time_decay_coeff = Param(30, "half-life in days for affinity decay", ptype=int)
    start_time = Param(None, "reference time (default: max activity time)", ptype=str)
    activity_time_format = Param("%Y-%m-%d %H:%M:%S", "strptime format", ptype=str)
    start_time_format = Param("%Y-%m-%d %H:%M:%S", "strptime format", ptype=str)
    num_users = Param(None, "explicit user vocabulary size (default: max id + 1)",
                      ptype=int)
    num_items = Param(None, "explicit item vocabulary size (default: max id + 1)",
                      ptype=int)

    def set_indexer_model(self, indexer_model) -> "SAR":
        """Wire vocabulary sizes from a fitted RecommendationIndexerModel so
        items/users with no interactions still exist in the model (reference
        SARModel operates on the indexer's full id space,
        RecommendationIndexer.scala:16-130)."""
        self.set(num_users=indexer_model.n_users, num_items=indexer_model.n_items)
        return self

    def _fit(self, table: Table) -> "SARModel":
        u = np.asarray(table[self.get("user_col")], np.int64)
        it = np.asarray(table[self.get("item_col")], np.int64)
        if len(u) == 0 and not (self.get("num_users") and self.get("num_items")):
            raise ValueError(
                "cannot fit SAR on an empty table without explicit "
                "num_users/num_items"
            )
        max_u = int(u.max()) if len(u) else -1
        max_i = int(it.max()) if len(it) else -1
        n_users = self.get("num_users") or max_u + 1
        n_items = self.get("num_items") or max_i + 1
        if max_u >= n_users or max_i >= n_items:
            raise ValueError(
                f"interaction ids exceed declared vocab: max user {max_u} "
                f"(num_users={n_users}), max item {max_i} (num_items={n_items})"
            )

        # -- affinity weights (SAR.scala:82-117) ------------------------- #
        if self.get("rating_col") and self.get("rating_col") in table:
            w = np.asarray(table[self.get("rating_col")], np.float64)
        else:
            w = np.ones(len(u), np.float64)
        if self.get("time_col") and self.get("time_col") in table and len(u):
            t_min = _to_minutes(table[self.get("time_col")],
                                self.get("activity_time_format"))
            if self.get("start_time"):
                ref = datetime.strptime(
                    self.get("start_time"), self.get("start_time_format")
                ).timestamp() / 60.0
            else:
                ref = float(t_min.max())
            half_life_min = self.get("time_decay_coeff") * 24 * 60
            w = w * np.power(2.0, -(ref - t_min) / half_life_min)

        affinity = np.zeros((n_users, n_items), np.float64)
        np.add.at(affinity, (u, it), w)

        # -- item-item similarity (SAR.scala:119-205) -------------------- #
        occurrence = np.zeros((n_users, n_items), np.float32)
        occurrence[u, it] = 1.0  # distinct (user, item)
        occ_dev = jnp.asarray(occurrence)
        cooccur = np.asarray(
            jax.jit(lambda b: b.T @ b)(occ_dev), np.float64
        )  # (I, I) on the MXU — the reference's breeze SparseMatrix product
        occ = np.diag(cooccur).copy()

        fn = self.get("similarity_function")
        with np.errstate(divide="ignore", invalid="ignore"):
            if fn == "jaccard":
                denom = occ[:, None] + occ[None, :] - cooccur
                sim = np.where(denom > 0, cooccur / denom, 0.0)
            elif fn == "lift":
                denom = occ[:, None] * occ[None, :]
                sim = np.where(denom > 0, cooccur / denom, 0.0)
            elif fn in ("cooccurrence", "cooccur"):
                sim = cooccur
            else:
                raise ValueError(f"unknown similarity_function {fn!r}")
        sim = np.where(cooccur >= self.get("support_threshold"), sim, 0.0)

        model = SARModel(
            user_col=self.get("user_col"), item_col=self.get("item_col"),
        )
        model.user_affinity = affinity.astype(np.float32)
        model.item_similarity = sim.astype(np.float32)
        model.seen = occurrence.astype(bool)
        return model


@register_stage
class SARModel(Model):
    """Scoring: affinity (U×I) @ similarity (I×I), top-k via
    `topk.top_k_rows` (reference SARModel.scala:95-130 BlockMatrix
    multiply + top-k udf)."""

    user_col = Param("user", "indexed user id column", ptype=str)
    item_col = Param("item", "indexed item id column", ptype=str)
    prediction_col = Param("prediction", "predicted affinity column", ptype=str)

    user_affinity: np.ndarray | None = None    # (U, I) float32
    item_similarity: np.ndarray | None = None  # (I, I) float32
    seen: np.ndarray | None = None             # (U, I) bool

    # device copies of the host arrays, uploaded once and reused across
    # calls; None until first use and after _load_state
    _device_cache: "dict[str, Any] | None" = None

    # rows per device block in recommend_for_all_users: bounds peak device
    # memory at two blocks×I instead of U×I
    USER_BLOCK = 4096

    def _device_arrays(self) -> dict[str, Any]:
        if self._device_cache is None:
            self._device_cache = {
                "affinity": jnp.asarray(self.user_affinity),
                "similarity": jnp.asarray(self.item_similarity),
                "seen": (jnp.asarray(self.seen)
                         if self.seen is not None else None),
            }
        return self._device_cache

    def invalidate_device_cache(self) -> None:
        self._device_cache = None

    def _scores(self) -> jnp.ndarray:
        dev = self._device_arrays()
        return _affinity_scores(dev["affinity"], dev["similarity"])

    def _transform(self, table: Table) -> Table:
        """Per (user, item) row: predicted affinity score. Gathers only the
        requested users' affinity rows — one (n_requested × I) matmul, never
        the full U×I score matrix."""
        u = np.asarray(table[self.get("user_col")], np.int64)
        it = np.asarray(table[self.get("item_col")], np.int64)
        n_u, n_i = self.user_affinity.shape
        valid = (u >= 0) & (u < n_u) & (it >= 0) & (it < n_i)
        pred = np.zeros(len(u), np.float64)
        if valid.any():
            dev = self._device_arrays()
            users, pos = np.unique(u[valid], return_inverse=True)
            rows = np.asarray(_affinity_scores(
                dev["affinity"][jnp.asarray(users)], dev["similarity"]))
            pred[valid] = rows[pos, it[valid]]
        return table.with_column(self.get("prediction_col"), pred)

    def recommend_for_all_users(self, k: int, remove_seen: bool = True,
                                user_block: int | None = None) -> Table:
        """Reference: SARModel.recommendForAllUsers (SARModel.scala:95-130).
        Returns Table{user, recommendations, ratings} with top-k item ids.

        Scores `user_block` users at a time, the rows cut inside the jitted
        program, and enqueues the next block before it reads this one back:
        at most two blocks are in flight, so peak device memory is two
        blocks×I rather than U×I. Matmul rows and top_k are row-independent,
        so the blocked result is byte-identical to the single big matmul.
        Every block of a pass has ONE shape (`_block_rows`: at most
        `user_block` rows): the last one's cut starts early enough to be
        whole, and the rows the block before it already gave are dropped
        on the host."""
        dev = self._device_arrays()
        n_users, n_items = self.user_affinity.shape
        k = min(k, n_items)
        block = user_block or self.USER_BLOCK
        rows = _block_rows(n_users, block)
        mask_seen = remove_seen and dev["seen"] is not None
        tracer = get_tracer()
        with tracer.start_span(
                "sar.recommend_all", users=n_users, items=n_items, k=k,
                block=block, blocks=-(-n_users // rows),
                remove_seen=mask_seen) as call:
            vals = np.empty((n_users, k), np.float64)
            idx = np.empty((n_users, k), np.int64)

            def read_back(enqueued) -> int:
                lo, hi, v, i = enqueued
                # np.asarray would wait for the same buffers: waiting here
                # first changes no order and tells waiting from copying
                with tracer.start_span("sar.wait"):
                    jax.block_until_ready((v, i))
                # the rows this block is asked for are its last hi - lo
                block_bytes = (hi - lo) * k * (v.itemsize + i.itemsize)
                with tracer.start_span("sar.readback", bytes=block_bytes):
                    block_vals, block_idx = vals[lo:hi], idx[lo:hi]
                    block_vals[...] = np.asarray(v)[lo - hi:]
                    block_idx[...] = np.asarray(i)[lo - hi:]
                    # users with fewer than k unseen items: top_k still
                    # returns the -inf (seen) entries — mark them invalid
                    # (id -1) instead of leaking seen items back as
                    # 0-rated recommendations
                    invalid = ~np.isfinite(block_vals)
                    block_idx[invalid] = -1
                    block_vals[invalid] = 0.0
                return block_bytes

            if mask_seen:
                enqueue = partial(_block_topk_unseen, dev["affinity"],
                                  dev["similarity"], dev["seen"])
            else:
                enqueue = partial(_block_topk, dev["affinity"],
                                  dev["similarity"])
            # one block of look-ahead: block b+1 is enqueued before the
            # host waits for block b, so the device has work queued while
            # the host reads back, casts and marks
            pipeline = AsyncReadback(read_back, lag=1)
            bytes_read_back = dispatched_ahead = 0
            for lo in range(0, n_users, rows):
                hi = min(lo + rows, n_users)
                with tracer.start_span("sar.slice", lo=lo, hi=hi):
                    start = np.int32(hi - rows)
                with tracer.start_span("sar.dispatch"):
                    v, i = enqueue(start, rows, k)
                    # the copy back starts when the block is done, under
                    # the next block's work, not when the host asks
                    v.copy_to_host_async()
                    i.copy_to_host_async()
                # 1 while the block before this one has not been read back
                dispatched_ahead += pipeline.pending
                bytes_read_back += sum(pipeline.push((lo, hi, v, i)))
            bytes_read_back += sum(pipeline.drain())
            table = Table({
                self.get("user_col"): np.arange(n_users, dtype=np.float64),
                "recommendations": idx,
                "ratings": vals,
            })
            call.set(bytes_read_back=bytes_read_back,
                     dispatched_ahead=dispatched_ahead)
        return table

    def _save_state(self) -> dict[str, Any]:
        return {
            "user_affinity": self.user_affinity,
            "item_similarity": self.item_similarity,
            "seen": self.seen.astype(np.uint8) if self.seen is not None else None,
        }

    def _load_state(self, state: dict[str, Any]) -> None:
        self.user_affinity = np.asarray(state["user_affinity"], np.float32)
        self.item_similarity = np.asarray(state["item_similarity"], np.float32)
        seen = state.get("seen")
        self.seen = None if seen is None else np.asarray(seen, bool)
        self.invalidate_device_cache()
