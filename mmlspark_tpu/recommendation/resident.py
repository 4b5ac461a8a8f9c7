"""Device-resident SAR scoring: pinned similarity, fused top-k.

The GBDT hot path (io_http/serving.py + core/fusion.ResidentExecutor)
pins a fused segment's params on device once and scores request batches
through a persistent executable per bucket rung. `SARTopKScorer` puts the
SAR recommender on the same rails: it wraps a fitted `SARModel` as a
registered Transformer whose `device_kernel()` is one fused program —
gather the requested users' affinity rows, multiply into the
device-pinned item-item similarity matrix, mask seen items, `top_k_rows`
— so the whole user-id -> recommendations computation is a single XLA
executable per ladder rung. The server around it (`SARHotPath`,
`serve_recommender`) is `recommendation/serving.py`, which imports the
serving package; this module and `import mmlspark_tpu.recommendation` do
not.

Similarity layout: the kernel keeps `similarity` as a dense row-major
(I, I) operand of a plain `@` — the contract a later Pallas
blocked-sparse kernel slots into (same operand, blocked CSR under the
hood) without touching the serving path.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.fusion import DeviceKernel
from ..core.params import Param
from ..core.pipeline import Model
from ..core.schema import Table
from ..core.serialize import register_stage
from .sar import SARModel
from .topk import top_k_rows

__all__ = ["SARTopKScorer"]

# the two output columns every SAR scoring path produces, in reply order
TOPK_COLS = ("recommendations", "ratings")


@register_stage
class SARTopKScorer(Model):
    """Top-k recommendation scoring as a fusable pipeline stage.

    Consumes a `features` column of user ids — (n, 1) float, the
    RequestDecoder's output shape — and produces `recommendations`
    (int64 item ids, -1 for exhausted/invalid slots) and `ratings`
    (float64 scores, 0.0 on those slots), row-aligned with
    `SARModel.recommend_for_all_users`. The kernel is total over any
    float input: out-of-range or non-integral user ids yield all-(-1)
    rows instead of failing the batch, so padded/garbage rows can ride
    through the resident executor and the route contract stays
    byte-deterministic."""

    user_col = Param("user", "request field carrying the user id", ptype=str)
    k = Param(10, "recommendations per user", ptype=int)
    remove_seen = Param(True, "mask items the user already interacted with",
                        ptype=bool)

    user_affinity: np.ndarray | None = None    # (U, I) float32
    item_similarity: np.ndarray | None = None  # (I, I) float32
    seen: np.ndarray | None = None             # (U, I) bool

    _kernel: "DeviceKernel | None" = None
    _host_fn = None

    @classmethod
    def from_model(cls, model: SARModel, k: int = 10,
                   remove_seen: bool = True) -> "SARTopKScorer":
        scorer = cls(user_col=model.get("user_col"), k=int(k),
                     remove_seen=bool(remove_seen))
        scorer.user_affinity = model.user_affinity
        scorer.item_similarity = model.item_similarity
        scorer.seen = model.seen
        return scorer

    def device_kernel(self) -> "DeviceKernel | str":
        if self.user_affinity is None or self.item_similarity is None:
            return "scorer holds no fitted SAR state"
        if self._kernel is not None:
            return self._kernel
        n_users, n_items = self.user_affinity.shape
        k = min(int(self.get("k")), n_items)
        mask_seen = bool(self.get("remove_seen")) and self.seen is not None
        params = {"affinity": self.user_affinity,
                  "similarity": self.item_similarity}
        if mask_seen:
            params["seen"] = self.seen

        def fn(p, cols):
            raw = cols["features"][:, 0]
            ids = raw.astype(jnp.int32)
            # total over any float payload: out-of-range / fractional /
            # NaN user ids score a clamped row but reply all-invalid
            valid = (ids >= 0) & (ids < n_users) & (raw == ids.astype(raw.dtype))
            safe = jnp.clip(ids, 0, n_users - 1)
            scores = p["affinity"][safe] @ p["similarity"]
            if mask_seen:
                scores = jnp.where(p["seen"][safe], -jnp.inf, scores)
            vals, idx = top_k_rows(scores, k)
            # -inf slots = fewer than k unseen items, same convention as
            # SARModel.recommend_for_all_users
            bad = ~jnp.isfinite(vals) | ~valid[:, None]
            return {"recommendations": jnp.where(bad, -1, idx),
                    "ratings": jnp.where(bad, 0.0, vals)}

        self._kernel = DeviceKernel(
            fn=fn,
            input_cols=("features",),
            output_cols=TOPK_COLS,
            params=params,
            name="SARTopKScorer",
            out_dtypes={"recommendations": np.int64, "ratings": np.float64},
            mesh_desc="rows P(data) / similarity+affinity replicated",
        )
        return self._kernel

    def _transform(self, table: Table) -> Table:
        """Host fallback, same program run through jax.jit directly (the
        fused path is the serving route; this keeps bare `transform`
        correct for staged pipelines and tests)."""
        kern = self.device_kernel()
        if isinstance(kern, str):
            raise ValueError(kern)
        if "features" in table:
            feats = np.asarray(table["features"], np.float64)
        else:
            feats = np.asarray(table[self.get("user_col")],
                               np.float64).reshape(-1, 1)
        if self._host_fn is None:
            self._host_fn = jax.jit(kern.fn)
        outs = self._host_fn(kern.params, {"features": jnp.asarray(feats)})
        result = table
        for c in kern.output_cols:
            arr = np.asarray(outs[c])
            want = kern.out_dtypes.get(c)
            if want is not None and arr.dtype != np.dtype(want):
                arr = arr.astype(want)
            result = result.with_column(c, arr)
        return result

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
        self._kernel = None
        self._host_fn = None
