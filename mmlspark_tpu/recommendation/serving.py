"""A fitted `SARModel` behind the serving package's device-resident lane.

- `SARHotPath` specializes `_HotPath` for two output columns
  (recommendation ids + ratings per request) and counts its traffic
  under the `sar_resident` route label, so
  `mmlspark_tpu_serving_path_total{path="sar_resident"}` separates SAR
  traffic from GBDT's `resident` in one process's scrape.
- `serve_recommender` is the `serve_model` twin around
  `resident.SARTopKScorer`: full-ladder warmup gates /readyz, every
  rung's resident reply is byte-compared against the handler path before
  it may route (divergence disables the route, never changes answers),
  readback completes lag-1 async, and steady state is zero-recompile
  because the bucket ladder closes the shape set.
"""

from __future__ import annotations

import json

import numpy as np

from ..core.fusion import fuse
from ..core.logging import get_logger
from ..core.pipeline import PipelineModel
from ..core.schema import Table
from ..io_http.schema import (HTTPRequestData, HTTPResponseData,
                              RequestDecoder, parse_request)
from ..io_http.serving import ServingServer, _HotPath
from .resident import SARTopKScorer
from .sar import SARModel

__all__ = ["SARHotPath", "serve_recommender", "topk_reply"]


def topk_reply(table: Table, reply_col: str = "reply") -> Table:
    """`make_reply` for the two-column top-k schema: one JSON body per row
    carrying both lists, byte-for-byte what `SARHotPath.replies_for`
    produces (tolist() -> Python ints/floats -> json.dumps)."""
    ids = np.asarray(table["recommendations"]).tolist()
    ratings = np.asarray(table["ratings"]).tolist()
    replies = [HTTPResponseData(
        status_code=200, reason="OK",
        headers={"Content-Type": "application/json"},
        entity=json.dumps(
            {"recommendations": i, "ratings": r}).encode(),
    ) for i, r in zip(ids, ratings)]
    return table.with_column(reply_col, replies)


class SARHotPath(_HotPath):
    """The SAR resident fast lane: same routing, warmup byte-compare, and
    readback machinery as the GBDT `_HotPath`, specialized for the
    two-column top-k reply and counted under its own route label."""

    resident_label = "sar_resident"

    def fetch_values(self, outs, n_valid: int, ledger=None):
        res = self.executor.fetch(outs, n_valid, ledger=ledger)
        return res["recommendations"], res["ratings"]

    def replies_for(self, vals, binary_mask=None
                    ) -> "list[HTTPResponseData]":
        # the two-column top-k reply stays JSON regardless of Accept —
        # binary negotiation covers single-value scoring replies only
        ids, ratings = vals
        return [HTTPResponseData(
            status_code=200, reason="OK",
            headers={"Content-Type": "application/json"},
            entity=json.dumps(
                {"recommendations": i, "ratings": r}).encode(),
        ) for i, r in zip(np.asarray(ids).tolist(),
                          np.asarray(ratings).tolist())]


def serve_recommender(
    model: SARModel,
    k: int = 10,
    remove_seen: bool = True,
    host: str = "127.0.0.1",
    port: int = 0,
    mesh=None,
    hot_path: bool = True,
    **server_kw,
) -> ServingServer:
    """Deploy a fitted `SARModel`: JSON `{user: id}` in,
    `{recommendations: [...], ratings: [...]}` out.

    The similarity matrix and affinity table pin on device once inside
    the fused segment; the handler path and the resident route execute
    the SAME jitted program with the SAME pinned params
    (`_FusedSegment._build` caches both), so warmup's per-rung byte
    comparison holds by construction and any divergence disables the
    fast lane (at WARNING) rather than changing answers.
    `serve_model(sar_model, ...)` delegates here."""
    if model.user_affinity is None or model.item_similarity is None:
        raise ValueError("serve_recommender needs a fitted SARModel")
    scorer = SARTopKScorer.from_model(model, k=k, remove_seen=remove_seen)
    fused = fuse(PipelineModel([scorer]), mesh=mesh)
    user_col = model.get("user_col")
    # one decoder serves the handler fast path AND the resident route,
    # so the cached schema and its hit/fallback counts stay unified
    decoder = RequestDecoder([user_col])
    hp = None
    if hot_path:
        rex = fused.resident_executor()
        if not isinstance(rex, str) and rex.upload_cols != ("features",):
            rex = f"segment uploads {rex.upload_cols}, not ('features',)"
        if isinstance(rex, str):
            get_logger("serving").warning(
                "no sar_resident hot path, serving through the handler: %s",
                rex)
        else:
            hp = SARHotPath(rex, decoder, "features", "recommendations",
                            readback_lag=fused.get("readback_lag"))

    def handler(table: Table) -> Table:
        reqs = list(table["request"])
        feats = decoder.decode(reqs)
        if feats is not None:
            scored = fused.transform(
                Table({"request": reqs, "features": feats}))
            return topk_reply(scored)
        t = parse_request(table)
        if user_col not in t:
            raise ValueError(f"request missing field {user_col!r}")
        t = t.with_column(
            "features",
            np.asarray(t[user_col], np.float64).reshape(-1, 1))
        return topk_reply(fused.transform(t))

    server_kw.setdefault("bucket_batches", True)
    # user id 0 always exists in a fitted model's id space, and 0.0 is
    # f32-exact — warmup compiles and byte-verifies every ladder rung
    server_kw.setdefault("warmup_request",
                         HTTPRequestData.from_json("/", {user_col: 0}))
    if hp is not None:
        server_kw.setdefault("bucket_multiple_of", hp.executor.data_axis_size)
    return ServingServer(handler, host=host, port=port, hot_path=hp,
                         **server_kw).start()
