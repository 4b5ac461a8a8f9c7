"""`SARModel.recommend_for_all_users` on a model resident on the device,
one whole pass over every user a call."""

from __future__ import annotations

import numpy as np

from harness import data
from harness.cells import load_module

ANNOTATION = "recommend.call"


class Adapter:
    annotation = ANNOTATION

    def __init__(self, cell, seed: int, devices):
        self.config, self.traffic = cell.config, cell.traffic
        self.seed, self.devices = seed, devices
        self.users = int(self.config["num_users"])
        self.items = int(self.config["num_items"])
        self.k = int(self.traffic["k"])
        self.work_per_call = float(self.users)
        self.reference = load_module("reference", self.config["reference"])

    def setup(self) -> None:
        from mmlspark_tpu.recommendation import SARModel

        c = self.config
        self.weights = self.reference.weights(
            data.device_key(self.seed, 11), self.users, self.items,
            int(c["num_interactions"]), int(c["support_threshold"]))
        # the state a fitted or loaded model holds, already where
        # `_device_arrays` would put it
        self.model = SARModel()
        self.model.user_affinity = self.weights["affinity"]
        self.model.item_similarity = self.weights["similarity"]
        self.model.seen = self.weights["seen"]
        self.first = self.newest = None
        self.warm_out = self.call(-1)   # the one warm-up: this cell's shapes

    def call(self, i: int) -> dict:
        """-> {"items", "ratings", "same"}: `same` says whether the pass
        returned what the warm-up pass did, bit for bit. Only the newest
        answer keeps its arrays (11 MB a pass: kept for every pass they
        would be fresh pages of host memory inside each timed call)."""
        table = self.model.recommend_for_all_users(
            self.k, remove_seen=bool(self.traffic["remove_seen"]),
            user_block=self.traffic.get("user_block"))
        out = {"items": np.asarray(table["recommendations"]),
               "ratings": np.asarray(table["ratings"]), "same": True}
        if self.first is None:
            self.first = out
        else:
            out["same"] = (
                np.array_equal(out["items"], self.first["items"])
                and np.array_equal(out["ratings"], self.first["ratings"]))
            if self.newest is not self.first:
                self.newest["items"] = self.newest["ratings"] = None
        self.newest = out
        return out

    def _numbers(self, items_out, ratings_out, done) -> list:
        limits = self.traffic["limits"]
        sample = self.reference.sample_users(
            data.rng_for(self.seed, 12), self.weights,
            int(self.traffic["sample_users"]))
        wrong_shape = int(items_out.shape != (self.users, self.k)
                          or ratings_out.shape != (self.users, self.k))
        if wrong_shape:
            numbers = {"rating_gap_p90": float("nan"),
                       "topk_regret": float("nan"),
                       "seen_or_invalid": float("nan")}
        else:
            numbers = self.reference.verify(self.weights, sample, items_out,
                                            ratings_out, self.k)
        numbers["rows_or_ranks_missing"] = wrong_shape
        numbers["call_mismatch"] = sum(1 for out in done if not out["same"])
        return [(k, v, limits[k]) for k, v in numbers.items()]

    def check(self, done: list) -> list:
        """[(name, value, limit)]: the last call's recommendations against
        the plain reference, and every call's against the warm-up's, bit
        for bit. To be called before any further call is made."""
        return self._numbers(done[-1]["items"], done[-1]["ratings"], done)

    def control(self, done: list, through: str) -> list:
        """The reference through a lower precision, in the program's
        place."""
        sample = self.reference.sample_users(
            data.rng_for(self.seed, 12), self.weights,
            int(self.traffic["sample_users"]))
        items_out, ratings_out = self.reference.control_answer(
            self.weights, sample, done[-1]["items"], done[-1]["ratings"],
            self.k, through)
        return self._numbers(items_out, ratings_out, [done[-1]])
