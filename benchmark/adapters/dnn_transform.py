"""`DeepModelTransformer.transform` over a table of token ids, the whole
table a call: the scoring lane of a sequence model (README, "Adding a
neural cell"). The program's column is one dense array, so a table of
several lengths is handed over one table a length, longest first, and a
call ends when every fetched column is back on the host."""

from __future__ import annotations

import numpy as np

from harness import data, precision
from harness.cells import load_module

ANNOTATION = "transform.call"
COLUMN = "tokens"
PAD_ROWS = 8        # rows of a length's padded batch that `pad_leak` reads


def row_gaps(out: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Absolute gap of every value of every row, over the reference's
    standard deviation of that output over the row (or the median row's,
    whichever is larger)."""
    ref = ref.reshape(len(ref), -1)
    std = ref.std(axis=1)
    scale = np.maximum(np.maximum(std, np.median(std)), 1e-30)[:, None]
    return np.abs(out.reshape(len(ref), -1) - ref) / scale


class Adapter:
    annotation = ANNOTATION

    def __init__(self, cell, seed: int, devices):
        self.config, self.traffic = cell.config, cell.traffic
        self.seed, self.devices = seed, devices
        self.groups = data.length_groups(int(self.traffic["rows"]),
                                         self.traffic["lengths"])
        self.work_per_call = float(sum(length * n
                                       for length, n in self.groups))
        self.batch = int(self.traffic["mini_batch_size"])
        self.fetch = dict(self.traffic["fetch_dict"])
        self.reference = load_module("reference", self.config["reference"])

    def _key(self):
        return data.device_key(self.seed, 21)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.nn.models import ModelBundle
        from mmlspark_tpu.nn.runner import DeepModelTransformer

        c, t = self.config, self.traffic
        served = jnp.dtype(c["precision"])
        # what a loaded pretrained model fills: the module's own names, in
        # the type the configuration serves. One jitted call from the seed;
        # the reference's float32 tree is a temporary of it and is not kept,
        # so the peak of the window counts the served copy only
        variables = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(served),
            self.reference.variables(self.reference.weights(key, c), c)))(
                self._key())
        bundle = ModelBundle(
            architecture=c["architecture"],
            config=dict(c["model"], dtype=c["precision"]),
            variables=variables, input_shape=(self.groups[0][0],))
        self.stage = DeepModelTransformer(
            input_col=COLUMN, fetch_dict=self.fetch,
            mini_batch_size=self.batch, bfloat16=bool(t["bfloat16"]),
            fused_dispatch=bool(t["fused_dispatch"])).set_model(bundle)
        rng = data.rng_for(self.seed, 22)
        self.ids = [rng.integers(0, int(c["vocab_size"]), (n, length),
                                 dtype=np.int32)
                    for length, n in self.groups]
        self.tables = [Table({COLUMN: ids}) for ids in self.ids]
        self.first = self.newest = None
        self.warm_out = self.call(-1)   # the one warm-up: this cell's shapes

    def call(self, i: int) -> dict:
        """-> {"columns": [{column: array} a length], "same"}: `same` says
        whether the call returned what the warm-up call did, bit for bit.
        Only the newest answer keeps its arrays."""
        columns = []
        for table in self.tables:
            scored = self.stage.transform(table)
            columns.append({name: np.asarray(scored[name])
                            for name in self.fetch})
        out = {"columns": columns, "same": True}
        if self.first is None:
            self.first = out
        else:
            out["same"] = all(
                np.array_equal(got[name], first[name])
                for got, first in zip(columns, self.first["columns"])
                for name in self.fetch)
            if self.newest is not self.first:
                self.newest["columns"] = None
        self.newest = out
        return out

    def _samples(self) -> list:
        """Per length (rows compared, how many of them lead): a seeded
        draw of the rows in full batches, then up to `PAD_ROWS` rows of
        the batch the program pads (its first and last among them)."""
        rng = data.rng_for(self.seed, 23)
        total = sum(n for _length, n in self.groups)
        out = []
        for _length, n in self.groups:
            ragged = n % self.batch
            full = n - ragged or n
            want = max(1, int(self.traffic["sample_rows"]) * n // total)
            body = np.sort(rng.choice(full, size=min(want, full),
                                      replace=False))
            tail = np.unique(np.linspace(
                n - ragged, n - 1, min(ragged, PAD_ROWS)).astype(np.int64))
            out.append((np.concatenate([body, tail]), len(body)))
        return out

    def _reference(self, weights) -> list:
        """Per length {column: the reference's value for the sampled
        rows}."""
        return [{name: self.reference.outputs(weights, self.config,
                                              ids[rows], fetch)
                 for name, fetch in self.fetch.items()}
                for ids, (rows, _lead) in zip(self.ids, self._samples())]

    def _numbers(self, columns: list, done: list, refs: list) -> list:
        limits = self.traffic["limits"]
        gaps, leaks = [], []
        missing = nonfinite = 0
        for (_length, n), got, ref_of, (rows, lead) in zip(
                self.groups, columns, refs, self._samples()):
            for name in self.fetch:
                ref = ref_of[name]
                if got[name].shape != (n, *ref.shape[1:]):
                    missing += 1
                    continue
                nonfinite += int((~np.isfinite(got[name])).sum())
                gap = row_gaps(got[name][rows], ref)
                gaps.append(gap[:lead].ravel())
                leaks.append(gap[lead:].ravel())
        if missing:
            numbers = dict.fromkeys(
                ("output_gap_p99", "output_gap_max", "pad_leak"),
                float("nan"))
        else:
            gaps, leaks = np.concatenate(gaps), np.concatenate(leaks)
            numbers = {"output_gap_p99": float(np.quantile(gaps, 0.99)),
                       "output_gap_max": float(gaps.max()),
                       "pad_leak": float(leaks.max()) if leaks.size else 0.0}
        numbers["nonfinite"] = nonfinite
        numbers["rows_or_positions_missing"] = missing
        numbers["call_mismatch"] = sum(1 for out in done if not out["same"])
        return [(k, v, limits[k]) for k, v in numbers.items()]

    def check(self, done: list) -> list:
        """[(name, value, limit)]: the last call's fetched columns against
        the plain reference for a seeded sample of rows (`output_gap_p99`,
        `output_gap_max` over rows of full batches; `pad_leak`, the widest
        gap of a row scored in the batch the program pads, against the
        same row scored alone by the reference), every value's finiteness,
        every column's shape, and every call against the warm-up's, bit
        for bit. The reference's float32 weights are made again from the
        seed for it, beside the served copy, and dropped after. To be
        called before any further call is made."""
        return self._numbers(done[-1]["columns"], done, self._reference(
            self.reference.weights(self._key(), self.config)))

    def control(self, done: list, through: str) -> list:
        """The reference from weights rounded through a lower precision,
        in the program's place for the sampled rows. The float32 tree is
        read first and then spent by the rounding: two of them do not fit
        beside the served copy."""
        weights = self.reference.weights(self._key(), self.config)
        refs = self._reference(weights)
        low = self._reference(precision.through(weights, through))
        columns = []
        for got, low_of, (rows, _lead) in zip(done[-1]["columns"], low,
                                              self._samples()):
            column = {}
            for name in self.fetch:
                column[name] = got[name].copy()
                column[name][rows] = low_of[name]
            columns.append(column)
        return self._numbers(columns, [done[-1]], refs)
