"""DeepModelTransformer — jit-compiled batched DNN inference as a pipeline
stage.

Reference: `CNTKModel` (src/cntk-model/src/main/scala/CNTKModel.scala:147-516)
— feedDict/fetchDict params (:206-225), FixedMiniBatchTransformer batching
(:475-479), per-partition model clone + per-row `model.evaluate` JNI calls
(:30-141). TPU redesign: the model's variables live in device memory ONCE
(not re-cloned per partition, CNTKModel.scala:83), the forward pass is one
jit-compiled program per batch shape, and rows are processed in fixed-size
minibatches padded to a static shape so XLA compiles exactly once. With a
mesh, inference runs data-parallel: batch sharded over DATA_AXIS, variables
replicated.
"""

from __future__ import annotations

import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dataplane import (AsyncReadback, ExecutableCache, Prefetcher,
                              ShapeBucketer)
from ..core.params import Param
from ..core.pipeline import Model
from ..core.schema import SCORE_KIND, Table
from ..core.serialize import register_stage
from ..observability.tracing import get_tracer, jax_compile_seconds
from ..parallel.mesh import DATA_AXIS, get_mesh
from .models import ModelBundle

__all__ = ["DeepModelTransformer"]


def _fetch_from_intermediates(state: dict, path: str):
    node: Any = state["intermediates"]
    for part in path.split("."):
        node = node[part]
    if isinstance(node, dict):
        node = node["__call__"]
    if isinstance(node, (tuple, list)):
        node = node[0]
    return node


class _Call(NamedTuple):
    """What a `_transform` settles before it touches a batch."""

    x: np.ndarray            # the column as one host array
    bs: int                  # rows a batch, rounded up to the mesh
    d: int                   # data-axis shards
    fused: bool              # one dispatch for the whole table
    family: tuple            # the apply cache's key
    apply_fn: Any
    variables: Any
    fetch: dict
    counters: tuple[str, ...]


@register_stage
class DeepModelTransformer(Model):
    """Batched forward pass of a ModelBundle over a Table column.

    fetch_dict maps output column -> "logits" | "probability" |
    "<intermediate path>" (a layer name from bundle.layer_names())."""

    input_col = Param("features", "input column (stacked to (n, ...))", ptype=str)
    fetch_dict = Param(
        {"output": "logits"}, "output column -> logits|probability|<layer path>"
    )
    mini_batch_size = Param(64, "rows per compiled device batch", ptype=int)
    use_mesh = Param(False, "shard batches over the data mesh axis", ptype=bool)
    # One host->device transfer + ONE dispatch for the whole table (a jitted
    # lax.scan over minibatches) instead of one dispatch per minibatch.
    # Per-dispatch latency dominates batched transforms of small
    # minibatches (the reference pays the same cost per JNI evaluate call,
    # CNTKModel.scala:131-138); bounded by fused_dispatch_budget_mb so huge
    # tables still stream batch-by-batch.
    fused_dispatch = Param(True, "scan all minibatches in one dispatch", ptype=bool)
    fused_dispatch_budget_mb = Param(
        512, "max input MB eligible for the fused single-dispatch path", ptype=int
    )
    bfloat16 = Param(
        False, "run the forward in bfloat16 (MXU-native; outputs stay float32)",
        ptype=bool,
    )
    # Async data plane (non-fused path): a bounded background thread
    # featurizes/pads/uploads minibatch N+1 while the device computes
    # minibatch N, and host readback of minibatch N-1 overlaps both.
    # Depth 0 is the strictly sequential fallback — outputs are
    # byte-identical at any depth (shapes and order never change).
    prefetch_depth = Param(
        2, "minibatches prepared ahead of device compute (0 = sequential)",
        ptype=int,
    )
    # Ragged tails pad to a power-of-two bucket ladder (<= mini_batch_size)
    # instead of all the way up to mini_batch_size: less wasted tail
    # compute, and the compiled-shape set stays a small closed ladder.
    shape_buckets = Param(
        True, "pad ragged tails to a pow-2 bucket ladder (vs full batch)",
        ptype=bool,
    )

    bundle: ModelBundle | None = None
    _apply_cache: dict | None = None
    _outbytes_cache: dict | None = None
    _exec_cache: ExecutableCache | None = None
    #: stats from the most recent pipelined (non-fused) _transform:
    #: prepare/wait seconds, overlap_fraction, executable-cache counters.
    #: A call's totals only: the per-batch view is the `runner.*` spans'
    #: (observability/tracing.py), so this dict need not grow
    last_pipeline_stats: dict | None = None

    def set_model(self, bundle: ModelBundle) -> "DeepModelTransformer":
        self.bundle = bundle
        self._apply_cache = {}
        self._outbytes_cache = {}
        self._exec_cache = ExecutableCache()
        return self

    # ------------------------------------------------------------------ #

    def _forward_fn(self, fetches: tuple[str, ...],
                    counters: tuple[str, ...] = ()):
        """`counters`: int32 arrays the module sows per batch
        (`batch_counters`), returned after the fetched outputs as they are.
        Only the streamed path asks for them."""
        bundle = self.bundle
        module = bundle.module
        need_caps = bool(counters) or any(
            f not in ("logits", "probability") for f in fetches)
        mean = np.asarray(bundle.preprocess.get("mean", 0.0), np.float32)
        std = np.asarray(bundle.preprocess.get("std", 1.0), np.float32)
        use_bf16 = bool(self.get("bfloat16"))

        def forward(variables, x):
            x = (x.astype(jnp.float32) - mean) / std
            if use_bf16:
                x = x.astype(jnp.bfloat16)
            if need_caps:
                logits, state = module.apply(
                    variables, x, train=False,
                    capture_intermediates=True, mutable=["intermediates"],
                )
            else:
                logits = module.apply(variables, x, train=False)
                state = None
            logits = logits.astype(jnp.float32)
            outs = []
            for f in fetches:
                if f == "logits":
                    outs.append(logits)
                elif f == "probability":
                    outs.append(jax.nn.softmax(logits, axis=-1))
                else:
                    outs.append(
                        _fetch_from_intermediates(state, f).astype(jnp.float32)
                    )
            outs.extend(_fetch_from_intermediates(state, c) for c in counters)
            return tuple(outs)

        return forward

    def _jit(self, fn, *lead):
        """`fn(variables, x)` jitted; under a mesh the variables replicated
        and x sharded over the data axis, after its `lead` axes."""
        if not self.get("use_mesh"):
            return jax.jit(fn)
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = get_mesh()
        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P(*lead, DATA_AXIS))
        return jax.jit(fn, in_shardings=(repl, data), out_shardings=repl)

    def _make_apply(self, fetches: tuple[str, ...],
                    counters: tuple[str, ...] = ()):
        return self._jit(self._forward_fn(fetches, counters))

    def _make_apply_fused(self, fetches: tuple[str, ...]):
        """Jit of scan(forward) over (nb, bs, ...) — whole table, one dispatch."""
        forward = self._forward_fn(fetches)

        def scanned(variables, xall):
            def body(_, xb):
                return 0, forward(variables, xb)

            _, outs = jax.lax.scan(body, 0, xall)
            return outs                                # tuple of (nb, bs, ...)

        return self._jit(scanned, None)

    def _transform(self, table: Table) -> Table:
        if self.bundle is None:
            raise ValueError("DeepModelTransformer has no model; call set_model()")
        tracer = get_tracer()
        if self.get("fused_dispatch"):
            # the one-dispatch path opens no `runner.*` span; a table its
            # budget sends to the streamed loop gets its root span here,
            # after the stacking
            call = self._settle(table)
            if call.fused:
                return self._score(table, call, None)
            with tracer.start_span("runner.transform") as root:
                return self._score(table, call, root)
        # streamed by the stage's own setting: the call's root span opens
        # at the entry, over the column's stacking too
        with tracer.start_span("runner.transform") as root:
            with tracer.start_span("runner.stack") as span:
                call = self._settle(table)
                span.set(bytes=int(call.x.nbytes))
            return self._score(table, call, root)

    def _settle(self, table: Table) -> _Call:
        """The column made one host array, the batch size, whether the
        fused path's budget takes the table, and the jitted forward from
        the apply cache."""
        col = table[self.get("input_col")]
        x = np.stack(col) if isinstance(col, list) else np.asarray(col)
        n = x.shape[0]
        fetch = dict(self.get("fetch_dict"))
        fetches = tuple(fetch.values())

        bs = int(self.get("mini_batch_size"))
        d = 1
        if self.get("use_mesh"):
            d = get_mesh().shape[DATA_AXIS]
            bs = ((bs + d - 1) // d) * d

        pad = (-n) % bs
        fused = bool(self.get("fused_dispatch"))
        if fused:
            # the fused scan holds the inputs AND every fetched output for
            # the WHOLE table on device at once — a narrow input with a wide
            # intermediate fetch can dwarf x.nbytes, so budget both sides.
            # The per-batch output size is an eval_shape (abstract trace);
            # cache it so per-request transforms (serving) don't re-trace
            # the model just to size its outputs.
            if self._outbytes_cache is None:
                self._outbytes_cache = {}
            okey = (fetches, bs, x.shape[1:], str(x.dtype), id(self.bundle))
            if okey not in self._outbytes_cache:
                out_abs = jax.eval_shape(
                    self._forward_fn(fetches),
                    self.bundle.variables,
                    jax.ShapeDtypeStruct((bs, *x.shape[1:]), x.dtype),
                )
                self._outbytes_cache[okey] = sum(
                    int(np.prod(o.shape)) * o.dtype.itemsize for o in out_abs
                )
            per_batch = self._outbytes_cache[okey]
            row_bytes = x.nbytes // n if n else 0
            total = row_bytes * (n + pad) + per_batch * ((n + pad) // bs)
            fused = total <= int(self.get("fused_dispatch_budget_mb")) * 2**20

        if self._apply_cache is None:
            self._apply_cache = {}
        # id(bundle) in the key: assigning a new bundle directly (without
        # set_model) must not score with stale cached/cast weights
        key = (fetches, bs, self.get("use_mesh"),
               self.get("bfloat16"), id(self.bundle), fused)
        counters = tuple(getattr(self.bundle.module, "batch_counters", ()))
        if key not in self._apply_cache:
            # weights cast ONCE; per-call casting would re-upload them
            variables = self._device_variables()
            # what a module sows per batch for the runner (`batch_counters`)
            # rides with the streamed path's lagged readback; a module
            # without takes the path as it was
            made = (self._make_apply_fused(fetches) if fused
                    else self._make_apply(fetches, counters))
            self._apply_cache[key] = (made, variables)
        apply_fn, variables = self._apply_cache[key]
        return _Call(x, bs, d, fused, key, apply_fn, variables, fetch,
                     counters)

    def _score(self, table: Table, call: _Call, root) -> Table:
        """`root`: a streamed call's `runner.transform` span; the fused
        one-dispatch path has none."""
        if call.fused:
            x, bs, n = call.x, call.bs, call.x.shape[0]
            pad = (-n) % bs
            if pad:
                x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])
            nb = len(x) // bs
            outs = call.apply_fn(
                call.variables, jnp.asarray(x.reshape(nb, bs, *x.shape[1:])))
            cols = [np.asarray(o).reshape(nb * bs, *o.shape[2:])[:n] for o in outs]
        else:
            cols = self._transform_pipelined(call, root)

        out = table
        for (col_name, fetch_name), arr in zip(call.fetch.items(), cols):
            kind = "probability" if fetch_name == "probability" else "raw_prediction"
            out = out.with_column(col_name, arr, meta={SCORE_KIND: kind})
        return out

    def _transform_pipelined(self, call: _Call, root) -> list[np.ndarray]:
        """Non-fused loop on the async data plane: prepare (slice + pad +
        upload) of minibatch N+1 overlaps device compute on N, and host
        readback lags one batch so it overlaps too. Shapes, batch order,
        and per-row outputs are identical at every prefetch depth.

        Every phase of a batch is a span under the call's root (the table
        in observability/tracing.py's docstring): the prefetcher's
        `runner.feed_wait` and `runner.prepare` with `runner.upload` inside
        it, and under `runner.step`, `runner.dispatch`, then `runner.wait`
        and `runner.readback` of the batch before."""
        x, bs, family, variables = call.x, call.bs, call.family, call.variables
        fetches, counters = tuple(call.fetch.values()), call.counters
        n = x.shape[0]
        bucketer = (ShapeBucketer(bs, shards=call.d)
                    if self.get("shape_buckets") else None)
        if self._exec_cache is None:
            self._exec_cache = ExecutableCache()
        tracer = get_tracer()

        def prepare(i: int):
            chunk = x[i:i + bs]
            m = chunk.shape[0]
            if bucketer is not None:
                padded, _ = bucketer.pad(chunk)
            elif m < bs:
                padded = np.concatenate(
                    [chunk, np.repeat(chunk[-1:], bs - m, axis=0)])
            else:
                padded = chunk
            # the prefetcher's `runner.prepare` is the active span here, on
            # whichever thread prepares
            around = tracer.current_span()
            if around is not None:
                around.set(rows=m, padded=int(padded.shape[0]),
                           bytes=int(padded.nbytes))
            with tracer.start_span("runner.upload", bytes=int(padded.nbytes)):
                return jnp.asarray(padded), m

        prefetch = Prefetcher(range(0, n, bs), prepare,
                              depth=int(self.get("prefetch_depth")),
                              name="runner", span=root, tracer=tracer)
        # fetch = block on the device result, copy it and slice the padding
        # off (a batch's counters, which follow the fetched outputs, have
        # no rows to slice); lag 1 keeps batch N-1's readback behind batch
        # N's dispatch. The wait stands before the copies, which blocked
        # there anyway: no value and no order changes
        nf = len(fetches)

        def fetch(parked):
            out, m, batch = parked
            with tracer.start_span("runner.wait", batch=batch):
                jax.block_until_ready(out)
            with tracer.start_span("runner.readback", batch=batch) as span:
                if tracer.enabled:      # the sum is the span's alone
                    span.set(bytes=sum(int(a.nbytes) for a in out))
                return (tuple(np.asarray(a)[:m] for a in out[:nf])
                        + tuple(np.asarray(a) for a in out[nf:]))

        readback = AsyncReadback(fetch, lag=1)
        chunks: list[tuple[np.ndarray, ...]] = []
        scored: list[int] = []          # rows a batch scored, padding too
        paid_before: list[float] = []

        def build():
            # runs on a miss only, and returns at once: the new entry's
            # first CALL traces, lowers and compiles, and what the tracer's
            # bridge sees of that on this thread is the entry's compile
            # seconds
            paid_before.append(jax_compile_seconds())
            return call.apply_fn

        root.set(rows=n, batch_size=bs, row_shape=list(x.shape[1:]))
        for xb, m in prefetch:
            shape_key = (int(xb.shape[0]), tuple(xb.shape[1:]),
                         str(xb.dtype))
            scored.append(int(xb.shape[0]))
            with tracer.start_span("runner.step", padded=int(xb.shape[0]),
                                   rows=m):
                # jit compiles once per entry here (its `jax.*` spans
                # hang under the dispatch); the counters make ragged
                # shapes defeating the ladder visible (recompiles > 0)
                with tracer.start_span("runner.dispatch") as dispatch:
                    fn = self._exec_cache.get_or_build(family, shape_key,
                                                       build)
                    out = fn(variables, xb)
                    dispatch.set(cache="miss" if paid_before else "hit")
                if paid_before:
                    self._exec_cache.add_compile_seconds(
                        family, shape_key,
                        jax_compile_seconds() - paid_before.pop())
                chunks.extend(readback.push((out, m, len(scored) - 1)))
        chunks.extend(readback.drain())
        # a batch's counters follow its fetched outputs in the module's
        # order; what they say of the call is the module's to tell
        counted = {name: np.stack([c[nf + i] for c in chunks])
                   for i, name in enumerate(counters)} if chunks else {}
        report = getattr(self.bundle.module, "call_span_arguments", None)
        if report is not None:
            root.set(**report(counted, scored, x.shape[1:]))
        self.last_pipeline_stats = {
            **prefetch.stats,
            "overlap_fraction": prefetch.overlap_fraction(),
            "prefetch_depth": prefetch.depth,
            "bucket_ladder": list(bucketer.ladder) if bucketer else [bs],
            **self._exec_cache.stats(),
        }
        return [np.concatenate([c[j] for c in chunks])
                for j in range(len(fetches))]

    # -- fusion --------------------------------------------------------- #

    def _device_variables(self):
        """The bundle's variables as a compiled program takes them
        (`_apply_cache`, the fusion kernel's device-resident params):
        cast to bfloat16 where the stage says so."""
        variables = self.bundle.variables
        if self.get("bfloat16"):
            variables = jax.tree.map(
                lambda a: a.astype(jnp.bfloat16)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
                variables,
            )
        return variables

    def _tp_forward_fn(self, fetches: tuple[str, ...], mesh):
        """Column-parallel forward for the fused tensor-parallel path, or
        None when this model can't take it (then the fused engine's default
        — rows sharded, variables replicated — applies).

        Only the hand-rolled MLP layout qualifies: its forward is a chain
        of Dense+relu, which maps exactly onto `gathered_column_parallel`
        (each chip computes a full-contraction slice of the output
        features, then a tiled all_gather reassembles them) — identical
        arithmetic to the unsharded matmul, so byte-identity holds.
        Returns (forward, variable_shardings)."""
        from ..parallel.mesh import MODEL_AXIS
        from ..parallel.tensor_parallel import (dense_column_shardings,
                                                dense_column_specs,
                                                gathered_column_parallel)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        bundle = self.bundle
        n_model = int(dict(mesh.shape).get(MODEL_AXIS, 1))
        if n_model <= 1:
            return None  # pure data parallelism: nothing to specialize
        if bundle.architecture != "mlp":
            return None
        if any(f not in ("logits", "probability") for f in fetches):
            return None  # intermediate captures need module.apply
        if self.get("bfloat16"):
            return None  # bf16 accumulation order voids byte-identity
        variables = bundle.variables
        if set(variables) != {"params"}:
            return None
        params = variables["params"]
        if "head" not in params:
            return None
        names = sorted((nm for nm in params if nm.startswith("dense_")),
                       key=lambda nm: int(nm.split("_", 1)[1]))
        names.append("head")
        if set(names) != set(params):
            return None
        for nm in names:
            layer = params[nm]
            k, b = layer.get("kernel"), layer.get("bias")
            if (k is None or b is None
                    or np.ndim(k) != 2 or np.ndim(b) != 1
                    or jnp.asarray(k).dtype != jnp.float32):
                return None
            if k.shape[1] % n_model:
                return None  # output features must split evenly

        mean = np.asarray(bundle.preprocess.get("mean", 0.0), np.float32)
        std = np.asarray(bundle.preprocess.get("std", 1.0), np.float32)
        # gather schedule: XLA's monolithic all_gather by default; the
        # hand-scheduled collective-permute ring (same bytes, each step
        # independently schedulable) when the phase ledger showed the
        # gather NOT overlapping compute on this mesh.
        ring = os.environ.get("MMLSPARK_TPU_RING_GATHER", "") == "1"

        def tp_body(variables, x):
            p = variables["params"]
            h = x.reshape((x.shape[0], -1))
            for nm in names:
                h = gathered_column_parallel(
                    h, p[nm]["kernel"], p[nm]["bias"], MODEL_AXIS, ring=ring)
                if nm != "head":
                    h = jax.nn.relu(h)
            return h

        specs = {"params": dense_column_specs(params)}
        # check_vma=False: a tiled all_gather leaves every model-axis chip
        # with the same full feature row, but its result is typed "varying"
        # over that axis, so out_specs' replication cannot be inferred
        body = shard_map(tp_body, mesh=mesh,
                         in_specs=(specs, P(DATA_AXIS, None)),
                         out_specs=P(DATA_AXIS, None), check_vma=False)

        def forward(variables, x):
            x = (x.astype(jnp.float32) - mean) / std
            logits = body(variables, x).astype(jnp.float32)
            return tuple(jax.nn.softmax(logits, axis=-1)
                         if f == "probability" else logits
                         for f in fetches)

        shardings = {"params": dense_column_shardings(mesh, params)}
        return forward, shardings

    def device_kernel(self):
        """Fusion kernel (core/fusion.py): the same `_forward_fn` the staged
        path jits, with the variables passed as device-resident params.
        The forward is row-independent (eval mode — no batch statistics),
        so the engine's chunking/padding cannot change any row's value.
        Under a mesh the engine row-shards batches by default; a mesh with
        a >1 model axis additionally swaps in the column-parallel forward
        via `mesh_fn` (weights sharded on output features)."""
        from ..core.fusion import DeviceKernel

        if self.bundle is None:
            return "no model bundle attached (call set_model())"
        fetch = dict(self.get("fetch_dict"))
        fetches = tuple(fetch.values())
        out_cols = tuple(fetch.keys())
        in_col = self.get("input_col")
        forward = self._forward_fn(fetches)

        def fn(params, cols):
            outs = forward(params, cols[in_col])
            return dict(zip(out_cols, outs))

        def ready(table: Table):
            if isinstance(table[in_col], list):
                return f"column {in_col!r} is a ragged list (host stacks it)"
            return True

        def mesh_fn(mesh):
            tp = self._tp_forward_fn(fetches, mesh)
            if tp is None:
                return None
            tp_forward, shardings = tp

            def tp_fn(params, cols):
                outs = tp_forward(params, cols[in_col])
                return dict(zip(out_cols, outs))

            return tp_fn, shardings

        meta = {c: {SCORE_KIND: "probability" if f == "probability"
                    else "raw_prediction"} for c, f in fetch.items()}
        return DeviceKernel(
            fn=fn, input_cols=(in_col,), output_cols=out_cols,
            params=self._device_variables(), name="DeepModelTransformer",
            out_dtypes={c: np.float32 for c in out_cols},
            out_meta=meta, ready=ready, mesh_fn=mesh_fn,
            mesh_desc=("rows P(data); dense kernels column-parallel "
                       "P(None,model) + tiled all_gather when the mesh has "
                       "a >1 model axis, else variables replicated"))

    # -- persistence ---------------------------------------------------- #

    def _save_state(self) -> dict[str, Any]:
        import base64
        import tempfile

        if self.bundle is None:
            return {}
        with tempfile.NamedTemporaryFile(delete=False) as fh:
            tmp = fh.name
        try:
            self.bundle.save(tmp)
            with open(tmp, "rb") as fh2:
                blob = fh2.read()
        finally:
            os.unlink(tmp)
        return {"bundle": base64.b64encode(blob).decode()}

    def _load_state(self, state: dict[str, Any]) -> None:
        import base64
        import tempfile

        if not state.get("bundle"):
            return
        blob = base64.b64decode(state["bundle"])
        with tempfile.NamedTemporaryFile(delete=False) as fh:
            fh.write(blob)
            tmp = fh.name
        try:
            self.bundle = ModelBundle.load(tmp)
        finally:
            os.unlink(tmp)
        self._apply_cache = {}
        self._exec_cache = ExecutableCache()
