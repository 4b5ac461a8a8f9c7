"""Whole-pipeline fusion: compile adjacent device-capable stages into ONE
XLA program with device-resident tables.

The role model is Spark SQL's whole-stage codegen (Neumann, "Efficiently
Compiling Efficient Query Plans"; Spark's `WholeStageCodegenExec`): instead
of running operators one at a time with materialized intermediates, compile
a maximal run of compatible operators into a single tight program.  Here
the operators are pipeline stages and the program is an XLA executable:
`PipelineModel._transform` runs stage-by-stage, so a featurize -> model ->
post-process chain crosses the host/device boundary once per jittable
stage (device_put, jit dispatch, full host read-back — 3x per batch for
that chain).  Fusion partitions the stage list into maximal runs of stages
that declare a pure device kernel, compiles each run into one jitted
composition, and keeps columns device-resident across stage boundaries.
Host materialization happens only at non-fusable boundaries (HTTP /
cognitive / text / grouping stages), which run exactly as before.

Stage protocol
--------------
A stage opts in by implementing::

    def device_kernel(self) -> DeviceKernel | str | None

returning a `DeviceKernel` when it can run on device, or a reason string
(or None) when it cannot.  A kernel's `fn(params, cols)` must be a pure,
jit-traceable, ROW-INDEPENDENT function over a dict of device columns —
row independence is what makes the engine's pad-to-bucket and chunked
execution semantics exact (padding rows are sliced away, chunk boundaries
cannot change any real row's value).  `params` is the kernel's
device-resident table (model variables, GBDT node arrays, ...): uploaded
once per segment via `device_put` and reused across every batch, never
baked into the executable as constants.

Integration
-----------
* `ExecutableCache` (core.dataplane) tracks one family per fused segment;
  ragged row counts pad up a `ShapeBucketer` ladder so steady-state
  recompiles stay at zero.
* Large tables stream through the segment in `mini_batch_size` chunks on
  the async data plane (`prefetch_depth` overlaps upload of chunk N+1
  with device compute on N).
* Each segment execution opens a `pipeline.fused_segment` span and the
  model publishes a `mmlspark_tpu_pipeline_fusion_ratio` gauge.

`serve_model` and `StreamingQuery` fuse `PipelineModel` handlers
automatically; `fuse()` is idempotent and `FusedPipelineModel` serializes
like the `PipelineModel` it wraps.

Sharded execution
-----------------
A fused segment optionally compiles under a `parallel.mesh` mesh
(`fuse(model, mesh=...)`, `FusedPipelineModel.set_mesh`, or the `use_mesh`
param picking up `get_mesh()`).  Batch chunks upload row-sharded over the
data axis (`data_sharding`), kernel params upload replicated
(`replicated_sharding`) unless the kernel supplies a `mesh_fn` with its own
placement (e.g. tensor-parallel matmul weights), and the jitted composition
is compiled with the inputs' committed shardings — GSPMD inserts the
collectives.  Chunk sizes and bucket-ladder steps round up to multiples of
the data-axis size so every shard gets equal rows; the executable-cache
family key gains `(mesh_shape, sharding_spec)` so sharded and single-chip
executables never collide.  Because kernels are row-independent and the
engine only ever row-shards them (a kernel's own `mesh_fn` must preserve
values too), the sharded result is byte-identical to the single-device
fused path.  A `mesh` of one device (or none) is exactly the single-chip
path.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import numpy as np

from .dataplane import AsyncReadback, ExecutableCache, Prefetcher, ShapeBucketer
from .params import Param
from .pipeline import PipelineModel, Transformer
from .schema import Table
from .serialize import register_stage
from .table_io import DeviceTable

__all__ = [
    "DeviceKernel",
    "StagePlan",
    "SegmentPlan",
    "FusionPlan",
    "ResidentExecutor",
    "kernel_of",
    "plan_fusion",
    "fuse",
    "FusedPipelineModel",
]


@dataclass
class DeviceKernel:
    """One stage's pure device program plus its column contract.

    fn(params, cols) -> dict of output columns; `cols` maps column name to
    a device array and contains at least `input_cols`.  The function must
    be row-independent (see module docstring).  `out_dtypes` maps output
    columns to the HOST dtype the staged path would produce — the engine
    casts after read-back so fused and staged tables carry identical
    schemas (e.g. float32 device features widening to a float64 column is
    exact).  `out_meta` carries per-column `ColumnMeta`; a value may be a
    callable taking the downloaded ndarray (for shape-dependent metadata
    like IMAGE_SPEC).  `ready(table)` is the runtime fusability check on
    the HOST inputs (dtype / uniformity preconditions); returning a string
    vetoes fusion for that table and the segment falls back to the staged
    path.  `ready_values(cols)` is the cheap VALUE-dependent subset of
    `ready` over a plain `{col: ndarray}` dict: a serving hot path that
    already validated the schema once at warmup calls only this per batch
    (a kernel with a value-dependent `ready` but no `ready_values` keeps
    paying the full check — no precondition is ever silently skipped).

    Mesh hooks: by default a kernel runs unchanged under a mesh — rows
    shard over the data axis, `params` replicate.  `mesh_fn(mesh)` lets a
    kernel specialize beyond that: return `(fn, param_shardings)` to swap
    in a mesh-aware body (e.g. tensor-parallel matmuls) with explicit
    param placement, or None to accept the default.  Any specialized body
    must still produce byte-identical values.  `mesh_desc` is the
    human-readable sharding contract `fusion_report` prints, and
    `kernel_label` names the device program variant (e.g. the GBDT
    models' `fused_traverse`) so the plan output pins WHICH kernel a
    segment compiles — a silent fallback to a slower variant shows up
    as a diff in CI."""

    fn: Callable[[Any, dict], dict]
    input_cols: tuple[str, ...]
    output_cols: tuple[str, ...]
    params: Any = None
    name: str = ""
    out_dtypes: dict[str, Any] = field(default_factory=dict)
    out_meta: dict[str, Any] = field(default_factory=dict)
    ready: "Callable[[Table], Any] | None" = None
    ready_values: "Callable[[dict], Any] | None" = None
    mesh_fn: "Callable[[Any], tuple | None] | None" = None
    mesh_desc: str = "rows P(data) / params replicated"
    kernel_label: str = ""


@dataclass
class StagePlan:
    stage: Any
    kernel: "DeviceKernel | None"
    reason: str = ""  # why the stage stays on host ("" when fused)

    @property
    def fused(self) -> bool:
        return self.kernel is not None


@dataclass
class SegmentPlan:
    fused: bool
    stages: list[StagePlan]


@dataclass
class FusionPlan:
    segments: list[SegmentPlan]

    @property
    def n_stages(self) -> int:
        return sum(len(s.stages) for s in self.segments)

    @property
    def n_fused_stages(self) -> int:
        return sum(len(s.stages) for s in self.segments if s.fused)

    @property
    def fusion_ratio(self) -> float:
        n = self.n_stages
        return (self.n_fused_stages / n) if n else 0.0

    def transfers_per_batch(self) -> tuple[int, int]:
        """(fused, staged) host<->device boundary crossings per batch:
        fused pays one upload + one read-back per fused segment; the
        staged path pays the same pair once per device-capable STAGE."""
        fused = 2 * sum(1 for s in self.segments if s.fused)
        staged = 2 * self.n_fused_stages
        return fused, staged

    def describe(self, mesh: Any = None, donate: "bool | None" = None,
                 pipeline_depth: "int | None" = None) -> str:
        """Human-readable segment plan (tools/fusion_report.py prints it).
        With a mesh, each fused segment also shows the mesh shape and the
        per-stage sharding spec it would compile under; `donate` /
        `pipeline_depth` (the model's runtime knobs) print next to it so a
        non-donating or unpipelined segment is visible in CI output."""
        lines = []
        fused_t, staged_t = self.transfers_per_batch()
        mesh_label = ("x".join(str(s) for s in mesh.shape.values())
                      if mesh is not None else "1")
        for i, seg in enumerate(self.segments):
            kind = "FUSED" if seg.fused else "HOST"
            suffix = f" mesh={mesh_label}" if seg.fused else ""
            if seg.fused and donate is not None:
                suffix += f" donate={'on' if donate else 'OFF'}"
            if seg.fused and pipeline_depth is not None:
                suffix += f" in_flight={int(pipeline_depth) + 1}"
            lines.append(f"segment {i} [{kind}]{suffix}")
            for sp in seg.stages:
                name = type(sp.stage).__name__
                if seg.fused:
                    k = sp.kernel
                    label = f" kernel={k.kernel_label}" if k.kernel_label \
                        else ""
                    lines.append(
                        f"  {name}: {','.join(k.input_cols)} -> "
                        f"{','.join(k.output_cols)}{label}")
                    lines.append(f"    sharding: {k.mesh_desc}")
                else:
                    lines.append(f"  {name}: {sp.reason}")
        lines.append(
            f"fused {self.n_fused_stages}/{self.n_stages} stages "
            f"(ratio {self.fusion_ratio:.2f}); transfers/batch: "
            f"{fused_t} fused vs {staged_t} staged device-stage pairs")
        return "\n".join(lines)


def kernel_of(stage: Any) -> tuple["DeviceKernel | None", str]:
    """(kernel, reason): a stage's declared device kernel, or why it has
    none.  A stage opts out by returning a reason string; an exception from
    `device_kernel()` is a defect and propagates."""
    decl = getattr(stage, "device_kernel", None)
    if decl is None:
        return None, "no device kernel declared"
    k = decl()
    if isinstance(k, DeviceKernel):
        if not k.name:
            k.name = type(stage).__name__
        return k, ""
    return None, (k if isinstance(k, str) else "stage declared itself non-fusable")


def _flatten(stages: Sequence[Any]) -> list[Any]:
    """Flatten nested PipelineModels into their leaf stages (sequential
    composition is associative, so this never changes semantics — and it
    lets fusable leaves inside a nested model join an adjacent run)."""
    out: list[Any] = []
    for s in stages:
        if isinstance(s, PipelineModel):
            out.extend(_flatten(s.get("stages") or []))
        else:
            out.append(s)
    return out


def plan_fusion(stages: Sequence[Any]) -> FusionPlan:
    """Partition a stage list into maximal fused runs / host runs."""
    segments: list[SegmentPlan] = []
    for stage in _flatten(stages):
        kernel, reason = kernel_of(stage)
        sp = StagePlan(stage, kernel, reason)
        if segments and segments[-1].fused == sp.fused:
            segments[-1].stages.append(sp)
        else:
            segments.append(SegmentPlan(sp.fused, [sp]))
    return FusionPlan(segments)


# --------------------------------------------------------------------- #
# fused segment runtime                                                 #
# --------------------------------------------------------------------- #


class _FusedSegment:
    """One maximal run of device-capable stages compiled as a single jitted
    composition over device-resident columns.  With a mesh (always >1
    device — callers normalize 1-device meshes to None so the single-chip
    path stays exactly the pre-mesh one), inputs row-shard over the data
    axis and params replicate unless a kernel's `mesh_fn` placed them
    itself."""

    def __init__(self, index: int, plans: list[StagePlan], mesh: Any = None,
                 donate: bool = False):
        self.index = index
        self.plans = plans
        self.mesh = mesh
        self.donate = bool(donate)
        self.kernels = [p.kernel for p in plans]
        self.stage_names = [type(p.stage).__name__ for p in plans]
        # upload set: inputs not produced by an earlier kernel in the run;
        # download set: the FINAL value of every column any kernel produces
        produced: dict[str, DeviceKernel] = {}
        uploads: list[str] = []
        for k in self.kernels:
            for c in k.input_cols:
                if c not in produced and c not in uploads:
                    uploads.append(c)
            for c in k.output_cols:
                produced[c] = k  # last producer wins
        self.upload_cols = tuple(uploads)
        self.download_cols = tuple(produced)
        self._last_producer = produced
        self._exec_cache = ExecutableCache()
        self._jitted = None
        self._composed = None
        self._device_params = None
        self._bodies = None
        self._param_placements: "tuple[str, ...] | None" = None

    # -- compilation ---------------------------------------------------- #

    def _build(self):
        import jax

        if self._device_params is None:
            # the device-resident tables: model variables, tree SoAs, bin
            # boundaries — uploaded once, reused by every batch (never
            # captured as jit constants, so they are not re-staged per
            # compiled shape)
            if self.mesh is None:
                self._device_params = tuple(
                    jax.tree.map(jax.device_put, k.params)
                    if k.params is not None else None
                    for k in self.kernels
                )
                self._bodies = [k.fn for k in self.kernels]
                self._param_placements = tuple(
                    "single" for _ in self.kernels)
            else:
                from ..parallel.mesh import replicated_sharding

                repl = replicated_sharding(self.mesh)
                bodies, dparams, placements = [], [], []
                for k in self.kernels:
                    body, shardings = k.fn, None
                    if k.mesh_fn is not None:
                        spec = k.mesh_fn(self.mesh)
                        if spec is not None:
                            body, shardings = spec
                    bodies.append(body)
                    if k.params is None:
                        dparams.append(None)
                        placements.append("none")
                    elif shardings is None:
                        dparams.append(jax.device_put(k.params, repl))
                        placements.append("replicated")
                    else:
                        dparams.append(jax.device_put(k.params, shardings))
                        placements.append("custom")
                self._device_params = tuple(dparams)
                self._bodies = bodies
                self._param_placements = tuple(placements)
        if self._jitted is None:
            bodies = self._bodies
            upload_cols = self.upload_cols
            download_cols = self.download_cols

            def composed(params_tuple, in_arrays):
                cols = dict(zip(upload_cols, in_arrays))
                for body, p in zip(bodies, params_tuple):
                    cols.update(body(p, cols))
                return tuple(cols[c] for c in download_cols)

            # no in/out_shardings: the committed placement of the uploaded
            # params and row-sharded chunks drives GSPMD partitioning.
            # Donation hands each chunk's input buffers (arg 1, the batch
            # tuple — NEVER arg 0: params are pinned and reused every
            # batch) to XLA for output reuse: steady-state batches recycle
            # donated device memory instead of allocating fresh.  Safe
            # because the engine never reads a chunk's device inputs after
            # its dispatch (every chunk uploads a fresh DeviceTable).
            self._composed = composed
            if self.donate:
                # XLA declines a donation whenever no output wants a
                # buffer of that size/layout and warns per call; that is
                # an allocator outcome, not an error (fusion_report and
                # executor stats carry the donation status), so the
                # per-call warning is pure noise
                import warnings

                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                self._jitted = jax.jit(composed, donate_argnums=(1,))
            else:
                self._jitted = jax.jit(composed)
        return self._jitted, self._device_params

    def _family_key(self, ins: dict) -> Any:
        """Executable-cache family: program lineage = this segment's column
        contract plus, under a mesh, (mesh_shape, sharding_spec) — a mesh
        change is a NEW family, never a recompile of the old one."""
        # donation changes the compiled program (input/output aliasing is
        # part of the executable), so it is part of the family lineage
        base = (id(self), ("donate", self.donate), tuple(
            (c, str(ins[c].dtype), ins[c].shape[1:]) for c in self.upload_cols))
        if self.mesh is None:
            return base
        self._build()  # placements are part of the lineage
        spec = tuple(zip((k.name for k in self.kernels),
                         self._param_placements)) + tuple(
            (c, "P(data)") for c in self.upload_cols)
        return ExecutableCache.family_key(
            base, mesh_shape=tuple(self.mesh.shape.items()),
            sharding_spec=spec)

    # -- execution ------------------------------------------------------ #

    def check_ready(self, table: Table) -> str:
        """'' when this table can run fused, else the blocking reason."""
        if table.num_rows == 0:
            return "empty batch (padding has no row to repeat)"
        for c in self.upload_cols:
            if c not in table:
                return f"input column {c!r} missing"
            col = table[c]
            if not isinstance(col, np.ndarray) or col.dtype == object:
                return f"input column {c!r} is not a dense ndarray"
        produced: set[str] = set()
        for k in self.kernels:
            # a `ready` precondition is a check on HOST inputs; once any of
            # the kernel's inputs is a device intermediate produced earlier
            # in the segment, its dtype/layout is fixed by the upstream
            # kernel's contract and there is no host column to inspect
            if k.ready is not None and produced.isdisjoint(k.input_cols):
                ok = k.ready(table)
                if ok is not True and ok is not None:
                    return str(ok)
            produced.update(k.output_cols)
        return ""

    def check_ready_values(self, cols: dict) -> str:
        """'' when these host input VALUES can run fused, else the blocking
        reason.  The cheap per-batch complement of `check_ready` for a
        serving hot path that validated schema/shape ONCE at warmup: only
        each kernel's `ready_values` hook runs (vectorized, no Table
        construction); a kernel with a value-dependent `ready` but no hook
        falls back to its full check so no precondition is skipped."""
        produced: set[str] = set()
        table = None
        for k in self.kernels:
            if produced.isdisjoint(k.input_cols):
                if k.ready_values is not None:
                    ok = k.ready_values(cols)
                    if ok is not True and ok is not None:
                        return str(ok)
                elif k.ready is not None:
                    if table is None:
                        table = Table(dict(cols))
                    ok = k.ready(table)
                    if ok is not True and ok is not None:
                        return str(ok)
            produced.update(k.output_cols)
        return ""

    def run_host(self, table: Table) -> Table:
        for p in self.plans:
            table = p.stage.transform(table)
        return table

    def run(self, table: Table, *, mini_batch_size: int, prefetch_depth: int,
            shape_buckets: bool, tracer: Any, fused_label: str = "pipeline",
            readback_lag: int = 1,
            pipeline_depth: "int | None" = None) -> tuple[Table, dict]:
        n = table.num_rows
        jitted, params = self._build()
        bs = max(int(mini_batch_size), 1)
        mesh = self.mesh
        if mesh is None:
            mesh_label = "1"
            in_shardings = None
            d = 1
        else:
            from ..parallel.mesh import (DATA_AXIS, data_sharding,
                                         mesh_shape_label)

            mesh_label = mesh_shape_label(mesh)
            d = int(mesh.shape[DATA_AXIS])
            # every shard gets equal rows: chunk size (and therefore every
            # full chunk) must divide evenly over the data axis
            bs = -(-bs // d) * d
        # The ladder must depend only on mini_batch_size, never on the row
        # count of THIS table: an n-derived max would mint n-specific bucket
        # shapes for small tables and recompile in steady state.  Under a
        # mesh the ladder is SKEW-AWARE (`shards=d`): the geometric rungs
        # are built in per-shard rows and scaled up, so every rung splits
        # into d equal slices — no shard ever carries more rows than
        # another, by construction rather than by divisibility luck.
        bucketer = ShapeBucketer(bs, shards=d) if shape_buckets else None
        ins = {c: np.asarray(table[c]) for c in self.upload_cols}
        if mesh is not None:
            in_shardings = {
                c: data_sharding(mesh, *([None] * (ins[c].ndim - 1)))
                for c in self.upload_cols}
        family = self._family_key(ins)
        stats = {
            "kind": "fused", "segment": self.index,
            "stages": list(self.stage_names), "rows": n,
            "mesh_shape": mesh_label,
            "uploads": 0, "downloads": 0,
            "prepare_seconds": 0.0, "fetch_seconds": 0.0,
            "pad_seconds": 0.0, "h2d_seconds": 0.0,
            "dispatch_seconds": 0.0, "wait_seconds": 0.0,
            "rows_real": 0, "rows_padded": 0,
            "ready_on_fetch": 0, "fetched": 0,
        }
        if mesh is not None:
            stats["param_placements"] = list(self._param_placements)
        shard_seconds: dict[str, float] = {}
        shard_rows: dict[str, int] = {}

        def prepare(start: int):
            stop = min(start + bs, n)
            m = stop - start
            target = bucketer.bucket_for(m) if bucketer is not None else bs
            cols = {}
            t_pad = 0.0
            for c in self.upload_cols:
                chunk = ins[c][start:stop]
                if target > m:
                    t0 = time.perf_counter()
                    chunk = np.concatenate(
                        [chunk, np.repeat(chunk[-1:], target - m, axis=0)])
                    t_pad += time.perf_counter() - t0
                cols[c] = chunk
            stats["pad_seconds"] += t_pad
            stats["rows_real"] += m
            stats["rows_padded"] += target - m
            if bucketer is not None and target > m:
                bucketer.note_pad(m, target)
            # one upload per input column; under a mesh the chunk commits
            # row-sharded, so the transfer lands per-shard on each chip
            t0 = time.perf_counter()
            dt = DeviceTable.from_host(cols, shardings=in_shardings)
            stats["h2d_seconds"] += time.perf_counter() - t0
            stats["uploads"] += len(self.upload_cols)
            return dt, m, target

        def fetch(item):
            outs, m = item
            # dispatch-overlap gauge: a batch whose device results are
            # already complete when the host comes to fetch it had its
            # compute fully hidden behind pipeline work
            stats["fetched"] += 1
            if _is_ready(outs):
                stats["ready_on_fetch"] += 1
            # Block on the WHOLE output before the per-shard copy loop:
            # otherwise the first shard's copy silently absorbs the wait
            # for the still-running async dispatch and reads as a "slow
            # shard" (the r07 ladder's 4.67x skew was exactly this
            # artifact).  The wait is device compute (wait_seconds); the
            # copies below measure readback bandwidth only.
            t0 = time.perf_counter()
            _block_ready(outs)
            stats["wait_seconds"] += time.perf_counter() - t0
            t0 = time.perf_counter()
            if mesh is None:
                host = tuple(np.asarray(o)[:m] for o in outs)
            else:
                # per-shard read-back: fetch each chip's shard separately,
                # timing the copies — the spread between the slowest and
                # fastest chip is the shard-skew gauge
                host = tuple(
                    _fetch_sharded(o, m, shard_seconds, shard_rows)
                    for o in outs)
            stats["fetch_seconds"] += time.perf_counter() - t0
            stats["downloads"] += len(host)
            return host

        prefetch = Prefetcher(range(0, n, bs), prepare,
                              depth=max(int(prefetch_depth), 0),
                              name=f"fused-seg{self.index}")
        # `pipeline_depth` is the bounded dispatch->dispatch window: at
        # most K+1 batches dispatched-but-unfetched, with lag-K readback —
        # h2d/prepare of chunk N+1 and the fetch of chunk N-K both overlap
        # device compute of chunks N-K+1..N (async dispatch).  None falls
        # back to the pre-pipelining readback_lag knob.
        lag = (max(int(readback_lag), 0) if pipeline_depth is None
               else max(int(pipeline_depth), 0))
        readback = AsyncReadback(fetch, lag=lag)
        chunks: list[tuple[np.ndarray, ...]] = []
        t_run0 = time.perf_counter()
        with tracer.start_span("pipeline.fused_segment", segment=self.index,
                               stages=",".join(self.stage_names), rows=n,
                               mesh_shape=mesh_label) as span:
            ledger = _ledger("fused", f"seg{self.index}", span=span,
                             mesh_shape=mesh_label)
            for dt, m, target in prefetch:
                shape_key = (target, tuple(
                    (str(dt[c].dtype), tuple(dt[c].shape[1:]))
                    for c in self.upload_cols))
                # jax.jit does the real per-shape caching; the
                # ExecutableCache entry makes hits/misses/RECOMPILES
                # observable (steady-state recompiles == 0 is the bar)
                fn = self._exec_cache.get_or_build(
                    family, shape_key, lambda: jitted)
                args = tuple(dt[c] for c in self.upload_cols)
                ledger.cost((family, shape_key), fn, params, args)
                t0 = time.perf_counter()
                outs = fn(params, args)
                stats["dispatch_seconds"] += time.perf_counter() - t0
                if ledger.armed:
                    # attribution mode trades the dispatch->dispatch
                    # overlap for a visible compute phase: the bracket
                    # serializes on THIS batch's device results
                    with ledger.phase("compute"):
                        _block_ready(outs)
                chunks.extend(readback.push((outs, m)))
            chunks.extend(readback.drain())
        stats["prepare_seconds"] = prefetch.stats["prepare_seconds"]
        stats["overlap_fraction"] = prefetch.overlap_fraction()
        stats["pipeline_depth"] = lag
        stats["dispatch_overlap_fraction"] = (
            stats["ready_on_fetch"] / stats["fetched"]
            if stats["fetched"] else 0.0)
        stats.update(self._exec_cache.stats())
        if shard_seconds:
            per_shard = sorted(shard_seconds.values())
            if per_shard[0] >= 1e-3:
                skew = per_shard[-1] / per_shard[0]
            elif shard_rows:
                # copy totals under ~1ms/shard are perf_counter noise,
                # not chip imbalance (host-platform devices read back
                # zero-copy, so max/min of microsecond timings explodes
                # with device count).  Below the timing floor the gauge
                # falls back to per-shard ROW skew — the quantity the
                # skew-aware bucketer actually controls, and exact at
                # any scale.
                rows = sorted(shard_rows.values())
                skew = rows[-1] / max(rows[0], 1)
            else:
                skew = per_shard[-1] / max(per_shard[0], 1e-9)
            stats["shard_skew_ratio"] = skew
            _set_shard_skew_gauge(fused_label, mesh_label, skew)
        if ledger.armed:
            host_prep = max(stats["prepare_seconds"] - stats["h2d_seconds"]
                            - stats["pad_seconds"], 0.0)
            ledger.add("prepare", host_prep)
            ledger.add("pad", stats["pad_seconds"])
            ledger.add("h2d", stats["h2d_seconds"])
            ledger.add("dispatch", stats["dispatch_seconds"])
            # device wait at fetch time is compute the pipeline failed to
            # hide; the d2h phase is now pure readback copy bandwidth
            ledger.add("compute", stats["wait_seconds"])
            ledger.add("d2h", stats["fetch_seconds"])
            ledger.set(dispatch_overlap_fraction=round(
                stats["dispatch_overlap_fraction"], 4))
            ledger.note_pad(stats["rows_real"],
                            stats["rows_real"] + stats["rows_padded"])
            for dev, sec in shard_seconds.items():
                ledger.note_shard(dev, sec, rows=shard_rows.get(dev))
            ledger.done(rtt_s=time.perf_counter() - t_run0)

        out = table
        for j, c in enumerate(self.download_cols):
            arr = (np.concatenate([ch[j] for ch in chunks])
                   if len(chunks) > 1 else chunks[0][j])
            kern = self._last_producer[c]
            want = kern.out_dtypes.get(c)
            if want is not None and arr.dtype != np.dtype(want):
                arr = arr.astype(want)
            meta = kern.out_meta.get(c)
            if callable(meta):
                meta = meta(arr)
            out = out.with_column(c, arr, meta=meta)
        return out, stats


def _fetch_sharded(arr: Any, m: int, shard_seconds: dict,
                   shard_rows: "dict | None" = None) -> np.ndarray:
    """Read a device array back shard by shard, accumulating per-device
    copy seconds into `shard_seconds` (feeds the shard-skew gauge) and,
    when `shard_rows` is given, per-device row counts (the profiler's
    shard-attribution table pairs slow shards with how many rows they
    held).  Whole-array copy for replicated/single-shard outputs (one
    transfer suffices and there is no per-chip spread to measure).
    Callers must `_block_ready` the array FIRST: on a still-in-flight
    result the first shard's copy would absorb the whole device-compute
    wait and masquerade as shard skew."""
    sharding = getattr(arr, "sharding", None)
    if sharding is not None and getattr(sharding, "is_fully_replicated", False):
        return np.asarray(arr)[:m]
    shards = list(getattr(arr, "addressable_shards", None) or [])
    if len(shards) <= 1:
        return np.asarray(arr)[:m]
    out = np.empty(arr.shape, np.dtype(arr.dtype))
    for sh in shards:
        t0 = time.perf_counter()
        piece = np.asarray(sh.data)
        key = str(sh.device)
        shard_seconds[key] = (shard_seconds.get(key, 0.0)
                              + time.perf_counter() - t0)
        if shard_rows is not None:
            shard_rows[key] = shard_rows.get(key, 0) + int(piece.shape[0])
        out[sh.index] = piece
    return out[:m]


# --------------------------------------------------------------------- #
# ResidentExecutor — the persistent serving session over one segment     #
# --------------------------------------------------------------------- #


class ResidentExecutor:
    """A long-lived serving session over a single fused segment.

    `_FusedSegment.run` is built for batch work: every call re-creates a
    Prefetcher, an AsyncReadback, a span, and the family key, and blocks
    until all chunks are back on host.  A serving hot path needs the
    opposite shape: params pinned on device ONCE at startup, then per
    request batch exactly one upload (`dispatch`) and one deferred
    read-back (`fetch`), so the caller can overlap reply serialization of
    batch N with device compute on batch N+1 (io_http/serving.py drives
    this through `AsyncReadback`).  Split dispatch/fetch is what makes
    that overlap possible — `run()` could never hand back an in-flight
    batch.

    Values are byte-identical to the staged path: the same jitted
    composition, the same pinned params, the same `out_dtypes` cast on
    read-back.  `round_trips` counts upload+readback pairs — one per
    dispatched batch, so a batched request costs at most one host
    round-trip (the ROADMAP serving bar)."""

    def __init__(self, segment: "_FusedSegment"):
        self.segment = segment
        self.upload_cols = segment.upload_cols
        self.download_cols = segment.download_cols
        self._jitted, self._params = segment._build()
        self._in_shardings: "dict | None" = None
        if segment.mesh is not None:
            # placements are fixed per ndim; resolved lazily at first
            # dispatch (the feature rank is unknown until then)
            self._in_shardings = {}
        self._family_cache: dict[tuple, Any] = {}
        self.dispatches = 0
        self.round_trips = 0
        # dispatch-overlap accounting: fetches whose device results were
        # already complete at fetch entry (compute hidden behind the
        # serving loop's reply serialization / next-batch assembly)
        self.fetches = 0
        self.ready_on_fetch = 0

    @property
    def data_axis_size(self) -> int:
        """Every dispatched batch's row count must divide by this (the
        mesh data-axis size; 1 single-device). Serving builds its bucket
        ladder with `multiple_of=` this so padded rungs stay shardable."""
        if self.segment.mesh is None:
            return 1
        from ..parallel.mesh import DATA_AXIS

        return int(self.segment.mesh.shape[DATA_AXIS])

    # -- host-side preconditions ---------------------------------------- #

    def check_ready(self, table: Table) -> str:
        """'' when this table can run resident, else the blocking reason
        (same contract as `_FusedSegment.check_ready`)."""
        return self.segment.check_ready(table)

    def check_ready_values(self, cols: dict) -> str:
        """Per-batch VALUE re-check over `{col: ndarray}` host inputs —
        the cheap complement of `check_ready` once schema validation has
        run (serving warmup does it exactly once).  Same ''-or-reason
        contract; see `_FusedSegment.check_ready_values`."""
        return self.segment.check_ready_values(cols)

    # -- per-batch execution -------------------------------------------- #

    def _signature(self, ins: dict) -> tuple:
        return tuple((c, str(ins[c].dtype), ins[c].shape[1:])
                     for c in self.upload_cols)

    def _family_for(self, ins: dict) -> Any:
        sig = self._signature(ins)
        fam = self._family_cache.get(sig)
        if fam is None:
            fam = self.segment._family_key(ins)
            self._family_cache[sig] = fam
        return fam

    def _shardings_for(self, ins: dict) -> "dict | None":
        if self.segment.mesh is None:
            return None
        from ..parallel.mesh import data_sharding

        out = {}
        for c in self.upload_cols:
            nd = ins[c].ndim
            s = self._in_shardings.get((c, nd))
            if s is None:
                s = data_sharding(self.segment.mesh, *([None] * (nd - 1)))
                self._in_shardings[(c, nd)] = s
            out[c] = s
        return out

    def dispatch(self, cols: dict, ledger: Any = None) -> tuple:
        """Upload one padded batch and launch the resident executable.
        Returns the still-in-flight device outputs (async dispatch): the
        caller is free to assemble the next batch before `fetch`ing.
        An armed profiler ledger brackets the h2d upload and the XLA
        dispatch call (serving threads one through per scored batch)."""
        if ledger is None:
            ledger = _LEDGER_FALLBACK
        with ledger.phase("prepare"):
            ins = {c: np.asarray(cols[c]) for c in self.upload_cols}
            rows = next(iter(ins.values())).shape[0] if ins else 0
            family = self._family_for(ins)
            shape_key = (rows, self._signature(ins))
            fn = self.segment._exec_cache.get_or_build(
                family, shape_key, lambda: self._jitted)
        with ledger.phase("h2d"):
            dt = DeviceTable.from_host(ins, shardings=self._shardings_for(ins))
        args = tuple(dt[c] for c in self.upload_cols)
        ledger.cost((id(self), family, shape_key), fn, self._params, args)
        with ledger.phase("dispatch"):
            outs = fn(self._params, args)
        self.dispatches += 1
        self.round_trips += 1
        return outs

    def fetch(self, outs: tuple, n_valid: int, ledger: Any = None) -> dict:
        """Block on the device results, slice padding off, and apply the
        staged path's host dtype casts — the columns a `transform` of the
        same batch would have produced, bit for bit.  When the ledger is
        armed, the device wait is bracketed separately (`compute`) from
        the host copy/cast (`d2h`) so the attribution table can split
        time-on-device from readback bandwidth."""
        if ledger is None:
            ledger = _LEDGER_FALLBACK
        self.fetches += 1
        if _is_ready(outs):
            self.ready_on_fetch += 1
        if ledger.armed:
            with ledger.phase("compute"):
                _block_ready(outs)
        result: dict[str, np.ndarray] = {}
        with ledger.phase("d2h"):
            for j, c in enumerate(self.download_cols):
                arr = np.asarray(outs[j])[:n_valid]
                kern = self.segment._last_producer[c]
                want = kern.out_dtypes.get(c)
                if want is not None and arr.dtype != np.dtype(want):
                    arr = arr.astype(want)
                result[c] = arr
        return result

    # -- warmup / AOT ---------------------------------------------------- #

    def warm(self, cols: dict, ladder: Sequence[int],
             prefetch_depth: int = 1, readback_lag: int = 1) -> int:
        """Compile and execute the resident program once per ladder rung so
        live traffic never pays a compile.  `cols` is a sample batch (>= 1
        row) of the upload columns; each rung's input is built by repeating
        its last row — the same padding live batches use, so the compiled
        shape set exactly covers what serving can mint.  Rung assembly
        overlaps the previous rung's device execution (`Prefetcher`) and
        read-backs trail by `readback_lag` (`AsyncReadback`), mirroring the
        hot loop's steady-state schedule.  Returns rungs executed."""
        ins = {c: np.asarray(cols[c]) for c in self.upload_cols}

        def prepare(rung: int):
            padded = {}
            for c, arr in ins.items():
                if rung > len(arr):
                    arr = np.concatenate(
                        [arr, np.repeat(arr[-1:], rung - len(arr), axis=0)])
                padded[c] = arr[:rung]
            return rung, padded

        prefetch = Prefetcher(list(ladder), prepare,
                              depth=max(int(prefetch_depth), 0),
                              name="resident-warm")
        readback = AsyncReadback(lambda item: self.fetch(item[0], item[1]),
                                 lag=max(int(readback_lag), 0))
        n = 0
        for rung, padded in prefetch:
            outs = self.dispatch(padded)
            readback.push((outs, rung))
            n += 1
        readback.drain()
        return n

    def aot_args(self, cols: dict, n_rows: int) -> tuple:
        """(fn, args) for `tools/aot_gate.py`: the resident executable plus
        abstract inputs at a ladder rung of `n_rows` (params stay concrete
        — they are already pinned on device).  `cols` is a >=1-row host
        sample fixing feature rank and dtype; dtypes canonicalize exactly
        as `DeviceTable.from_host` would (float64 -> float32 under jax's
        x64 default), so the lowered program is the one serving runs."""
        import jax
        import jax.numpy as jnp

        ins = {c: np.asarray(cols[c]) for c in self.upload_cols}
        abstract = tuple(
            jax.ShapeDtypeStruct((n_rows,) + ins[c].shape[1:],
                                 jnp.asarray(ins[c][:1]).dtype)
            for c in self.upload_cols)
        if self.segment.mesh is None:
            return self._jitted, (self._params, abstract)
        from ..parallel.mesh import data_sharding, replicated_sharding

        mesh = self.segment.mesh
        # replicated prefix for the params tree matches the default (and
        # the GBDT mesh_fn's explicit) placement; rows shard over data.
        # Donation must match the live executable: an aliased program is a
        # DIFFERENT program, so gating the non-donated lowering would
        # validate something serving never runs.
        donate = (1,) if self.segment.donate else ()
        jfn = jax.jit(self.segment._composed, donate_argnums=donate,
                      in_shardings=(
                          replicated_sharding(mesh),
                          tuple(data_sharding(mesh, *([None] * (ins[c].ndim - 1)))
                                for c in self.upload_cols)))
        return jfn, (self._params, abstract)

    def stats(self) -> dict:
        """Executable-cache counters + session round-trip accounting +
        the donation/pipelining gauges serving's info() republishes."""
        out = self.segment._exec_cache.stats()
        out.update(dispatches=self.dispatches, round_trips=self.round_trips,
                   fetches=self.fetches, ready_on_fetch=self.ready_on_fetch,
                   dispatch_overlap_fraction=(
                       self.ready_on_fetch / self.fetches
                       if self.fetches else 0.0),
                   donate_buffers=self.segment.donate)
        return out


# --------------------------------------------------------------------- #
# FusedPipelineModel                                                    #
# --------------------------------------------------------------------- #


@register_stage
class FusedPipelineModel(PipelineModel):
    """A PipelineModel whose device-capable stage runs execute as single
    fused XLA programs.  Behaves exactly like the staged model (same
    columns, dtypes, metadata, values); non-fusable stages run on the host
    path unchanged.  Build with `fuse(model)`."""

    mini_batch_size = Param(
        4096, "rows per fused device dispatch (large tables stream through "
              "the segment in chunks of this size)", ptype=int)
    prefetch_depth = Param(
        2, "chunks prepared/uploaded ahead of device compute (0 = "
           "sequential)", ptype=int)
    shape_buckets = Param(
        True, "pad ragged chunk tails to a pow-2 bucket ladder so the "
              "compiled-shape set stays closed", ptype=bool)
    fused_label = Param(
        "pipeline", "label for the fusion-ratio gauge", ptype=str)
    readback_lag = Param(
        1, "device batches kept in flight before device->host readback is "
           "forced (0 = fetch synchronously after every dispatch); also the "
           "lag of the serving hot path's overlapped reply fetch", ptype=int)
    donate_buffers = Param(
        True, "donate each chunk's device input buffers to the fused "
              "executable (jit donate_argnums on the batch tuple; params "
              "are never donated) so steady-state batches reuse device "
              "memory instead of allocating fresh — identical values, "
              "fewer allocations", ptype=bool)
    pipeline_depth = Param(
        None, "sharded dispatches kept in flight per segment (the bounded "
              "dispatch->dispatch pipeline window: at most this+1 batches "
              "dispatched-but-unfetched, lag-K readback; 0 = fetch "
              "synchronously after every dispatch). None inherits "
              "readback_lag, keeping the pre-pipelining schedule",
        ptype=int)
    use_mesh = Param(
        False, "compile fused segments under the process mesh "
               "(parallel.mesh.get_mesh()) when no explicit mesh was set "
               "via fuse(model, mesh=...) / set_mesh()", ptype=bool)

    #: stats from the most recent transform: per-segment timings, transfer
    #: counts, executable-cache counters, fusion ratio
    last_stats: "dict | None" = None
    #: explicit mesh (runtime handle, not serialized state — like a model
    #: bundle, it is re-attached after load via set_mesh)
    mesh: Any = None
    _segments: "list | None" = None
    _segments_key: "tuple | None" = None
    _plan: "FusionPlan | None" = None
    _mesh: Any = None  # the normalized mesh the current segments compile on

    def plan(self) -> FusionPlan:
        self._ensure_segments()
        return self._plan

    def resident_executor(self) -> "ResidentExecutor | str":
        """A persistent serving session over this model, or the reason one
        cannot exist.  Requires the whole plan to be ONE fused segment —
        any host stage in the chain forces a host materialization between
        device programs, so there is no single resident executable to pin
        (`serve_model` falls back to the per-request handler path then)."""
        segments = self._ensure_segments()
        if len(segments) != 1 or not isinstance(segments[0], _FusedSegment):
            fused = sum(1 for s in segments if isinstance(s, _FusedSegment))
            host = "; ".join(
                f"{type(sp.stage).__name__}: {sp.reason}"
                for s in segments if not isinstance(s, _FusedSegment)
                for sp in s.stages)
            return (f"plan is {len(segments)} segments ({fused} fused) — a "
                    "resident session needs exactly one fused segment"
                    + (f" (on the host: {host})" if host else ""))
        return ResidentExecutor(segments[0])

    def set_mesh(self, mesh: Any) -> "FusedPipelineModel":
        """Attach (or with None, detach) the mesh fused segments compile
        under; segments rebuild on next use.  Returns self."""
        self.mesh = mesh
        self._segments = None
        return self

    def _effective_mesh(self) -> Any:
        """The mesh segments actually compile on: the explicit one, else
        `get_mesh()` when `use_mesh` is set — normalized to None whenever
        it spans a single device, so a trivial mesh IS the single-chip
        path (same executables, same cache keys)."""
        mesh = self.mesh
        if mesh is None and self.get("use_mesh"):
            from ..parallel.mesh import get_mesh

            mesh = get_mesh()
        if mesh is None:
            return None
        from ..parallel.mesh import mesh_device_count

        return mesh if mesh_device_count(mesh) > 1 else None

    def _ensure_segments(self):
        stages = list(self.get("stages") or [])
        mesh = self._effective_mesh()
        donate = bool(self.get("donate_buffers"))
        key = (tuple(id(s) for s in stages), mesh, donate)
        if self._segments is None or self._segments_key != key:
            self._plan = plan_fusion(stages)
            segs = []
            for i, sp in enumerate(self._plan.segments):
                segs.append(_FusedSegment(i, sp.stages, mesh=mesh,
                                          donate=donate)
                            if sp.fused else sp)
            self._segments = segs
            self._segments_key = key
            self._mesh = mesh
        return self._segments

    def _transform(self, table: Table) -> Table:
        segments = self._ensure_segments()
        tracer = _get_tracer()
        mesh_label = "1"
        if self._mesh is not None:
            from ..parallel.mesh import mesh_shape_label

            mesh_label = mesh_shape_label(self._mesh)
        stats: dict[str, Any] = {
            "segments": [], "uploads": 0, "downloads": 0,
            "fusion_ratio": self._plan.fusion_ratio,
            "n_stages": self._plan.n_stages,
            "n_fused_stages": self._plan.n_fused_stages,
            "mesh_shape": mesh_label,
        }
        current = table
        for seg in segments:
            t0 = time.perf_counter()
            if isinstance(seg, _FusedSegment):
                why_not = seg.check_ready(current)
                if why_not:
                    current = seg.run_host(current)
                    seg_stats = {
                        "kind": "host_fallback", "segment": seg.index,
                        "stages": list(seg.stage_names), "reason": why_not,
                        "mesh_shape": "1",  # ran staged on the host path
                    }
                else:
                    current, seg_stats = seg.run(
                        current,
                        mini_batch_size=self.get("mini_batch_size"),
                        prefetch_depth=self.get("prefetch_depth"),
                        shape_buckets=self.get("shape_buckets"),
                        tracer=tracer,
                        fused_label=self.get("fused_label"),
                        readback_lag=self.get("readback_lag"),
                        pipeline_depth=self.get("pipeline_depth"))
                    stats["uploads"] += seg_stats["uploads"]
                    stats["downloads"] += seg_stats["downloads"]
            else:
                for sp in seg.stages:
                    current = sp.stage.transform(current)
                seg_stats = {
                    "kind": "host",
                    "stages": [type(sp.stage).__name__ for sp in seg.stages],
                    "mesh_shape": "1",
                }
            seg_stats["seconds"] = time.perf_counter() - t0
            stats["segments"].append(seg_stats)
        self.last_stats = stats
        _set_fusion_gauge(self.get("fused_label"), stats["fusion_ratio"],
                          mesh_label)
        return current

    def _load_state(self, state: dict[str, Any]) -> None:
        super()._load_state(state)
        self._segments = None  # rebuild against the loaded stages


def fuse(model: Any, mesh: Any = None, **params: Any) -> FusedPipelineModel:
    """Compile a PipelineModel (or any Transformer) for whole-pipeline
    fusion.  Idempotent; non-fusable stages keep their staged path, so
    `fuse` never changes results — only where the work runs.  With `mesh`,
    fused segments compile sharded over that mesh (still byte-identical;
    a 1-device mesh is the plain single-chip path)."""
    if isinstance(model, FusedPipelineModel):
        return model.set_mesh(mesh) if mesh is not None else model
    if isinstance(model, PipelineModel):
        stages = list(model.get("stages") or [])
    elif isinstance(model, Transformer):
        stages = [model]
    else:
        raise TypeError(f"fuse() needs a Transformer, got {type(model).__name__}")
    fm = FusedPipelineModel(stages, **params)
    if mesh is not None:
        fm.set_mesh(mesh)
    return fm


# --------------------------------------------------------------------- #
# observability shims (lazy: observability imports core.pipeline)       #
# --------------------------------------------------------------------- #


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def set(self, **kw):
        pass


class _NullTracer:
    def start_span(self, *a, **kw):
        return _NullSpan()


def _get_tracer():
    try:
        from ..observability.tracing import get_tracer

        return get_tracer()
    except Exception:
        return _NullTracer()


class _NullLedgerFallback:
    """Stand-in when observability.profiler is unavailable (mirrors
    _NullTracer: fusion must run without the observability package)."""

    armed = False

    def phase(self, name):
        return _NullSpan()

    def add(self, name, seconds):
        pass

    def note_pad(self, rows_real, rows_target):
        pass

    def note_shard(self, shard, seconds, rows=None):
        pass

    def cost(self, key, fn, *args, **kwargs):
        return None

    def set(self, **meta):
        pass

    def done(self, rtt_s=None):
        pass


_LEDGER_FALLBACK = _NullLedgerFallback()


def _ledger(kind: str, segment: str, span: Any = None, **meta: Any):
    """A phase ledger from the process-default profiler (the shared
    no-op when it is disarmed), or the local fallback when the
    observability package cannot load."""
    try:
        from ..observability.profiler import get_profiler

        return get_profiler().ledger(kind, segment, span=span, **meta)
    except Exception:
        return _LEDGER_FALLBACK


def _block_ready(outs: Any) -> None:
    """block_until_ready for the profiler's compute bracket; fail-soft
    (host-only test doubles have nothing to block on)."""
    try:
        import jax

        jax.block_until_ready(outs)
    except Exception:
        pass


def _is_ready(outs: Any) -> bool:
    """Non-blocking: True when every device result in `outs` had already
    completed at the moment the host asked — the numerator of the
    dispatch-overlap gauge (compute fully hidden behind pipeline work).
    Host-only doubles count as ready: there is nothing to wait on."""
    try:
        import jax

        return all(bool(leaf.is_ready()) for leaf in jax.tree.leaves(outs)
                   if hasattr(leaf, "is_ready"))
    except Exception:
        return True


def _set_fusion_gauge(label: str, ratio: float, mesh_shape: str = "1") -> None:
    try:
        from ..observability.metrics import get_registry

        get_registry().gauge(
            "mmlspark_tpu_pipeline_fusion_ratio",
            "fraction of pipeline stages executing inside fused segments",
            labels=("pipeline", "mesh_shape")).labels(
                pipeline=label, mesh_shape=mesh_shape).set(ratio)
    except Exception:
        pass


def _set_shard_skew_gauge(label: str, mesh_shape: str, ratio: float) -> None:
    try:
        from ..observability.metrics import get_registry

        get_registry().gauge(
            "mmlspark_tpu_shard_skew_ratio",
            "slowest/fastest per-shard wall time within a fused sharded "
            "segment (1.0 = perfectly balanced chips)",
            labels=("pipeline", "mesh_shape")).labels(
                pipeline=label, mesh_shape=mesh_shape).set(ratio)
    except Exception:
        pass
