"""JAX's trace, lowering and compile events as spans of the process-default
tracer (`observability/tracing.py`: `install_jax_bridge`), completed spans
(`Tracer.record_span`), the imports as spans, and the runner's compile
seconds read from the bridge. Every tracer here is on the wall clock: a
tracer on an injected clock takes no bridged span."""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mmlspark_tpu
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn.models import ModelBundle
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability import Tracer, set_default_tracer
from mmlspark_tpu.observability import tracing

JAX_NAMES = ("jax.trace", "jax.lower", "jax.compile")


@pytest.fixture
def tracer():
    tr = Tracer()
    old = set_default_tracer(tr)
    yield tr
    set_default_tracer(old)


def named(tracer, name):
    return [s for s in tracer.spans() if s.name == name]


def union_seconds(spans) -> float:
    total, end = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s.start_us):
        stop = s.start_us + s.dur_us
        total += max(0.0, stop - max(end, s.start_us))
        end = max(end, stop)
    return total * 1e-6


# -- record_span ---------------------------------------------------------- #

def test_record_span_follows_the_parent_rule():
    tr = Tracer(id_seed=1)
    alone = tr.record_span("done", 10.0, 5.0, size=3)
    assert (alone.parent_id, alone.start_us, alone.dur_us) == (0, 10.0, 5.0)
    assert alone.args == {"size": 3}
    with tr.start_span("active") as active:
        under = tr.record_span("done", 20.0, 1.0)
        given = tr.record_span("done", 21.0, 1.0, parent=alone)
    assert under.parent is active and under.trace_id == active.trace_id
    assert given.parent is alone and given.trace_id == alone.trace_id
    assert len({s.span_id for s in tr.spans()}) == 4
    # it was never active: the thread's active span is what it was
    assert tr.current_span() is None
    # and it exports like any other
    (event,) = [e for e in tr.chrome_events() if e["args"].get("size")]
    assert (event["ts"], event["dur"], event["ph"]) == (10.0, 5.0, "X")


def test_record_span_on_a_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    assert tr.record_span("done", 0.0, 1.0) is None
    assert tr.spans() == []


def test_record_span_shares_the_ring_and_its_drop_count():
    tr = Tracer(max_spans=3)
    for i in range(5):
        tr.record_span("done", float(i), 1.0)
    assert [s.start_us for s in tr.spans()] == [2.0, 3.0, 4.0]
    assert tr.drop_count == 2


# -- the bridge ----------------------------------------------------------- #

def test_a_fresh_jit_leaves_three_children_and_a_second_call_none(tracer):
    @jax.jit
    def fresh_square(x):
        return x * x + 1.0

    x = jnp.arange(6.0)
    with tracer.start_span("first") as first:
        fresh_square(x).block_until_ready()
    with tracer.start_span("second") as second:
        fresh_square(x).block_until_ready()
    ours = {name: [s for s in named(tracer, name)
                   if "fresh_square" in s.args["fun_name"]]
            for name in JAX_NAMES}
    assert [len(ours[name]) for name in JAX_NAMES] == [1, 1, 1]
    (trace,), (lower,), (compile_,) = (ours[name] for name in JAX_NAMES)
    assert trace.args == {"fun_name": "fresh_square"}
    assert lower.args == {"fun_name": "jit(fresh_square)"}
    # the test session runs without the persistent cache
    assert compile_.args == {"fun_name": "jit(fresh_square)",
                             "cache_hit": False, "retrieval_s": 0.0}
    for s in (trace, lower, compile_):
        assert s.parent is first and s.trace_id == first.trace_id
        assert s.dur_us > 0
        # ended inside the span that paid for it
        assert first.start_us <= s.start_us + s.dur_us <= (
            first.start_us + first.dur_us)
    assert trace.start_us <= lower.start_us <= compile_.start_us
    assert not [s for s in tracer.spans() if s.parent is second]


def test_a_nested_jit_leaves_its_own_trace_and_the_union_counts_once(tracer):
    @jax.jit
    def inner_cube(x):
        return x * x * x

    @jax.jit
    def outer_sum(x):
        return inner_cube(x).sum() + inner_cube(x + 1.0).sum()

    before = tracing.jax_compile_seconds()
    with tracer.start_span("call") as call:
        outer_sum(jnp.arange(5.0)).block_until_ready()
    paid = tracing.jax_compile_seconds() - before
    traces = {s.args["fun_name"]: s for s in named(tracer, "jax.trace")}
    # ONE span for the two calls of `inner_cube`: the second found its
    # jaxpr in the cache and traced nothing
    assert collections.Counter(
        s.args["fun_name"] for s in named(tracer, "jax.trace")
        if s.args["fun_name"] in ("inner_cube", "outer_sum")) == {
            "inner_cube": 1, "outer_sum": 1}
    inner, outer = traces["inner_cube"], traces["outer_sum"]
    assert inner.parent is call and outer.parent is call
    # the inner interval lies inside the outer one (a millisecond's room:
    # JAX times with time.time(), the tracer ends the span on its own
    # clock)
    assert outer.start_us - 1e3 <= inner.start_us
    assert inner.start_us + inner.dur_us <= outer.start_us + outer.dur_us + 1e3
    under = [s for s in tracer.spans() if s.parent is call]
    union = union_seconds(under)
    assert union < sum(s.dur_us for s in under) * 1e-6
    # the thread's running total is that union, not the sum
    assert paid == pytest.approx(union, abs=2e-3)
    assert call.dur_us * 1e-6 >= union - 2e-3


def test_a_cache_hit_is_marked(tracer, tmp_path):
    def program():
        # a new function object a call, the same program: the second
        # compile request finds the first one's executable in the cache
        return jax.jit(lambda x: jnp.tanh(x @ x.T).sum(axis=0) * 3.25)

    saved = {name: getattr(jax.config, name) for name in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    from jax._src import compilation_cache

    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        x = jnp.ones((7, 3))
        program()(x).block_until_ready()
        program()(x).block_until_ready()
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    miss, hit = [s for s in named(tracer, "jax.compile")
                 if "lambda" in s.args["fun_name"]]
    assert miss.args["cache_hit"] is False and miss.args["retrieval_s"] == 0.0
    assert hit.args["cache_hit"] is True
    assert 0.0 < hit.args["retrieval_s"] <= hit.dur_us * 1e-6 + 1e-3
    assert list(tmp_path.iterdir())


def test_the_listeners_are_registered_once():
    from jax._src import monitoring

    def ours():
        return (monitoring._scalar_listeners.count(tracing._on_jax_start),
                monitoring.get_event_duration_listeners().count(
                    tracing._on_jax_duration))

    assert ours() == (1, 1)             # `import mmlspark_tpu` installed it
    assert all(tracing.install_jax_bridge() for _ in range(3))
    assert ours() == (1, 1)


def test_a_disabled_tracer_records_nothing_and_the_total_still_runs():
    off = Tracer(enabled=False)
    old = set_default_tracer(off)
    try:
        before = tracing.jax_compile_seconds()
        jax.jit(lambda x: x - 7.5)(jnp.arange(3.0)).block_until_ready()
        assert off.spans() == []
        assert tracing.jax_compile_seconds() > before
    finally:
        set_default_tracer(old)


def test_a_tracer_on_an_injected_clock_takes_no_bridged_span():
    class Clock:
        def __init__(self):
            self.readings = 0

        def monotonic(self):
            self.readings += 1
            return float(self.readings)

    clock = Clock()
    fake = Tracer(clock=clock)
    old = set_default_tracer(fake)
    try:
        jax.jit(lambda x: x / 3.5)(jnp.arange(3.0)).block_until_ready()
        tracing.record_import("some.module", time.monotonic())
    finally:
        set_default_tracer(old)
    assert fake.spans() == [] and clock.readings == 0


def test_another_thread_s_compile_hangs_under_its_own_span(tracer):
    import threading

    def work():
        with tracer.start_span("worker.job"):
            jax.jit(lambda x: x * 11.5)(jnp.arange(4.0)).block_until_ready()

    before = tracing.jax_compile_seconds()
    with tracer.start_span("main.job"):
        thread = threading.Thread(target=work)
        thread.start()
        thread.join()
    (compile_,) = [s for s in named(tracer, "jax.compile")
                   if "lambda" in s.args["fun_name"]]
    assert compile_.parent.name == "worker.job"
    assert compile_.tid == compile_.parent.tid
    # this thread paid nothing
    assert tracing.jax_compile_seconds() == before


# -- the imports ---------------------------------------------------------- #

def test_the_imports_are_spans():
    """A fresh interpreter: the package, and the one sub-package whose
    import costs over 0.2 s on the chip's host (`nn`: flax and optax),
    each leave one span; `recommendation` (0.14 s there) and `gbdt` (0.05)
    carry no stamp."""
    import json
    import subprocess
    import sys

    from conftest import subprocess_env

    code = (
        "import json, mmlspark_tpu\n"
        "from mmlspark_tpu.observability import get_tracer\n"
        "early = len(get_tracer().spans())\n"
        "import mmlspark_tpu.nn, mmlspark_tpu.recommendation\n"
        "import mmlspark_tpu.gbdt\n"
        "print(json.dumps([early] + [[s.name, s.args, s.dur_us, s.parent_id]"
        " for s in get_tracer().spans()]))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=subprocess_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    early, *spans = json.loads(proc.stdout.strip().splitlines()[-1])
    assert early == 1                  # the package's own, as it ended
    imports = [s for s in spans if s[0] == tracing.IMPORT_SPAN]
    assert [s[1] for s in imports] == [
        {"module": "mmlspark_tpu"}, {"module": "mmlspark_tpu.nn"}]
    assert all(dur > 0 and parent == 0 for _n, _a, dur, parent in imports)
    assert mmlspark_tpu.__version__


# -- the runner: which step recompiled, and what it cost ------------------ #

def _encoder_stage(**model):
    bundle = ModelBundle.init("transformer", (16,), **dict(
        dict(vocab_size=32, num_layers=1, d_model=16, num_heads=2, d_ff=32,
             max_len=16), **model))
    return DeepModelTransformer(
        input_col="tokens", fetch_dict={"p": "pooled_features"},
        mini_batch_size=4, fused_dispatch=False).set_model(bundle)


def _ids(rows, length, vocab=32):
    return np.random.default_rng(rows).integers(
        0, vocab, (rows, length), dtype=np.int32)


def test_transform_of_a_new_shape_leaves_the_three_under_its_step(tracer):
    stage = _encoder_stage()
    table = Table({"tokens": _ids(8, 16)})
    stage.transform(table)
    first_call = len(tracer.spans())
    steps = named(tracer, "runner.step")
    assert len(steps) == 2
    # a step's enqueue is its `runner.dispatch`, and what JAX does for a
    # new shape hangs under that
    dispatches = named(tracer, "runner.dispatch")
    assert [d.parent for d in dispatches] == steps
    assert [d.args["cache"] for d in dispatches] == ["miss", "hit"]
    by_step = [collections.Counter(
        s.name for s in tracer.spans() if s.parent is d) for d in dispatches]
    # the first step of the shape paid; the second, the same shape, nothing
    assert by_step[0]["jax.lower"] == by_step[0]["jax.compile"] == 1
    assert by_step[0]["jax.trace"] >= 1
    assert by_step[1] == {}
    paid = stage.last_pipeline_stats["compile_seconds"]
    assert paid > 0
    under = [s for s in tracer.spans() if s.parent is dispatches[0]]
    assert paid == pytest.approx(union_seconds(under), abs=5e-3)
    assert steps[0].dur_us * 1e-6 >= paid - 5e-3
    # a second transform: no `jax.*` span, and the ledger does not grow
    stage.transform(table)
    again = tracer.spans()[first_call:]
    assert {s.name for s in again} == {
        "runner.transform", "runner.stack", "runner.feed_wait",
        "runner.prepare", "runner.upload", "runner.step", "runner.dispatch",
        "runner.wait", "runner.readback"}
    assert stage.last_pipeline_stats["compile_seconds"] == paid
    assert stage._exec_cache.compile_seconds == paid
    # another length is another entry: its first step pays, and is named
    stage.transform(Table({"tokens": _ids(4, 12)}))
    (step,) = [s for s in named(tracer, "runner.step")[4:]]
    (dispatch,) = [s for s in named(tracer, "runner.dispatch")[4:]]
    assert dispatch.parent is step and dispatch.args["cache"] == "miss"
    assert {s.name for s in tracer.spans() if s.parent is dispatch} == set(
        JAX_NAMES)
    assert stage.last_pipeline_stats["compile_seconds"] > paid
    ledger = stage._exec_cache.compile_ledger()
    assert len(ledger) == 2 and all(row["seconds"] > 0 for row in ledger)


MLA = dict(
    d_model=64, num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, d_ff_dense=128, first_k_dense=1,
    n_routed_experts=8, experts_held=[0, 8], num_experts_per_tok=3,
    d_ff_expert=32, n_shared_experts=1, routed_scaling_factor=2.446,
    norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=50000.0,
    vocab_size=256, attention_impl="chunked", head_chunk=16)
HYBRID = dict(
    d_model=64, num_heads=8, num_kv_heads=2, conv_taps=3, d_ff_dense=128,
    num_dense_layers=2, n_routed_experts=8, experts_held=[0, 8],
    num_experts_per_tok=4, d_ff_expert=32, n_shared_experts=0,
    routed_scaling_factor=1.0, norm_topk_prob=True, route_epsilon=1e-6,
    rms_norm_eps=1e-5, rope_theta=1e6, vocab_size=256, tie_embeddings=True,
    attention_impl="chunked", head_chunk=16)
EVA = dict(
    d_model=32, num_heads=4, window_size=32, chunk_size=4, d_ff_dense=64,
    rms_norm_eps=1e-5, rope_theta=1e5, vocab_size=40, num_pred_heads=3,
    max_len=128, attention_impl="chunked", head_chunk=16)
ENCODER = dict(d_model=32, num_heads=4, d_ff=64, vocab_size=50, max_len=64)

# family -> (widths, one layer of each kind, three, what is fetched)
FAMILIES = {
    "transformer": (ENCODER, dict(num_layers=1), dict(num_layers=3),
                    "pooled_features"),
    # the dense layer and one expert layer, or three
    "mla_moe_decoder": (MLA, dict(num_layers=2), dict(num_layers=4),
                        "token_logprobs"),
    # both mixers under the two dense layers, then under one pair of
    # expert layers, or three pairs
    "hybrid_moe_decoder": (
        HYBRID, dict(layer_types=["conv", "full_attention"] * 2),
        dict(layer_types=["conv", "full_attention"] * 4), "token_logprobs"),
    "eva_decoder": (EVA, dict(num_layers=1), dict(num_layers=3),
                    "token_logprobs"),
}

# The functions that are traced anew with every layer today, by name, for
# ROADMAP S5 (a finding, not hidden: an entry that goes is a start that got
# shorter). `<lambda>`: flax's `Scope.param` checks every parameter's shape
# with `jax.eval_shape(lambda: init_fn(...))` at every apply, one abstract
# trace a parameter, a layer, a shape. `_normal`, `_truncated_normal`,
# `_uniform`: the initialisers those lambdas reach, at every new shape and
# key. Nothing of the package's own is on the list: the modules' own jitted
# pieces (`_grouped_ffn`, the attention cores) are traced once a shape.
KNOWN_GROWTH = {
    "transformer": {"<lambda>"},
    "mla_moe_decoder": {"<lambda>"},
    "hybrid_moe_decoder": {"<lambda>"},
    "eva_decoder": {"<lambda>"},
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_three_layers_trace_no_function_more_often_than_one(family):
    """The count that would have refused PR 34 on a CPU: a function that is
    traced once a LAYER makes every start longer with depth. The same
    widths at one layer of each kind and at three; every cache dropped
    before each, so that neither run finds the other's traces."""
    widths, one, three, fetch = FAMILIES[family]

    def traced(depth):
        bundle = ModelBundle.init(family, (24,), **widths, **depth)
        stage = DeepModelTransformer(
            input_col="tokens", fetch_dict={"out": fetch}, mini_batch_size=4,
            fused_dispatch=False).set_model(bundle)
        jax.clear_caches()
        tr = Tracer()
        old = set_default_tracer(tr)
        try:
            stage.transform(Table({"tokens": _ids(4, 24)}))
        finally:
            set_default_tracer(old)
        (root,) = named(tr, "runner.transform")
        assert stage.last_pipeline_stats["compile_seconds"] > 0
        return collections.Counter(
            s.args["fun_name"] for s in named(tr, "jax.trace")
            if s.trace_id == root.trace_id)

    shallow, deep = traced(one), traced(three)
    grown = {name for name in deep if deep[name] > shallow.get(name, 0)}
    print(f"{family}: {sum(shallow.values())} traces at one layer, "
          f"{sum(deep.values())} at three; grown: "
          f"{ {n: (shallow.get(n, 0), deep[n]) for n in sorted(grown)} }")
    assert grown == KNOWN_GROWTH[family]
    assert set(deep) == set(shallow)


def test_fit_leaves_traces_under_its_epochs(tracer):
    from mmlspark_tpu.nn.trainer import DNNLearner

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    table = Table({"features": x,
                   "label": (x[:, 0] > 0).astype(np.int64)})

    def fit():
        start = len(tracer.spans())
        DNNLearner(architecture="mlp", model_config={"features": (8,)},
                   epochs=2, batch_size=16, features_col="features",
                   label_col="label").fit(table)
        spans = tracer.spans()[start:]
        epochs = {s.span_id for s in spans if s.name == "trainer.epoch"}
        assert epochs
        return collections.Counter(
            s.name for s in spans if s.parent_id in epochs)

    first = fit()
    assert first["jax.trace"] >= 1 and first["jax.lower"] >= 1
    # ROADMAP S3: `fit` does not keep its epoch programs, so a second one
    # traces them again; printed, not asserted (S3 will change it)
    print(f"first fit under trainer.epoch: {dict(first)}; "
          f"second: {dict(fit())}")


# -- SAR: one shape a pass ------------------------------------------------ #

@pytest.mark.parametrize("remove_seen,program", [
    (True, "_block_topk_unseen"), (False, "_block_topk")])
def test_a_pass_over_17_blocks_and_a_tail_traces_the_block_program_once(
        tracer, remove_seen, program):
    """141 users in blocks of 8 are 17 whole blocks and 5 rows, as
    `sar_recommend_all`'s 69,878 are 17 of 4096 and 246: the last block is
    cut whole too, so the pass leaves ONE trace, lowering and compile of
    the block program, under its first `sar.dispatch`, and a second pass
    none."""
    from mmlspark_tpu.recommendation import SARModel, sar

    rng = np.random.default_rng(3)
    model = SARModel()
    model.user_affinity = rng.random((141, 20)).astype(np.float32)
    model.item_similarity = rng.random((20, 20)).astype(np.float32)
    model.seen = rng.random((141, 20)) < 0.3
    getattr(sar, program).clear_cache()
    model.recommend_for_all_users(5, remove_seen=remove_seen, user_block=8)
    ours = {name: [s for s in named(tracer, name)
                   if program in s.args["fun_name"]]
            for name in JAX_NAMES}
    assert [len(ours[name]) for name in JAX_NAMES] == [1, 1, 1]
    first = named(tracer, "sar.dispatch")[0]
    assert all(s.parent is first for spans in ours.values() for s in spans)
    assert len(named(tracer, "sar.dispatch")) == 18
    spans_so_far = len(tracer.spans())
    model.recommend_for_all_users(5, remove_seen=remove_seen, user_block=8)
    assert not [s for s in tracer.spans()[spans_so_far:]
                if s.name in JAX_NAMES]
