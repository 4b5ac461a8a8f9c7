"""Spans inside `SARModel.recommend_for_all_users`: one `sar.recommend_all`
a call and, a block, `sar.slice`, `sar.dispatch`, `sar.wait`,
`sar.readback` under it, on the process-default tracer. A fake clock that
ticks once a reading makes every duration exact."""

from __future__ import annotations

import numpy as np
import pytest

from mmlspark_tpu.observability import Tracer, set_default_tracer
from mmlspark_tpu.recommendation import SARModel

USERS, ITEMS = 37, 20
PHASES = ["sar.slice", "sar.dispatch", "sar.wait", "sar.readback"]
TICK = 0.001


class TickingClock:
    """Every reading is one tick later than the last."""

    def __init__(self):
        self.readings = 0

    def monotonic(self) -> float:
        self.readings += 1
        return self.readings * TICK


@pytest.fixture
def model():
    rng = np.random.default_rng(5)
    m = SARModel()
    m.user_affinity = rng.random((USERS, ITEMS)).astype(np.float32)
    m.item_similarity = rng.random((ITEMS, ITEMS)).astype(np.float32)
    m.seen = rng.random((USERS, ITEMS)) < 0.3
    return m


@pytest.fixture
def tracer():
    tr = Tracer(clock=TickingClock(), id_seed=1)
    old = set_default_tracer(tr)
    yield tr
    set_default_tracer(old)


@pytest.mark.parametrize("block,remove_seen,k", [
    (8, True, 5),        # ragged: four blocks of 8 and one of 5
    (37, True, 5),       # one block
    (None, True, 5),     # the default block, larger than the table
    (8, False, 5),       # the unmasked program
    (10, True, 50),      # k clipped to the number of items
])
def test_a_call_records_its_phases(model, tracer, block, remove_seen, k):
    table = model.recommend_for_all_users(k, remove_seen=remove_seen,
                                          user_block=block)
    spans = tracer.spans()
    (root,) = [s for s in spans if s.name == "sar.recommend_all"]
    children = [s for s in spans if s is not root]
    size = block or SARModel.USER_BLOCK
    blocks = -(-USERS // size)
    k = min(k, ITEMS)
    assert root.parent_id == 0 and spans[-1] is root    # completes last
    assert root.args == {
        "users": USERS, "items": ITEMS, "k": k, "block": size,
        "blocks": blocks, "remove_seen": remove_seen,
        # float32 ratings and int32 item ids, before the casts to 64 bits
        "bytes_read_back": USERS * k * (4 + 4)}
    assert table["ratings"].dtype == np.float64
    assert table["recommendations"].dtype == np.int64
    # slice, dispatch, wait, readback a block, in that order, each under
    # the call's span and in its trace
    assert [s.name for s in children] == PHASES * blocks
    assert all(s.parent is root and s.trace_id == root.trace_id
               for s in children)
    slices = [s for s in children if s.name == "sar.slice"]
    assert [(s.args["lo"], s.args["hi"]) for s in slices] == [
        (lo, min(lo + size, USERS)) for lo in range(0, USERS, size)]
    readbacks = [s for s in children if s.name == "sar.readback"]
    assert [s.args["bytes"] for s in readbacks] == [
        (s.args["hi"] - s.args["lo"]) * k * 8 for s in slices]
    # one tick a span, two readings a child: the children account for
    # all of the call but its self time
    assert all(s.dur_us == pytest.approx(TICK * 1e6) for s in children)
    assert root.dur_us == pytest.approx((8 * blocks + 1) * TICK * 1e6)


def test_a_disabled_tracer_changes_nothing_and_records_nothing(model, tracer):
    traced = model.recommend_for_all_users(5, user_block=8)
    off = Tracer(enabled=False)
    set_default_tracer(off)
    plain = model.recommend_for_all_users(5, user_block=8)
    assert off.spans() == []
    assert list(plain.columns) == list(traced.columns)
    for name in traced.columns:
        assert plain[name].dtype == traced[name].dtype
        np.testing.assert_array_equal(plain[name], traced[name])


def test_every_call_is_its_own_trace(model, tracer):
    for _ in range(3):
        model.recommend_for_all_users(5, user_block=16)
    roots = [s for s in tracer.spans() if s.name == "sar.recommend_all"]
    assert len(roots) == 3
    assert len({s.trace_id for s in roots}) == 3
    assert len(tracer.spans()) == 3 * (1 + 4 * 3)
