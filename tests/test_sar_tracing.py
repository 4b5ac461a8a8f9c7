"""Spans inside `SARModel.recommend_for_all_users`: one `sar.recommend_all`
a call and, a block, `sar.slice`, `sar.dispatch`, `sar.wait`,
`sar.readback` under it, on the process-default tracer, block b+1's first
two before block b's last two. A fake clock that ticks once a reading makes
every duration exact. And what the pipelined loop returns: the one-block
call's table, bit for bit."""

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


def make_model(users=USERS, seen_share=0.3):
    rng = np.random.default_rng(5)
    m = SARModel()
    m.user_affinity = rng.random((users, ITEMS)).astype(np.float32)
    m.item_similarity = rng.random((ITEMS, ITEMS)).astype(np.float32)
    m.seen = rng.random((users, ITEMS)) < seen_share
    return m


def assert_same_table(got, want):
    assert list(got.columns) == list(want.columns)
    for name in want.columns:
        assert got[name].dtype == want[name].dtype
        np.testing.assert_array_equal(got[name], want[name])


@pytest.fixture
def model():
    return make_model()


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
        "bytes_read_back": USERS * k * (4 + 4),
        # every block but the first is enqueued while the one before it
        # has not been read back yet
        "dispatched_ahead": blocks - 1}
    assert table["ratings"].dtype == np.float64
    assert table["recommendations"].dtype == np.int64
    # slice and dispatch of block b+1 come before wait and readback of
    # block b; each span lies under the call's span and in its trace
    assert [s.name for s in children] == (
        PHASES[:2] + PHASES * (blocks - 1) + PHASES[2:])
    # never more than two blocks in flight, and the last one drained
    in_flight = np.cumsum([{"sar.dispatch": 1, "sar.readback": -1}.get(
        s.name, 0) for s in children])
    assert in_flight.max() == min(blocks, 2) and in_flight[-1] == 0
    assert all(s.parent is root and s.trace_id == root.trace_id
               for s in children)
    slices = [s for s in children if s.name == "sar.slice"]
    assert [(s.args["lo"], s.args["hi"]) for s in slices] == [
        (lo, min(lo + size, USERS)) for lo in range(0, USERS, size)]
    readbacks = [s for s in children if s.name == "sar.readback"]
    assert [s.args["bytes"] for s in readbacks] == [
        (s.args["hi"] - s.args["lo"]) * k * 8 for s in slices]
    # one tick a span, two readings a child (8 a block and one for the
    # call): the children account for all of the call but its self time
    assert all(s.dur_us == pytest.approx(TICK * 1e6) for s in children)
    assert root.dur_us == pytest.approx((8 * blocks + 1) * TICK * 1e6)


def test_a_disabled_tracer_changes_nothing_and_records_nothing(model, tracer):
    traced = model.recommend_for_all_users(5, user_block=8)
    off = Tracer(enabled=False)
    set_default_tracer(off)
    plain = model.recommend_for_all_users(5, user_block=8)
    assert off.spans() == []
    assert_same_table(plain, traced)


def test_every_call_is_its_own_trace(model, tracer):
    for _ in range(3):
        model.recommend_for_all_users(5, user_block=16)
    roots = [s for s in tracer.spans() if s.name == "sar.recommend_all"]
    assert len(roots) == 3
    assert len({s.trace_id for s in roots}) == 3
    assert len(tracer.spans()) == 3 * (1 + 4 * 3)


@pytest.mark.parametrize("users,block,remove_seen,with_seen,k", [
    (37, 8, True, True, 5),      # ragged: four blocks of 8 and one of 5
    (32, 16, True, True, 5),     # two equal blocks
    (37, 8, False, True, 5),     # the unmasked program, by the argument
    (37, 8, True, False, 5),     # and by a model that holds no seen mask
    (37, 10, True, True, 50),    # k clipped to the number of items
    (37, 1, True, True, 3),      # a block a user: 36 enqueued ahead
    (700, 600, True, True, 5),   # three blocks of 344 where 600 may be
])
def test_pipelined_blocks_return_the_one_block_table(tracer, users, block,
                                                     remove_seen, with_seen,
                                                     k):
    m = make_model(users)
    if not with_seen:
        m.seen = None
    whole = m.recommend_for_all_users(k, remove_seen=remove_seen,
                                      user_block=users)
    blocked = m.recommend_for_all_users(k, remove_seen=remove_seen,
                                        user_block=block)
    assert_same_table(blocked, whole)
    assert blocked["recommendations"].shape == (users, min(k, ITEMS))
    one, many = [s for s in tracer.spans() if s.name == "sar.recommend_all"]
    assert one.args["blocks"] == 1 and one.args["dispatched_ahead"] == 0
    assert many.args["dispatched_ahead"] == many.args["blocks"] - 1 > 0
    assert many.args["remove_seen"] == (remove_seen and with_seen)
    if remove_seen and with_seen:
        valid = blocked["recommendations"] >= 0
        rows = np.nonzero(valid)[0]
        assert not m.seen[rows, blocked["recommendations"][valid]].any()


@pytest.mark.parametrize("block", [8, 37])
def test_users_short_of_unseen_items_are_marked(block):
    """Nine in ten items seen, so most users have fewer than k unseen
    ones: the ranks past a user's last unseen item read -1 and 0.0, in
    every block of the pipelined loop."""
    k = 5
    m = make_model(seen_share=0.9)
    unseen = (~m.seen).sum(axis=1)
    assert (unseen < k).sum() > 20 and (unseen >= k).any()
    table = m.recommend_for_all_users(k, user_block=block)
    items, ratings = table["recommendations"], table["ratings"]
    ranks = np.arange(k)[None, :]
    short = ranks >= unseen[:, None]
    np.testing.assert_array_equal(items[short], -1)
    np.testing.assert_array_equal(ratings[short], 0.0)
    assert (items[~short] >= 0).all() and (ratings[~short] > 0).all()
    rows = np.nonzero(~short)[0]
    assert not m.seen[rows, items[~short]].any()


@pytest.mark.parametrize("users,block,rows", [
    (69878, 4096, 3328),    # MovieLens-10M: 21 equal blocks, ten rows twice
    (8192, 4096, 4096),     # users that divide: the block given
    (8193, 4096, 2816),     # one user more: three blocks, 255 rows twice
    (37, 8, 8),             # a block under 256 rows has one size to take
    (37, 37, 37), (5, 4096, 5)])        # fewer users than a block: one
def test_every_block_of_a_pass_takes_the_rows_that_waste_fewest(users, block,
                                                                rows):
    from mmlspark_tpu.recommendation.sar import _block_rows

    assert _block_rows(users, block) == rows
    assert rows <= block and -(-users // rows) * rows >= users
