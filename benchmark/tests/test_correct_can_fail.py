"""`correct` is a comparison that has been shown to fail: the control (the
reference through the next lower precision, in the program's place) fails
one of the cell's limits, and a run whose timed path is broken underneath
comes out not correct. Tiny sizes, CPU (where the program's product is
float32, so the limits here are float32's and the control is bfloat16);
the readings at the cell's own size on the chip are in PERF.md section 2."""

import json

import pytest

from conftest import LIMITS, SCORE_LIMITS, run_tool


@pytest.mark.parametrize("cell,limits,number", [
    # rounded factors show in the ratings returned
    ("tiny_sar_all", LIMITS, "rating_gap_p90"),
    # weights through bfloat16 show in the fetched columns
    ("tiny_score_streamed", SCORE_LIMITS, "output_gap_p99"),
])
def test_control_fails_a_limit_and_sound_runs_pass(tiny_checkout, cell,
                                                   limits, number):
    proc = run_tool(tiny_checkout, ["benchmark/controls.py", "--workload",
                                    cell, "--seeds", "21,22,23",
                                    "--control", "bfloat16,int8"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 3
    for line in lines:
        over = lambda got: [k for k, v in got.items()      # noqa: E731
                            if not v <= limits[k]]
        assert over(line["sound"]) == [], line
        assert number in over(line["control.bfloat16"]), line
        assert number in over(line["control.int8"]), line
        assert (line["control.int8"][number]
                > 3 * line["control.bfloat16"][number]), line


_PATCH = """
import numpy as np
from mmlspark_tpu.recommendation import SARModel
from mmlspark_tpu.core.schema import Table
_sound = SARModel.recommend_for_all_users
def _broken(self, k, remove_seen=True, user_block=None):
    t = _sound(self, k, remove_seen=remove_seen, user_block=user_block)
    items = np.array(t["recommendations"]); ratings = np.array(t["ratings"])
    %s
    return Table({"user": t["user"], "recommendations": items,
                  "ratings": ratings})
SARModel.recommend_for_all_users = _broken
"""
_SCORE_PATCH = """
import numpy as np
from mmlspark_tpu.nn.runner import DeepModelTransformer
_sound = DeepModelTransformer._transform
def _broken(self, table):
    out = _sound(self, table)
    for name in self.get("fetch_dict"):
        arr = np.array(out[name])
        %s
        out = out.with_column(name, arr)
    return out
DeepModelTransformer._transform = _broken
"""
_GAPS = {"output_gap_p99", "output_gap_max", "pad_leak"}
# fault -> (what breaks the program, the number it has to fail, the others
# it may fail with it); every other number of the run has to hold
SCORE_BREAKS = {
    # a layer left out: the module is built one block short
    "a_layer_left_out": ("""
from mmlspark_tpu.nn import models
_make = models.make_model
models.make_model = lambda architecture, **config: _make(
    architecture, **dict(config, num_layers=config["num_layers"] - 1))
""", "output_gap_p99", _GAPS),
    # an answer altered where it is produced: two rows change places
    "two_rows_swapped": (_SCORE_PATCH % "arr[[0, 1]] = arr[[1, 0]]",
                         "output_gap_max", {"output_gap_p99"}),
    # a part of the batch left out: the table's last batch is not scored
    "a_batch_dropped": ("""
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn.runner import DeepModelTransformer
_sound = DeepModelTransformer._transform
def _broken(self, table):
    col = self.get("input_col")
    return _sound(self, Table({col: table[col][:-8]}))
DeepModelTransformer._transform = _broken
""", "rows_or_positions_missing", _GAPS),
    # the rows that pad a ragged batch leak into its last real row
    "the_pad_row_leaks": (_SCORE_PATCH % (
        "arr[-1] = arr[-1] if len(arr) % 8 == 0 "
        "else 0.5 * (arr[-1] + arr[-2])"), "pad_leak", set()),
}
BREAKS = {
    # an answer altered where it is produced: every user's best item
    # replaced by the item after it
    "an_item_altered": (_PATCH % "items[:, 0] = (items[:, 0] + 1) % 200",
                        "topk_regret"),
    # a part of the batch left out: the last block of users not scored
    "a_block_left_out": (_PATCH % "items[-200:] = -1; ratings[-200:] = 0.0",
                         "seen_or_invalid"),
    # the seen mask not applied
    "seen_not_removed": ("""
from mmlspark_tpu.recommendation import SARModel
_sound = SARModel.recommend_for_all_users
def _broken(self, k, remove_seen=True, user_block=None):
    return _sound(self, k, remove_seen=False, user_block=user_block)
SARModel.recommend_for_all_users = _broken
""", "seen_or_invalid"),
    # a pass that returns fewer ranks than asked
    "a_rank_left_out": ("""
from mmlspark_tpu.recommendation import SARModel
_sound = SARModel.recommend_for_all_users
def _broken(self, k, remove_seen=True, user_block=None):
    return _sound(self, k - 1, remove_seen=remove_seen,
                  user_block=user_block)
SARModel.recommend_for_all_users = _broken
""", "rows_or_ranks_missing"),
}


def _over(proc) -> list:
    return [l.split()[1].rstrip(":") for l in proc.stdout.splitlines()
            if l.startswith("check ") and l.endswith("<-- over")]


def _argv(cell: str) -> list:
    return ["benchmark/run.py", "--workload", cell, "--seed", "31",
            "--seconds", "1", "--trace", "0"]


@pytest.fixture(scope="module")
def sound_is_correct(tiny_checkout):
    """The cell's unbroken run comes out correct (made once a cell)."""
    seen = {}

    def check(cell):
        if cell not in seen:
            sound = run_tool(tiny_checkout, _argv(cell))
            seen[cell] = json.loads(sound.stdout.splitlines()[-1])["correct"]
        assert seen[cell] is True

    return check


@pytest.mark.parametrize("fault", sorted(BREAKS))
def test_broken_timed_path_is_not_correct(tiny_checkout, sound_is_correct,
                                          fault):
    prelude, caught_by = BREAKS[fault]
    sound_is_correct("tiny_sar_all")
    broken = run_tool(tiny_checkout, _argv("tiny_sar_all"), prelude=prelude)
    assert broken.returncode == 0, broken.stderr[-2000:]
    line = json.loads(broken.stdout.splitlines()[-1])
    assert line["correct"] is False, broken.stdout[-1500:]
    assert caught_by in _over(broken), broken.stdout[-1500:]


@pytest.mark.parametrize("cell,fault", [
    ("tiny_score_fused", "a_layer_left_out"),
    ("tiny_score_fused", "two_rows_swapped"),
    ("tiny_score_fused", "a_batch_dropped"),
    ("tiny_score_fused", "the_pad_row_leaks"),
    ("tiny_score_streamed", "a_batch_dropped"),
    ("tiny_score_streamed", "the_pad_row_leaks"),
])
def test_broken_scoring_fails_the_number_named_for_it(
        tiny_checkout, sound_is_correct, cell, fault):
    prelude, caught_by, may_fail_too = SCORE_BREAKS[fault]
    sound_is_correct(cell)
    broken = run_tool(tiny_checkout, _argv(cell), prelude=prelude)
    assert broken.returncode == 0, broken.stderr[-2000:]
    line = json.loads(broken.stdout.splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0, (
        broken.stdout[-1500:])
    over = set(_over(broken))
    assert caught_by in over, broken.stdout[-1500:]
    assert over <= {caught_by} | may_fail_too, broken.stdout[-1500:]
