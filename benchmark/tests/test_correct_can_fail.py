"""`correct` is a comparison that has been shown to fail: the control (the
reference through the next lower precision, in the program's place) fails
one of the cell's limits, and a run whose timed path is broken underneath
comes out not correct. Tiny sizes, CPU (where the program's product is
float32, so the limits here are float32's and the control is bfloat16);
the readings at the cell's own size on the chip are in PERF.md section 2."""

import json

import pytest

from conftest import LIMITS, run_tool


def test_control_fails_a_limit_and_sound_runs_pass(tiny_checkout):
    proc = run_tool(tiny_checkout, ["benchmark/controls.py", "--workload",
                                    "tiny_sar_all", "--seeds", "21,22,23",
                                    "--control", "bfloat16,int8"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 3
    for line in lines:
        over = lambda got: [k for k, v in got.items()      # noqa: E731
                            if not v <= LIMITS[k]]
        assert over(line["sound"]) == [], line
        # rounded factors show in the ratings returned
        assert "rating_gap_p90" in over(line["control.bfloat16"]), line
        assert "rating_gap_p90" in over(line["control.int8"]), line
        assert (line["control.int8"]["rating_gap_p90"]
                > 3 * line["control.bfloat16"]["rating_gap_p90"]), line


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


@pytest.mark.parametrize("fault", sorted(BREAKS))
def test_broken_timed_path_is_not_correct(tiny_checkout, fault):
    prelude, caught_by = BREAKS[fault]
    argv = ["benchmark/run.py", "--workload", "tiny_sar_all", "--seed", "31",
            "--seconds", "1", "--trace", "0"]
    sound = run_tool(tiny_checkout, argv)
    assert json.loads(sound.stdout.splitlines()[-1])["correct"] is True
    broken = run_tool(tiny_checkout, argv, prelude=prelude)
    assert broken.returncode == 0, broken.stderr[-2000:]
    line = json.loads(broken.stdout.splitlines()[-1])
    assert line["correct"] is False, broken.stdout[-1500:]
    over = [l.split()[1].rstrip(":") for l in broken.stdout.splitlines()
            if l.startswith("check ") and l.endswith("<-- over")]
    assert caught_by in over, broken.stdout[-1500:]
