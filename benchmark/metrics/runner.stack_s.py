"""Seconds of a call in which the column was made one host array and the
jitted forward looked up (`np.stack` of a list of rows, or a view of the
array the table holds): the call's `runner.stack` spans summed, one a
length of the traffic file, median over the window's untraced calls
(tracer's ring)."""
from harness.runner_spans import median_seconds


def read(run):
    return median_seconds(run, "runner.stack")
