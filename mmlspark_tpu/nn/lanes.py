"""What the kernels that fold a long axis into running statistics share
(`attention/fold.py`'s online softmax over the keys, `loglik.py`'s
log-sum-exp over the vocabulary), owned by neither: the lane-dense idiom (a
statistic kept a vector register wide, never a (rows, 1) column), the value
that masks, and the one scoped VMEM a call may state."""

from __future__ import annotations

import functools
import operator

import jax.numpy as jnp

# the lanes of a vector register: the running statistics are kept that wide
LANES = 128
NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/max NaN-free

# What a grid step may keep in VMEM by its kernel's own count
# (`fold._step_bytes`, `loglik._head_bytes`), and what a call whose step
# passes the default STATES as its scoped VMEM: twice the 16 MB a custom call
# gets without asking. A key head's whole group needs 16.5 MB (4 heads of 64)
# to 27.5 MB (7 of 128) by the compiler's count, 2.6 MB more with a padded
# tail, and 5 and 7 have no divisor between. What a call states beyond the
# default is taken from the whole program (its neighbours keep fewer operands
# in VMEM): read on a v5e in the three grouped cells, that costs 0.04 to
# 0.2% of a call where the kernels return 0.7 to 3.0% (PERF.md, PR 48; a
# call that took 64 MB had cost its neighbours 8%: PR 28). A constant, not a
# parameter; a call whose step fits the default states nothing.
STEP_VMEM = 32 * 1024 * 1024


def over(x, width: int):
    """A per-row statistic held replicated across its lanes, (rows, lanes),
    laid over `width` columns: whole registers repeated, never a (rows, 1)
    column permuted back over the lanes. A column broadcasts by itself."""
    lanes = x.shape[1]
    if lanes == 1 or width == lanes:
        return x
    if width < lanes:
        return x[:, :width]
    if width % lanes:
        return x[:, :1]
    return jnp.tile(x, (1, width // lanes))


def lane_sums(p, lanes: int):
    """p's columns added up in blocks of `lanes`: (rows, lanes) partial
    sums a row, elementwise (no reduction across lanes), one block after
    the other (added by halves, six equations where eight, the compiler
    schedules a step 2% worse: PERF.md, PR 44); one lane is the row sum
    itself."""
    if lanes == 1:
        return p.sum(-1, keepdims=True)
    return functools.reduce(operator.add, jnp.split(p, p.shape[1] // lanes, 1))
