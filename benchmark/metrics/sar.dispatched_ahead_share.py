"""Of the blocks of a call that can be enqueued while an earlier one is
still unread (all but the pass's first), the share that were: the root
span's `dispatched_ahead` over `blocks` - 1, %, median over the window's
untraced calls (tracer's ring). One block of look-ahead kept all through a
pass reads 100% whatever the block size (17 of 17 at 18 blocks); a loop
that reads each block back before it enqueues the next reads 0%."""
import statistics

from harness.program_spans import SAR_ROOT, window_args


def read(run):
    calls = window_args(run, SAR_ROOT)
    shares = [100.0 * args["dispatched_ahead"] / max(args["blocks"] - 1, 1)
              for call in calls or () for args in call
              if "dispatched_ahead" in args and args.get("blocks")]
    return statistics.median(shares) if shares else None
