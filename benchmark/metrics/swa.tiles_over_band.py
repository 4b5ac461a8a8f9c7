"""What the banded kernel's tiles cost at the band's edges: the (query
block, key block) pairs its grid computes on over the pairs the band
itself holds in tiles of that size, as `runner.transform` writes both on
its root span from shapes (`attn_window_tile_pairs`,
`attn_window_tile_pairs_needed`: summed over a table's batches, heads and
sliding layers; a table whose rows fit the window writes none); summed
over a call's tables, median over the window's untraced calls (tracer's
ring). 1.0 is a grid that visits the band and nothing else; at tiles of
1024 a window of 4096 is 5 key blocks a query block where 4 and a bit are
needed."""
import statistics

from harness.cells import load_module

VISITED, NEEDED = "attn_window_tile_pairs", "attn_window_tile_pairs_needed"


def read(run):
    calls = load_module("metrics", "moe_expert_roofline").root_args(run)
    ratios = []
    for call in calls or ():
        banded = [args for args in call if VISITED in args and args.get(
            NEEDED)]
        if banded:
            ratios.append(sum(args[VISITED] for args in banded)
                          / sum(args[NEEDED] for args in banded))
    return statistics.median(ratios) if ratios else None
