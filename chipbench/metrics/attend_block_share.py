"""attend_block_share: share of the S-blocks in the int8-KV decode attend's
grid that it read in the window: 100 x d(attend_blocks) /
d(attend_blocks_grid), from ``/v1/stats``.  Per call, ``attend_blocks``
counts ceil(len / bs) over the rows (the blocks a row holds) and
``attend_blocks_grid`` rows x S / bs (the blocks the grid spans), in every
layer and decode step.  A program without the counters, or one whose
decode runs no int8-KV attend, reads nothing."""
from chipbench.readers import stat_delta

KEY = "attend_blocks"


def read(run):
    if KEY not in run["snap1"]["stats"]:
        return None
    grid = stat_delta(run, "attend_blocks_grid")
    if grid <= 0:
        return None
    return 100.0 * stat_delta(run, KEY) / grid
