"""attend_block_share reads the int8-KV attend's block counters from
``/v1/stats`` (no JAX), and nothing where a program lacks them."""
from __future__ import annotations

import pytest

from chipbench import bench


def run_of(stats0, stats1):
    return {"root": bench.ROOT, "snap0": {"stats": stats0, "metrics": {}},
            "snap1": {"stats": stats1, "metrics": {}}}


def read(run):
    return bench.load_metric("attend_block_share").read(run)


def test_attend_block_share_from_the_attend_counters():
    # 100 dispatches of 4 steps, 24 layers, 16 rows of 4 blocks: 2.2 read
    grid = 100 * 4 * 24 * 16 * 4
    r = run_of({"attend_blocks": 7, "attend_blocks_grid": 64},
               {"attend_blocks": 7 + grid * 2.2 / 4,
                "attend_blocks_grid": 64 + grid})
    assert read(r) == pytest.approx(55.0)


@pytest.mark.parametrize("stats", [
    {"decode_steps": 1},                                # a program without them
    {"attend_blocks": 0, "attend_blocks_grid": 0},      # fp KV: no int8 attend
], ids=["no_counters", "no_int8_attend"])
def test_attend_block_share_reads_nothing_without_attend_blocks(stats):
    assert read(run_of(stats, dict(stats))) is None
