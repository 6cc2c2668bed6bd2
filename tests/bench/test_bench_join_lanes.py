"""The reader of the engine's ``ingest.join_lanes`` counter."""

import os
from types import SimpleNamespace as NS

import benchsteer  # noqa: F401  (puts bench/ on the path)
import harness

READER = os.path.join(harness.BENCH, "layer_metrics", "ingest.join_lanes.py")


def _read(before, after):
    mod = harness.load_module(READER, "bench_layer_ingest_join_lanes")
    return mod.read(NS(spans=[], window={"jobs": 4},
                       registry_before={"counters": before},
                       registry_after={"counters": after}))


def test_lanes_per_job():
    """Four jobs of two join maps, each two batches of 65,536 rows probed
    8 wide."""
    lanes = 4 * 2 * 2 * 65536 * 8
    assert _read({"ingest.join_lanes": 100}, {"ingest.join_lanes": 100 + lanes}) == lanes / 4


def test_nothing_from_a_program_without_the_counter():
    assert _read({}, {"ingest.host_syncs": 36}) is None
