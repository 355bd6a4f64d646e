"""The reader of ``local_sort_tiles.bulk``: launches of the stable bitonic
kernel per call, on hand-made traces and on one recorded on the chip.

    python -m pytest bench/
"""
from __future__ import annotations

import json
import os

import pytest

from bench import run
from bench.test_reduce import FIXTURE, TRACE, _run

READ = run.load_reader("local_sort_tiles.bulk")


def test_counts_the_kernels_of_each_call():
    # the second call runs one bitonic launch, the first none
    assert READ(_run(TRACE)) == pytest.approx(0.5)


def test_counts_every_tile_of_a_loop():
    tiles = [[f"bitonic_sort_rows_stable.{i % 2}", 700 + 40 * i, 30]
             for i in range(5)]
    ops = [op for op in TRACE["ops"]
           if not op[0].startswith("bitonic")] + tiles
    assert READ(_run(dict(TRACE, ops=ops))) == pytest.approx(2.5)


def test_nothing_without_a_trace():
    assert READ(_run(None)) is None


def test_recorded_chip_trace_runs_one_launch_per_class():
    """A one-class-one-launch program (2^18 records: ten size classes)."""
    with open(FIXTURE) as f:
        trace = json.load(f)
    assert READ(_run(trace, n=1 << 18)) == 10.0


def test_the_metric_is_declared_for_the_bulk_cells():
    entry, = [m for m in run.load_benchmark()["per_layer"]
              if m["name"] == "local_sort_tiles.bulk"]
    assert entry["workloads"] == ["kv32_uniform.bulk", "k32_ands3.bulk"]
    assert os.path.exists(os.path.join(os.path.dirname(__file__), "metrics",
                                       "local_sort_tiles.bulk.py"))
