"""`core.full_gc_ms_per_s` (PR 35): a data file over the `counter_ratio`
reader that is there. Over a made-up context it reads the growth of the
core's `FullCollectionSeconds` in the window as milliseconds a second; a
program without the counter (the parent) reads nothing and raises nothing;
BENCHMARK.json lists it once, for every cell, under the commit proxy.
"""

import json
import os

import pytest

from conftest import BENCH, ROOT
import run

NAME = "core.full_gc_ms_per_s"


def entry() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert len(listed) == 1
    return listed[0]


def context(before: dict, after: dict, seconds: float = 40.0) -> dict:
    return {"resolver": (before, after), "storage": [], "seconds": seconds}


def test_it_reads_the_windows_collection_time_as_ms_a_second():
    ctx = context({"FullCollections": 3, "FullCollectionSeconds": 0.25},
                  {"FullCollections": 19, "FullCollectionSeconds": 3.05})
    got = run.read_metrics([entry()], "fdb-write-1m", ctx, must=False)
    assert got == {NAME: {"value": pytest.approx(70.0), "unit": "ms/s"}}


def test_a_window_without_a_full_collection_reads_zero_not_nothing():
    ctx = context({"FullCollectionSeconds": 0.5},
                  {"FullCollectionSeconds": 0.5})
    got = run.read_metrics([entry()], "ycsb-b", ctx, must=False)
    assert got[NAME]["value"] == 0.0


@pytest.mark.parametrize("before,after", [
    ({"BatchesIn": 1}, {"BatchesIn": 9}),
    ({"BatchesIn": 1}, {"BatchesIn": 9, "FullCollectionSeconds": 0.1})])
def test_a_parent_without_the_counter_reads_nothing(before, after):
    got = run.read_metrics([entry()], "fdb-write", context(before, after),
                           must=False)
    assert got == {}


def test_it_is_listed_once_for_every_cell_under_the_commit_proxy():
    m = entry()
    assert m == {"name": NAME, "unit": "ms/s", "better": "lower",
                 "source": "program_counter", "layer": "commit proxy",
                 "moves": "throughput_ops_s"}
    with open(os.path.join(BENCH, "metrics", NAME + ".json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "counter_ratio", "args": {
        "role": "resolver", "numerator": ["FullCollectionSeconds"],
        "per_second": True, "scale": 1000.0}}
