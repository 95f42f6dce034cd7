"""The control of `correct`, at a size a test run can hold: the reference put
in the program's place comes out correct when sound, and not correct with a
guarantee of the configuration broken. On the chip's machine the same
control.py runs at the cells' own sizes (PERF.md has those readings)."""

import json
import os

import pytest

from conftest import BENCH
from control import run_control


def _load(config: str, mix: str) -> tuple[dict, dict]:
    with open(os.path.join(BENCH, "tests", "data", config)) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", mix)) as f:
        return cfg, json.load(f)


@pytest.mark.parametrize("config,mix,broken,fails", [
    ("fdb-bench-1chip.tiny.json", "write10.json", None, None),
    ("fdb-bench-1chip.tiny.json", "write10.json", "durability",
     "readback_mismatches"),
    ("fdb-bench-1chip.tiny.json", "mixed-90-10.json", None, None),
    ("fdb-bench-1chip.tiny.json", "mixed-90-10.json", "isolation",
     "conflict_violations"),
    ("fdb-bench-1chip.tiny.json", "mixed-90-10.json", "durability",
     "readback_mismatches"),
    ("ycsb-1chip.tiny.json", "ycsb-f.json", None, None),
    ("ycsb-1chip.tiny.json", "ycsb-f.json", "isolation",
     "conflict_violations"),
    ("ycsb-1chip.tiny.json", "ycsb-f.json", "durability",
     "readback_mismatches"),
    ("ycsb-1chip.tiny.json", "ycsb-b.json", None, None),
    ("ycsb-1chip.tiny.json", "ycsb-b.json", "isolation",
     "conflict_violations"),
    ("ycsb-1chip.tiny.json", "ycsb-b.json", "durability",
     "readback_mismatches"),
])
def test_the_control_fails_and_the_sound_reference_passes(config, mix, broken,
                                                          fails):
    cfg, traffic = _load(config, mix)
    correct, compared, notes = run_control(cfg, traffic, seed=3_000_000_023,
                                           txns=6000, broken=broken)
    assert notes["txns_replayed"] >= 6000
    if broken is None:
        assert correct, compared
        assert not any(c["value"] for c in compared.values())
    else:
        assert not correct
        assert compared[fails]["value"] > compared[fails]["limit"] == 0


def test_blind_writes_cannot_show_a_broken_isolation():
    """Why `fdb-write`'s control is the durability one: a transaction that
    reads nothing conflicts with nothing, so its cell compares no verdicts."""
    cfg, traffic = _load("fdb-bench-1chip.tiny.json", "write10.json")
    correct, _compared, _notes = run_control(cfg, traffic, seed=5, txns=3000,
                                             broken="isolation")
    assert correct
