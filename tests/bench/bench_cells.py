"""Cells of BENCHMARK.json cut to shapes a CPU test run can hold."""
from __future__ import annotations

import copy
import json
import os

from bench import loadgen, run

ROOT = run.ROOT
TINY = {"hurricane": [10, 24, 40], "hacc": [9000]}


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny(cell_name: str) -> run.Cell:
    cell = run.Cell.load(ROOT, cell_name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["field"]["shape"] = TINY[cell.config["field"]["generator"]]
    return cell


def cell_for(op: str, gen: str) -> str:
    """The cell that drives `op` on the field of generator `gen`."""
    b = bench_json()
    cfg = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        with open(os.path.join(ROOT, cfg[w["config"]]["file"])) as f:
            g = json.load(f)["field"]["generator"]
        if loadgen.load(ROOT, w["traffic"])["op"] == op and g == gen:
            return w["name"]
    raise LookupError((op, gen))
