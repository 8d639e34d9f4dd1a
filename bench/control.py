"""Readings that the limits of `bench.check` are set from, at a cell's own
size, in one process on the chip:

    python3 bench/control.py --workload <cell> --seeds 11,12,... \
        --control-seeds 21,22,23 [--out readings.jsonl]

For each of ``--seeds`` the program runs the cell's own timed path (set-up,
a window of one call, the comparison) and its numbers are printed.  For
each of ``--control-seeds`` the plain reference takes the program's place,
computed in the nearest precision below the configuration's float32
(bfloat16): the reference encoder writes the container of a compress
cell, the reference decoder reconstructs the field of a decompress cell,
and the same comparison reads it.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, loadgen, run  # noqa: E402

LOWER = ml_dtypes.bfloat16


def control_numbers(cell: run.Cell, seed: int) -> dict:
    """The comparison's numbers with the reference, in bfloat16, in the
    program's place, on the cell's first field."""
    from repro import codecs
    cfg = cell.config
    comp = cfg["compressor"]
    x = run.make_fields(cell, seed)[0]
    ref = check.reference(cfg["reference"])
    if cell.traffic["op"] == "compress":
        xh = np.asarray(x)
        del x
        packed = ref.encode(
            xh, eb_rel=comp["eb"], nbins=comp["nbins"],
            chunk_size=comp["chunk_size"], sub_size=comp["sub_size"],
            block=tuple(cfg["lorenzo_block"]),
            outlier_frac=comp["outlier_frac"],
            dtype=LOWER)
        nums, _, _ = check.check_compress([xh], [(0, packed)], comp["eb"],
                                          cfg["reference"], {0: 0})
        return nums
    codec = codecs.get(cfg["codec"], **comp)
    packed, _ = loadgen.compress_call(codec, loadgen.Spans())(x)
    xh = np.asarray(x)
    del x
    y, _ = ref.decode(*packed, dtype=LOWER)
    nums, _, _ = check.check_decompress(
        [xh], [packed], [] if y is None else [(0, y)], comp["eb"],
        cfg["reference"])
    return nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = run.Cell.load(ROOT, args.workload)
    run.chip_devices(cell.chips)
    run.enable_compile_cache(ROOT)
    out = open(args.out, "a") if args.out else None
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = [int(s) for s in args.control_seeds.split(",") if s]
    for kind, seed in [("program", s) for s in seeds] + \
            [("control", s) for s in ctrl]:
        t0 = time.perf_counter()
        if kind == "program":
            r = run.run_cell(cell, seed, 0.0, False)
            nums = {k: v["value"] for k, v in r["checks"].items()}
        else:
            nums = control_numbers(cell, seed)
        line = json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                           "numbers": nums,
                           "seconds": time.perf_counter() - t0})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
