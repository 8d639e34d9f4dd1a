"""Share of the field's values that went to the sparse outlier store.

Moves `compress_gbps`: the program's ``outliers.values`` counter
(`core/stages._pack_outliers`, the values in the store of each call,
counted from the host copy `pack_blob` already holds) inside the window,
over the values of the window's calls (the configuration's field shape
times the calls).  Every value in the store is one the predictor missed
by half the bins or more; each costs the store 8 B and the outlier pass
its work.  None where the window recorded none (a program without the
counter).
"""
import numpy as np

from bench import program

MOVES = "compress_gbps"


def read(ctx):
    n = program.counts(ctx, "outliers.values")
    if not n:
        return None
    values = int(np.prod(ctx["config"]["field"]["shape"]))
    return 100.0 * sum(n) / (ctx["calls"] * values)
