"""Grid steps of the interpolation predict kernel per compress call.

Moves `compress_gbps`: the program's ``interp.tiles`` counter
(`core/interp.py`, recorded on the host once per compress call from the
static level plan and the kernel's row tile, when the Pallas predict
kernel runs) inside the window, over the window's calls.  47,936 at
100x500x500 with 8-row tiles: each step is one kernel invocation, so
the count bounds the kernel by per-step overhead before HBM bandwidth.
None where the window recorded none (a program without the counter).
"""
from bench import program

MOVES = "compress_gbps"


def read(ctx):
    n = program.counts(ctx, "interp.tiles")
    if not n:
        return None
    return sum(n) / ctx["calls"]
