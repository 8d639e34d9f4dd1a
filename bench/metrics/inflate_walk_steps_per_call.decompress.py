"""Serial walk steps of the gap-array inflate kernel per decompress call.

Moves `decompress_gbps`: the program's ``inflate.walk_steps`` counter
(`core/stages.py`, recorded on the host once per decompress call from
the kernel's static grid, when the Pallas gap kernel runs) inside the
window, over the window's calls.  Grid steps x `sub_size`: each step of
the walk is one link of the kernel's serial chain, so the count bounds
the kernel by the chain's latency before HBM bandwidth.  None where the
window recorded none (a program without the counter).
"""
from bench import program

MOVES = "decompress_gbps"


def read(ctx):
    n = program.counts(ctx, "inflate.walk_steps")
    if not n:
        return None
    return sum(n) / ctx["calls"]
