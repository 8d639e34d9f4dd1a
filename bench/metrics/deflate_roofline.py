"""Roofline share of the deflate kernel (kernels/deflate).

Moves `compress_gbps`. Reads one 32-bit codeword unit per symbol, writes
the used words of the bitstream and the gap arrays (the measured
container's sizes).
"""
import re

from bench import roofline

MOVES = "compress_gbps"
# the kernel in a v5e trace: the Pallas custom call XLA names after the
# jitted wrapper (`_deflate_jit.1`), whose op_name ends in pallas_call
EVENTS = re.compile(r"^%?_deflate_jit(\.\d+)?(\s|$)|"
                    r"jit\(_deflate_jit\)/pallas_call")


def work_bytes(w: dict) -> int:
    return 4 * w["n_sym"] + w["stream_bytes"] + w["gap_bytes"]


def read(ctx):
    return roofline.share(ctx, EVENTS, work_bytes)
