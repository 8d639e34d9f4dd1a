"""Roofline share of the gap-array inflate kernel (kernels/inflate).

Moves `decompress_gbps`. Reads the used words of the bitstream and the
gap arrays (the measured container's sizes), writes one quant code per
symbol (2 B).
"""
import re

from bench import roofline

MOVES = "decompress_gbps"
# the kernel in a v5e trace: the Pallas custom call XLA names after the
# jitted wrapper (`_inflate_jit.1`), whose op_name ends in pallas_call
EVENTS = re.compile(r"^%?_inflate_jit(\.\d+)?(\s|$)|"
                    r"jit\(_inflate_jit\)/pallas_call")


def work_bytes(w: dict) -> int:
    return w["stream_bytes"] + w["gap_bytes"] + 2 * w["n_sym"]


def read(ctx):
    return roofline.share(ctx, EVENTS, work_bytes)
