"""Roofline share of the quant-code histogram kernel (kernels/histogram).

Moves `compress_gbps`. Reads every quant code (2 B), writes nbins int32
counts.
"""
import re

from bench import roofline

MOVES = "compress_gbps"
# the kernel in a v5e trace: the Pallas custom call XLA names after the
# jitted wrapper (`_histogram_jit.1`), whose op_name ends in pallas_call
EVENTS = re.compile(r"^%?_histogram_jit(\.\d+)?(\s|$)|"
                    r"jit\(_histogram_jit\)/pallas_call")


def work_bytes(w: dict) -> int:
    return 2 * w["n_sym"] + 4 * w["nbins"]


def read(ctx):
    return roofline.share(ctx, EVENTS, work_bytes)
