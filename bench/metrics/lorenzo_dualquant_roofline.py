"""Roofline share of the fused PREQUANT + Lorenzo delta + POSTQUANT kernel
(kernels/lorenzo).

Moves `compress_gbps`. Reads the float32 field, writes one quant code
per blocked value (2 B: nbins <= 65536) and each outlier's (index,
delta) pair (8 B).
"""
import re

from bench import roofline

MOVES = "compress_gbps"
# the kernel in a v5e trace: the Pallas custom call XLA names after the
# jitted wrapper (`_dualquant_jit.1`), whose op_name ends in pallas_call
EVENTS = re.compile(r"^%?_dualquant_jit(\.\d+)?(\s|$)|"
                    r"jit\(_dualquant_jit\)/pallas_call")


def work_bytes(w: dict) -> int:
    return 4 * w["n_values"] + 2 * w["n_sym"] + 8 * w["n_outliers"]


def read(ctx):
    return roofline.share(ctx, EVENTS, work_bytes)
