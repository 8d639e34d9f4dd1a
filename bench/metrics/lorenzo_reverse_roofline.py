"""Roofline share of the reverse Lorenzo kernel: in-block prefix sums and
dequantisation (kernels/lorenzo).

Moves `decompress_gbps`. Reads one quant code per blocked value (2 B)
and the outliers (8 B each), writes the float32 field.
"""
import re

from bench import roofline

MOVES = "decompress_gbps"
# the kernel in a v5e trace: the Pallas custom call XLA names after the
# jitted wrapper (`_reverse_jit.1`), whose op_name ends in pallas_call
EVENTS = re.compile(r"^%?_reverse_jit(\.\d+)?(\s|$)|"
                    r"jit\(_reverse_jit\)/pallas_call")


def work_bytes(w: dict) -> int:
    return 2 * w["n_sym"] + 8 * w["n_outliers"] + 4 * w["n_values"]


def read(ctx):
    return roofline.share(ctx, EVENTS, work_bytes)
