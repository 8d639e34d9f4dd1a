"""Roofline share of the codebook-gather encode kernel (kernels/encode).

Moves `compress_gbps`. Reads every quant code (2 B) and the nbins-entry
codebook (4 B each), writes one 32-bit (length, codeword) unit per
symbol (paper Fig. 4).
"""
import re

from bench import roofline

MOVES = "compress_gbps"
# the kernel in a v5e trace: the Pallas custom call XLA names after the
# jitted wrapper (`_encode_jit.1`), whose op_name ends in pallas_call
EVENTS = re.compile(r"^%?_encode_jit(\.\d+)?(\s|$)|"
                    r"jit\(_encode_jit\)/pallas_call")


def work_bytes(w: dict) -> int:
    return 2 * w["n_sym"] + 4 * w["nbins"] + 4 * w["n_sym"]


def read(ctx):
    return roofline.share(ctx, EVENTS, work_bytes)
