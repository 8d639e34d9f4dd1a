"""Share of the window in which the device ran XLA glue between kernels.

The reader of `glue_share.compress` (moves `compress_gbps`) and
`glue_share.decompress` (moves `decompress_gbps`): one quantity, split
by the end-to-end metric of the cells that report it.  Device-busy time
(the union of the operations' intervals) outside the codec's Pallas
kernels, over the window: the Huffman tree build, outlier `nonzero`,
decode tables, reshapes and copies of core/compressor.py and
core/stages.py.
"""
import re

# the codec's Pallas kernels in a v5e trace: custom calls XLA names after
# their jitted wrappers (`_deflate_jit.1`), op_name ending in pallas_call
KERNELS = re.compile(r"^%?_(dualquant|histogram|encode|deflate|inflate|"
                     r"reverse)_jit(\.\d+)?(\s|$)|/pallas_call")


def read(ctx):
    red = ctx["trace"]
    glue = red.busy_s - (red.seconds(KERNELS) or 0.0)
    return 100.0 * glue / red.window_s
