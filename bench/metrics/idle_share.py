"""Share of the traced window in which no operation ran on the device.

The reader of `idle_share.compress` (moves `compress_gbps`) and
`idle_share.decompress` (moves `decompress_gbps`): one quantity, split
by the end-to-end metric of the cells that report it.  100 (1 - busy /
window), busy being the union of the device operations' intervals
(`bench.trace_reduce`).
"""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
