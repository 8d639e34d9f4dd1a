"""Share of the window spent moving the container between host and
device, on the host clock.

The reader of `transfer_share.compress` (moves `compress_gbps`) and
`transfer_share.decompress` (moves `decompress_gbps`): the program's
``codec.pack.d2h`` spans (`compressor.pack_blob` pulling the blob to the
host) and ``codec.unpack.h2d`` spans (`compressor.unpack_blob` handing
the fields to the device) inside the window, over the window.  A
compress window holds only the first, a decompress window only the
second.
"""
from bench import program

SPANS = ("codec.pack.d2h", "codec.unpack.h2d")


def read(ctx):
    t = program.span_seconds(ctx, SPANS)
    if t is None:
        return None
    t0, t1 = ctx["window"]
    return 100.0 * t / (t1 - t0)
