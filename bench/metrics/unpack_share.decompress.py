"""Share of the window spent unpacking the container on the host.

Moves `decompress_gbps`: the harness's `bench.unpack` spans
(`codecs.from_arrays` and `codec.unpack`, which scatters the packed words
into the dense buffer and moves the arrays to the device) over the
window, on the host clock.
"""
MOVES = "decompress_gbps"
SPAN = "bench.unpack"


def read(ctx):
    t0, t1 = ctx["window"]
    inside = [b - a for n, a, b in ctx["spans"]
              if n == SPAN and a >= t0 and b <= t1]
    if not inside:
        return None
    return 100.0 * sum(inside) / (t1 - t0)
