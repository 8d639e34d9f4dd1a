"""Share of the window spent packing the container on the host.

Moves `compress_gbps`: the harness's `bench.pack` spans (`codec.pack`,
which pulls the dense word buffer to the host and packs the used words,
and `codecs.to_arrays`) over the window, on the host clock.
"""
MOVES = "compress_gbps"
SPAN = "bench.pack"


def read(ctx):
    t0, t1 = ctx["window"]
    inside = [b - a for n, a, b in ctx["spans"]
              if n == SPAN and a >= t0 and b <= t1]
    if not inside:
        return None
    return 100.0 * sum(inside) / (t1 - t0)
