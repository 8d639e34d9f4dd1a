"""Blocking reads of device data per call, counted by the program.

The reader of `host_syncs_per_call.compress` (moves `compress_gbps`)
and `host_syncs_per_call.decompress` (moves `decompress_gbps`): the
``host_syncs`` counter events inside the window over the window's
calls.  The codec counts one event at each read of a `jax.Array` on
its path (`resolve_eb`, `pack_blob`, `HuffmanEncoder.decode_meta`, ...).
"""
from bench import program


def read(ctx):
    n = program.counts(ctx, "host_syncs")
    if n is None:
        return None
    return sum(n) / ctx["calls"]
