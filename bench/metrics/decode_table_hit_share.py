"""Share of the decode-table lookups that the cache served.

The reader of `decode_table_hit_share.decompress` (moves
`decompress_gbps`): ``decode_table.hits`` over hits plus
``decode_table.builds`` (`huffman.decode_table`, an LRU keyed by the
codebook array's identity) inside the window.  None where the window
made no lookup.
"""
from bench import program

MOVES = "decompress_gbps"


def read(ctx):
    hits = program.counts(ctx, "decode_table.hits")
    builds = program.counts(ctx, "decode_table.builds")
    if hits is None or builds is None or not (hits or builds):
        return None
    return 100.0 * sum(hits) / (sum(hits) + sum(builds))
