"""Cache manager: prompt tokens served from the prefix trie over prompt tokens looked up. A control here: nothing is shared, so about 0."""
from benchmark.layers import _shared


def read(ctx):
    return _shared.pct(ctx['stats']['prefix_hit_tokens'], ctx['stats']['prefix_lookup_tokens'])
