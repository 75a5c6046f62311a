"""Docs per second at the window's median shard: the docs of one shard
over the median time from its first encode batch to the next shard's.
The build's tail (last flush, manifest) and a shard that a host stall
held up weigh nothing here, so this moves with the build's steady pace
where ``build_docs_per_s`` also carries the stalls."""
import numpy as np


def read(x):
    shards = x.get("shard_s", [])[:-1]     # the last holds the tail
    if not shards:
        return None
    return x["docs_per_shard"] / float(np.median(shards))
