"""Host spans at the boundary of each layer, in the profiler's own trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: about a
microsecond when no profile is being taken; while one is, a host event
on the calling thread, on the clock the device's ops share. Args are
ints or short strings the host already holds. A number known only at
the span's end (bytes fetched, time waited) is added with
``set_metadata`` on the object the ``with`` statement binds. No span
reads a device value or waits on one.

Spans of one build batch share the arg ``batch`` (the indexer's encode
batch number), spans of one shard the arg ``shard``, spans of one
served microbatch the arg ``batch`` (the engine's batch number). Byte
args count the numpy operands a call copies to (``h2d_bytes``) or from
(``d2h_bytes``) the device, where it copies them.
"""
from __future__ import annotations

import jax
import numpy as np

# build thread (retrieval/indexer.py, core/pooling.py)
INDEXER_INPUT = "repro.indexer.input"            # batch
INDEXER_ENCODE = "repro.indexer.encode"          # batch, docs, h2d_bytes,
#                                                  tokens, valid_tokens
INDEXER_POOL = "repro.indexer.pool"              # batch, n_max, block_b
INDEXER_FETCH = "repro.indexer.fetch"            # batch, d2h_bytes
INDEXER_FLUSH_WAIT = "repro.indexer.flush_wait"  # batch, shard, wait_us
# flush thread
INDEXER_SHARD = "repro.indexer.shard"            # shard, docs, vectors
PLAID_ADD = "repro.plaid.add"                    # h2d_bytes, d2h_bytes
INDEXER_SHARD_SAVE = "repro.indexer.shard_save"  # shard
INDEXER_SHARD_REOPEN = "repro.indexer.shard_reopen"  # shard
# serving engine (launch/engine.py): batcher thread, then a search lane
ENGINE_ENCODE = "repro.engine.encode"            # batch, n, bucket, reason
ENGINE_SEARCH = "repro.engine.search"            # batch, replica,
#                                                  staged_wait_us
ENGINE_RESOLVE = "repro.engine.resolve"          # batch
# query encoder (retrieval/searcher.py)
ENCODER_QUERIES = "repro.encoder.queries"        # h2d_bytes, d2h_bytes
# PLAID search (core/plaid.py, core/maxsim.py)
PLAID_CANDIDATES = "repro.plaid.candidates"      # path, fallback,
#                                                  h2d_bytes, d2h_bytes
PLAID_RERANK = "repro.plaid.rerank"              # h2d_bytes
PLAID_TOPK = "repro.plaid.topk"                  # d2h_bytes


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of the constants above)."""
    return jax.profiler.TraceAnnotation(name, **args)


def host_nbytes(*arrays) -> int:
    """Bytes of the operands that are host (numpy) arrays: what passing
    them to a device computation copies over."""
    return sum(int(a.nbytes) for a in arrays if isinstance(a, np.ndarray))
