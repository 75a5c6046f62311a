"""Paper Table 3: vector count & index size vs pooling factor.

Dense single-vector (16-bit HNSW) vs PLAID-indexed ColBERT at pooling
factors 1/2/3/4/6, on the trec-covid analogue at the encoder's doc_maxlen
(paper: 256-token truncation; our bench encoder: 128). Footprint numbers
come straight from the ``QualitySweep`` cells (built through the
``repro.Retriever`` facade — no direct Indexer calls), so the size table
and the quality tables describe the very same indexes. Lands in the
``table3`` section of ``BENCH_quality.json``.
"""
from __future__ import annotations

from benchmarks.common import bench_encoder
from repro.eval import (BENCH_QUALITY_FILE, QualitySweep,
                        synthetic_dataset, write_bench_section)

FACTORS = (1, 2, 3, 4, 6)
BACKEND = "plaid"
BITS = 2


def run(verbose: bool = True, out: str = BENCH_QUALITY_FILE):
    params, cfg = bench_encoder(verbose=verbose)
    ds = synthetic_dataset("trec-covid", vocab_size=cfg.trunk.vocab_size,
                           doc_maxlen=cfg.doc_maxlen - 2,
                           query_maxlen=cfg.query_maxlen - 2,
                           n_docs=300, n_queries=16)
    rep = QualitySweep(params, cfg, ds, methods=("ward",),
                       factors=FACTORS, backends=(BACKEND,),
                       quant_bits=(BITS,), metrics=("ndcg@10",)).run()

    print("\nTable 3 — vector count & index size")
    # dense single-vector baseline: one 16-bit vector per doc in HNSW
    dense_bytes = ds.n_docs * cfg.proj_dim * 2
    print(f"{'16-bit dense single-vector':32s} {ds.n_docs:>9d} vecs "
          f"{dense_bytes/2**20:8.2f} MiB")

    sizes = {"dense_bytes": dense_bytes}
    for factor in FACTORS:
        c = rep.cell(BACKEND, "ward", factor, BITS)
        label = (f"{BITS}-bit PLAID (no pooling)" if factor == 1
                 else f"{BITS}-bit PLAID pool {factor}")
        print(f"{label:32s} {c.n_vectors:>9d} vecs "
              f"{c.index_bytes/2**20:8.2f} MiB "
              f"({c.vector_reduction:5.1%} fewer vectors)")
        sizes[str(factor)] = {"n_vectors": c.n_vectors,
                              "index_bytes": c.index_bytes,
                              "vector_reduction": c.vector_reduction}
    write_bench_section(out, "table3",
                        {"report": rep, "sizes": sizes,
                         "backend": BACKEND, "quant_bits": BITS})
    return {"report": rep, "sizes": sizes}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
