"""GTE-ModernColBERT at a CPU size (4 layers: global, local, local and a
trailing global; d 64, 4 heads, window 8, docs of 40 tokens with
padding) on seeded random weights, against the plain reference
(``bench/refs/moderncolbert.py``: float32 HIGHEST, dense scores with an
explicit band mask); the masked attention kernel against the dense
masked path; and the build and search path end to end. The encoder's
outputs are pinned bit for bit in ``tests/test_encoder.py``."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.moderncolbert import SMOKE
from repro.kernels.flash_attention import masked
from repro.models import attention
from repro.models.attention import _full_attn
from repro.models.colbert import encode_docs, encode_queries, init_colbert

REF_PATH = Path(__file__).resolve().parents[1] / "bench/refs/moderncolbert.py"


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("moderncolbert_ref",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model(cfg) -> dict:
    """The configuration as the reference reads it."""
    return dataclasses.asdict(cfg)


def _f32(cfg):
    return dataclasses.replace(cfg, trunk=dataclasses.replace(
        cfg.trunk, dtype="float32"))


@pytest.fixture(scope="module")
def params():
    return init_colbert(jax.random.PRNGKey(3), SMOKE)


@pytest.fixture(scope="module")
def docs():
    """Bodies of 38 tokens: full, half, short, a hole, fully padded."""
    rng = np.random.default_rng(4)
    t = rng.integers(24, 1000, (5, 38)).astype(np.int32)
    t[1, 19:] = 0
    t[2, 5:] = 0
    t[3, 10] = 0
    t[4] = 0
    t[0, 3] = 12                          # a skiplist (punctuation) id
    return t


def _worst_cos_gap(got, want) -> float:
    return max(float(1.0 - (np.asarray(g, np.float64) * w).sum(-1).min())
               for g, w in zip(got, want))


def _program_docs(params, cfg, toks, ref):
    v, emit = encode_docs(params, jnp.asarray(toks), cfg)
    _, want_emit = ref.doc_input(toks, _model(cfg))
    np.testing.assert_array_equal(np.asarray(emit), want_emit)
    return [np.asarray(v)[i][want_emit[i]] for i in range(len(toks))]


# f32 compute: the program and the reference differ only in summation
# order (1e-5); bf16 compute rounds every matmul operand to 8 bits of
# mantissa through 4 layers (1e-2 at this size)
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 1e-2)])
def test_doc_vectors_match_reference(params, docs, ref, dtype, tol):
    cfg = SMOKE if dtype == "bfloat16" else _f32(SMOKE)
    got = _program_docs(params, cfg, docs, ref)
    want = ref.encode_docs(params, _model(cfg), docs)
    assert _worst_cos_gap(got, want) < tol


def test_query_vectors_match_reference(params, docs, ref):
    cfg = _f32(SMOKE)
    got, _ = encode_queries(params, jnp.asarray(docs[:, :4]), cfg)
    want = ref.encode_queries(params, _model(cfg), docs[:, :4])
    assert _worst_cos_gap(np.asarray(got), want) < 1e-5


def test_doc_vectors_through_the_masked_kernel(params, docs, ref,
                                               monkeypatch):
    """The chip's path (the masked kernel, interpreted) at S = 40 > the
    smoke threshold of 16 matches the reference too."""
    calls, kernel = [], masked.masked_attention

    def interpreted(*a, **kw):
        calls.append(kw["window"])
        return kernel(*a, interpret=True, **kw)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(masked, "masked_attention", interpreted)
    # a config of its own name: encode_docs traces afresh
    cfg = dataclasses.replace(_f32(SMOKE), name="smoke-kernel")
    got = _program_docs(params, cfg, docs, ref)
    assert sorted(set(calls)) == [0, 8]
    want = ref.encode_docs(params, _model(cfg), docs)
    assert _worst_cos_gap(got, want) < 1e-5


def test_the_layer_kinds_alternate():
    kinds = [SMOKE.trunk.is_global(i) for i in range(4)]
    assert kinds == [True, False, False, True]
    from repro.configs.moderncolbert import CONFIG
    glob = [i for i in range(22) if CONFIG.trunk.is_global(i)]
    assert glob == [0, 3, 6, 9, 12, 15, 18, 21]


@pytest.mark.parametrize("S", [40, 300])
@pytest.mark.parametrize("window", [0, 8, 64])
def test_masked_attention_matches_dense(S, window):
    """Valid query rows agree with the dense masked path (band edges
    included); docs with no valid key come out zero."""
    rng = np.random.default_rng(S + window)
    B, H, dh = 4, 2, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.bfloat16)
               for _ in range(3))
    mask = np.ones((B, S), bool)
    mask[1, S // 3:] = False              # short doc
    mask[2, 7] = mask[2, S - 5] = False   # holes
    mask[3] = False                       # fully padded
    got = np.asarray(masked.masked_attention(
        q, k, v, jnp.asarray(mask), window=window, interpret=True),
        np.float32)
    want = np.asarray(_full_attn(q, k, v, pad_mask=jnp.asarray(mask),
                                 window=window), np.float32)
    last = [S, S // 3, S, 0]
    for b in range(B):
        np.testing.assert_allclose(got[b, :last[b]], want[b, :last[b]],
                                   atol=2e-2, rtol=2e-2)
    assert not got[3].any()


def test_band_span_holds_every_band():
    for S in (128, 256, 2048):
        width, starts = masked.band_span(S, 64)
        for iq, k0 in enumerate(starts):
            q0 = iq * masked.LOCAL_BQ
            lo, hi = max(q0 - 64, 0), min(q0 + masked.LOCAL_BQ + 63, S - 1)
            assert k0 <= lo and hi < k0 + width and k0 % masked.ALIGN == 0


def test_build_then_search_is_exact_maxsim(params):
    """Streaming ``Retriever.build`` (flat backend) then ``search``: the
    top-k equals exact MaxSim over the stored vectors; the serving
    engine answers the same."""
    import repro
    from repro.core.spec import (IndexSpec, PoolingSpec, RetrieverSpec,
                                 ServeSpec, ShardSpec)
    from repro.retrieval.indexer import Indexer
    rng = np.random.default_rng(5)
    toks = rng.integers(24, 1000, (24, 38)).astype(np.int32)
    toks[::3, 20:] = 0
    spec = RetrieverSpec(pooling=PoolingSpec(method="ward", factor=2),
                         index=IndexSpec.from_config(SMOKE, backend="flat"),
                         shard=ShardSpec(shard_max_vectors=100))
    r = repro.Retriever.build(params, SMOKE, (toks[i:i + 8]
                                              for i in range(0, 24, 8)),
                              spec, encode_batch=8)
    assert r.index.n_docs == 24 and r.index.n_shards > 1
    stored = Indexer(params, SMOKE, pooling_spec=spec.pooling,
                     index_spec=spec.index,
                     encode_batch=8).encode_and_pool(toks)
    queries = toks[[0, 5, 13], 4:9]
    qv, _ = encode_queries(params, jnp.asarray(queries), SMOKE)
    scores = np.array([[(np.asarray(q) @ d.T).max(1).sum() for d in stored]
                       for q in np.asarray(qv)])
    want = np.argsort(-scores, axis=1, kind="stable")[:, :5]
    _, ids = r.search(queries, k=5)
    np.testing.assert_array_equal(np.asarray(ids), want)
    engine = r.serve(ServeSpec(max_batch=4, max_wait_ms=1.0, k=5))
    engine.start()
    try:
        futs = [engine.submit(q[None]) for q in queries]
        got = np.concatenate([f.result(60.0)[1] for f in futs])
    finally:
        engine.stop()
    np.testing.assert_array_equal(got, want)
