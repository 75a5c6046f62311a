"""Host spans of the program's layers (``repro.obs``) in a profiler
trace, on the CPU at the smoke configuration: a small streaming build
and a few served requests run traced; every span appears with its args,
a batch's spans share its number, the byte args equal the ``nbytes``
the shapes give, the candidate span names its path and why the host
path served, and the results are bitwise those of an untraced run."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs import get_smoke_config
from repro.core import plaid
from repro.core.spec import IndexSpec, PoolingSpec
from repro.launch.engine import ServingEngine
from repro.models.colbert import init_colbert
from repro.retrieval.indexer import Indexer
from repro.retrieval.searcher import Searcher

B = 16                  # encode batch and stream batch: no padded rows
N_BATCHES = 6
BUILD_ARGS = {
    obs.INDEXER_INPUT: {"batch"},
    obs.INDEXER_ENCODE: {"batch", "docs", "h2d_bytes", "tokens",
                         "valid_tokens"},
    obs.INDEXER_POOL: {"batch", "n_max", "block_b"},
    obs.INDEXER_FETCH: {"batch", "d2h_bytes"},
    obs.INDEXER_FLUSH_WAIT: {"batch", "shard", "wait_us"},
    obs.INDEXER_SHARD: {"shard", "docs", "vectors"},
    obs.PLAID_ADD: {"h2d_bytes", "d2h_bytes"},
    obs.INDEXER_SHARD_SAVE: {"shard"},
    obs.INDEXER_SHARD_REOPEN: {"shard"},
}
SERVE_ARGS = {
    obs.ENGINE_ENCODE: {"batch", "n", "bucket", "reason"},
    obs.ENCODER_QUERIES: {"h2d_bytes", "d2h_bytes"},
    obs.ENGINE_SEARCH: {"batch", "replica", "staged_wait_us"},
    obs.PLAID_CANDIDATES: {"path", "h2d_bytes", "d2h_bytes"},
    obs.PLAID_RERANK: {"h2d_bytes"},
    obs.PLAID_TOPK: {"d2h_bytes"},
    obs.ENGINE_RESOLVE: {"batch"},
}


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("colbertv2")
    params = init_colbert(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(30, cfg.trunk.vocab_size,
                        size=(B * N_BATCHES, cfg.doc_maxlen - 2)
                        ).astype(np.int32)
    return cfg, params, toks


def traced(fn, path):
    """(fn's result, [(name, args)] of the ``repro.`` spans it ran, in
    start order)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    [f] = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                    recursive=True)
    spans = [(e.start_ns, e.name, dict(e.stats))
             for p in ProfileData.from_file(f).planes
             for line in p.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, [(n, a) for _, n, a in sorted(spans, key=lambda s: s[0])]


def named(spans, name):
    return [a for n, a in spans if n == name]


def indexer(cfg, params, **kw):
    spec = IndexSpec.from_config(cfg, backend="plaid", ndocs=32, **kw)
    return Indexer(params, cfg, encode_batch=B, index_spec=spec,
                   pooling_spec=PoolingSpec(method="ward", factor=2))


def build(cfg, params, toks, out_dir):
    return indexer(cfg, params).build_streaming(
        (toks[i:i + B] for i in range(0, len(toks), B)),
        shard_max_vectors=2 * B * 12, out_dir=str(out_dir))


@pytest.fixture(scope="module")
def built(model, tmp_path_factory):
    cfg, params, toks = model
    tmp = tmp_path_factory.mktemp("spans_build")
    plain = build(cfg, params, toks, tmp / "plain")
    (index, stats), spans = traced(
        lambda: build(cfg, params, toks, tmp / "traced"), tmp / "trace")
    return index, stats, spans, plain


def test_build_spans_carry_their_args(built):
    _, _, spans, _ = built
    for name, args in BUILD_ARGS.items():
        got = named(spans, name)
        assert got, f"no {name} span"
        for a in got:
            assert args <= set(a), (name, a)


def test_build_spans_of_a_batch_share_its_number(built):
    index, _, spans, _ = built
    batches = list(range(N_BATCHES))
    for name in (obs.INDEXER_ENCODE, obs.INDEXER_POOL, obs.INDEXER_FETCH):
        assert [a["batch"] for a in named(spans, name)] == batches, name
    # one wait per batch, and one more that finds the stream's end
    assert ([a["batch"] for a in named(spans, obs.INDEXER_INPUT)]
            == batches + [N_BATCHES])
    shards = list(range(index.n_shards))
    for name in (obs.INDEXER_FLUSH_WAIT, obs.INDEXER_SHARD,
                 obs.INDEXER_SHARD_SAVE, obs.INDEXER_SHARD_REOPEN):
        assert sorted(a["shard"] for a in named(spans, name)) == shards


def test_build_token_and_pool_args(built, model):
    """``tokens`` counts the encoder's slots, ``valid_tokens`` the
    [CLS][D] markers and the body tokens that fit; the pool span names
    the doc width and the Ward kernel's docs per program at it."""
    from repro.kernels.ward_pool.ops import ward_block_b
    cfg, _, toks = model
    L = cfg.doc_maxlen
    enc = named(built[2], obs.INDEXER_ENCODE)
    for b, a in enumerate(enc):
        body = toks[b * B:(b + 1) * B, :L - 2]
        assert a["tokens"] == B * L
        assert a["valid_tokens"] == 2 * B + np.count_nonzero(body)
    for a in named(built[2], obs.INDEXER_POOL):
        assert a["n_max"] == L and a["block_b"] == ward_block_b(L) == 8


def test_layer_kinds_land_under_their_scopes():
    """An alternating model's attention cores carry the scopes
    ``encoder/attention/global`` and ``encoder/attention/local``; a
    model of global layers only carries neither."""
    import re
    import jax.numpy as jnp
    from repro.models.colbert import encode_docs

    def scopes(cfg):
        p = jax.eval_shape(lambda k: init_colbert(k, cfg),
                           jax.random.PRNGKey(0))
        toks = jax.ShapeDtypeStruct((2, cfg.doc_maxlen - 2), jnp.int32)
        text = encode_docs.lower(p, toks, cfg).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    names = scopes(get_smoke_config("gte-moderncolbert"))
    for kind in ("global", "local"):
        # a scan's ``while/body`` may sit between the components
        under = re.compile(rf"encoder/(.*/)?attention/{kind}/")
        assert any(under.search(n) for n in names), kind
    plain = scopes(get_smoke_config("colbertv2"))
    assert any(re.search(r"encoder/(.*/)?attention/", n) for n in plain)
    assert not any("/global/" in n or "/local/" in n for n in plain)


def test_build_byte_args_equal_the_shapes(built, model):
    cfg, _, toks = model
    index, stats, spans, _ = built
    dim, n = cfg.proj_dim, int(stats.n_vectors_stored)
    enc = named(spans, obs.INDEXER_ENCODE)
    assert all(a["h2d_bytes"] == B * toks.shape[1] * 4 and a["docs"] == B
               for a in enc)
    # pooled rows f32, one int32 count per doc, one raw count per batch
    assert (sum(a["d2h_bytes"] for a in named(spans, obs.INDEXER_FETCH))
            == n * dim * 4 + len(toks) * 4 + N_BATCHES * 4)
    words = dim * cfg.quant_bits // 32
    first = named(spans, obs.INDEXER_SHARD)[0]["vectors"]
    add = named(spans, obs.PLAID_ADD)
    # each shard's vectors go over once for the codec encode, the first
    # shard's twice more to train it; ids and packed words come back
    assert (sum(a["h2d_bytes"] for a in add)
            == n * dim * 4 + 2 * first * dim * 4)
    assert sum(a["d2h_bytes"] for a in add) == n * (4 + words * 4)
    assert sum(a["vectors"] for a in named(spans, obs.INDEXER_SHARD)) == n
    waits = sum(a["wait_us"] for a in named(spans, obs.INDEXER_FLUSH_WAIT))
    assert abs(stats.flush_wait_s - waits * 1e-6) <= index.n_shards * 1e-6


def test_build_is_bitwise_the_same_traced(built):
    index, stats, _, (plain, plain_stats) = built
    assert stats.n_vectors_stored == plain_stats.n_vectors_stored
    for a, b in zip(index.shards, plain.shards):
        np.testing.assert_array_equal(a._plaid.codes, b._plaid.codes)
        np.testing.assert_array_equal(a._plaid.assignments,
                                      b._plaid.assignments)


@pytest.fixture(scope="module")
def searcher(model):
    cfg, params, toks = model
    index, _ = indexer(cfg, params).build(toks)
    return Searcher(params, cfg, index)


SIZES = (1, 3, 2)           # one request per batch: buckets 1, 4, 2
K = 5


def serve(searcher, qtoks):
    """Each request alone in its batch, so batch i holds request i."""
    with ServingEngine(searcher, max_batch=4, max_wait_ms=1.0, k=K,
                       pipeline_depth=2) as eng:
        out, lo = [], 0
        for n in SIZES:
            out.append(eng.submit(qtoks[lo:lo + n]).result(timeout=60))
            lo += n
    return out


@pytest.fixture(scope="module")
def served(searcher, model, tmp_path_factory):
    cfg = model[0]
    rng = np.random.default_rng(1)
    qtoks = rng.integers(30, cfg.trunk.vocab_size,
                         size=(sum(SIZES), cfg.query_maxlen - 2)
                         ).astype(np.int32)
    plain = serve(searcher, qtoks)
    out, spans = traced(lambda: serve(searcher, qtoks),
                        tmp_path_factory.mktemp("spans_serve"))
    return out, spans, plain, qtoks


def test_serve_spans_carry_their_args(served):
    _, spans, _, _ = served
    for name, args in SERVE_ARGS.items():
        got = named(spans, name)
        assert got, f"no {name} span"
        for a in got:
            assert args <= set(a), (name, a)


def test_serve_spans_of_a_batch_share_its_number(served):
    _, spans, _, _ = served
    enc = named(spans, obs.ENGINE_ENCODE)
    assert [a["n"] for a in enc] == list(SIZES)
    assert [a["bucket"] for a in enc] == [1, 4, 2]
    nums = [a["batch"] for a in enc]
    assert len(set(nums)) == len(SIZES)
    for name in (obs.ENGINE_SEARCH, obs.ENGINE_RESOLVE):
        assert [a["batch"] for a in named(spans, name)] == nums, name
    assert all(a["staged_wait_us"] >= 0 and a["replica"] == 0
               for a in named(spans, obs.ENGINE_SEARCH))


def test_serve_byte_args_equal_the_shapes(served, model):
    cfg = model[0]
    _, spans, _, qtoks = served
    # from the first batch on: the engine's warm-up searches come before
    first = [n for n, _ in spans].index(obs.ENGINE_ENCODE)
    spans = spans[first:]
    lq, dim, L = cfg.query_maxlen, cfg.proj_dim, qtoks.shape[1]
    q = lq * dim * 4                        # one query's f32 vectors
    widths = [1, 4, 2]                      # encoder widths = buckets
    enc = named(spans, obs.ENCODER_QUERIES)
    assert [a["h2d_bytes"] for a in enc] == [w * L * 4 for w in widths]
    assert [a["d2h_bytes"] for a in enc] == [w * q for w in widths]
    cand = named(spans, obs.PLAID_CANDIDATES)
    assert all(a["path"] == "device" and a["scorer"] == "bag"
               for a in cand)
    # the batch's query vectors go over once, for the centroid scores:
    # the bag scorer (and, off TPU, the jnp stage 3) reads the scores,
    # not the vectors (centroids and live mask are resident)
    assert [a["h2d_bytes"] for a in cand] == [w * q for w in widths]
    assert [a["d2h_bytes"] for a in cand] == [0] * 3
    assert ([a["h2d_bytes"] for a in named(spans, obs.PLAID_RERANK)]
            == [w * q for w in widths])
    # top-k scores f32 and device-gathered ids int32
    assert ([a["d2h_bytes"] for a in named(spans, obs.PLAID_TOPK)]
            == [w * K * 8 for w in widths])


def test_serve_is_bitwise_the_same_traced(served):
    out, _, plain, _ = served
    for (S, I), (S0, I0) in zip(out, plain):
        assert np.array_equal(S, S0) and np.array_equal(I, I0)


@pytest.mark.parametrize("kernel,cap,path,fallback", [
    ("auto", None, "device", None),
    ("host", None, "host", "host_kernel"),
    ("auto", 0, "host", "gather_cap"),
])
def test_candidate_span_names_path_and_fallback(searcher, model, tmp_path,
                                                monkeypatch, kernel, cap,
                                                path, fallback):
    cfg = model[0]
    index = searcher.index
    qs = searcher.encode_queries(np.full((2, cfg.query_maxlen - 2), 40,
                                         np.int32))
    if cap is not None:
        monkeypatch.setattr(plaid, "_DEVICE_GATHER_CAP", cap)
    monkeypatch.setattr(index, "probe_kernel", kernel)
    _, spans = traced(lambda: index.search_batch(qs, k=K), tmp_path)
    [a] = named(spans, obs.PLAID_CANDIDATES)
    assert a["path"] == path and a.get("fallback") == fallback
    assert a.get("scorer") == ("bag" if path == "device" else None)


def test_candidate_span_names_negative_t_cs_fallback(searcher, model,
                                                     tmp_path, monkeypatch):
    """A negative prune threshold sends the batch to the host path (the
    bag scorer would let an absent centroid's 0 win), and the span says
    so."""
    cfg = model[0]
    index = searcher.index
    qs = searcher.encode_queries(np.full((2, cfg.query_maxlen - 2), 40,
                                         np.int32))
    monkeypatch.setattr(index, "t_cs", -0.1)
    _, spans = traced(lambda: index.search_batch(qs, k=K), tmp_path)
    [a] = named(spans, obs.PLAID_CANDIDATES)
    assert (a["path"], a["fallback"]) == ("host", "negative_t_cs")
    assert "scorer" not in a
