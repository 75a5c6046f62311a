"""The encoder on the CPU: its outputs pinned bit for bit, ColBERTv2's
against the plain reference (``bench/refs/colbert.py``: float32 HIGHEST,
no kernels), and the benchmark's configuration files against the
program's config dataclasses.

Three batches run through both smoke configurations: every row at the
doc cap (skiplist ids included), a ragged batch with an empty row, and a
short one with a hole."""
import dataclasses
import hashlib
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.colbertv2 import SMOKE as COLBERT_SMOKE
from repro.configs.moderncolbert import SMOKE as MODERN_SMOKE
from repro.models.colbert import encode_docs, encode_queries, init_colbert
from repro.models.transformer import forward

ROOT = Path(__file__).resolve().parents[1]
CONFIGS = {"colbertv2": COLBERT_SMOKE, "gte-moderncolbert": MODERN_SMOKE}
BATCHES = ("cap", "ragged", "short")


def _load(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _batch(name: str, cfg) -> np.ndarray:
    """Raw token ids of one test batch."""
    if name == "cap":
        return np.random.default_rng(11).integers(
            8, 1000, (4, cfg.doc_maxlen - 2)).astype(np.int32)
    if name == "ragged":
        toks = np.random.default_rng(7).integers(24, 1024, (4, 46)).astype(
            np.int32)
        toks[1, 30:] = 0
        toks[3] = 0
        return toks
    toks = np.random.default_rng(13).integers(24, 1000, (3, 9)).astype(
        np.int32)
    toks[0, 4] = 0
    toks[2, 6:] = 0
    return toks


@pytest.fixture(scope="module")
def params():
    return {name: init_colbert(jax.random.PRNGKey(7), cfg)
            for name, cfg in CONFIGS.items()}


# sha256 (first 16 hex digits) of the trunk's hidden states, the doc
# vectors and the query vectors on the CPU, computed by the program at
# commit b049404; a digest that moves is a change of behaviour
PINNED = {
    ("colbertv2", "cap"):
        ("1b08ce9f116acd0a", "7399d674bf5bbe11", "a6a9452363c88419"),
    ("colbertv2", "ragged"):
        ("172306cd977c29e7", "1d4805e86ea2ef2e", "2433081ad4ab2b5f"),
    ("colbertv2", "short"):
        ("b2ec2b7cf9cfaab5", "170cd8a33d85b350", "c5e44765202e1d4a"),
    ("gte-moderncolbert", "cap"):
        ("8fae9ed0595ba22d", "16153ce03eea0d24", "eabce66a07ae9c05"),
    ("gte-moderncolbert", "ragged"):
        ("3d034ecf7a5a29a2", "d6c2008a9cb48c26", "59413ebfa94d7235"),
    ("gte-moderncolbert", "short"):
        ("48598ef62c1c8429", "ef5756f03ba48705", "ec48d4b45528b442"),
}
OUTPUTS = ("trunk", "docs", "queries")


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_encoder_bitwise_unchanged(params, config, output, batch):
    cfg, p = CONFIGS[config], params[config]
    toks = _batch(batch, cfg)
    if output == "trunk":
        got = jax.jit(lambda p, t, m: forward(p, t, cfg.trunk, pad_mask=m))(
            p["trunk"], jnp.asarray(toks), jnp.asarray(toks != 0))
    elif output == "docs":
        got, _ = encode_docs(p, jnp.asarray(toks), cfg)
    else:
        got, _ = encode_queries(p, jnp.asarray(toks[:, :6]), cfg)
    digest = hashlib.sha256(np.asarray(got, np.float32).tobytes())
    assert (digest.hexdigest()[:16]
            == PINNED[config, batch][OUTPUTS.index(output)])


@pytest.fixture(scope="module")
def colbert_ref():
    return _load("bench/refs/colbert.py", "colbert_ref")


def _worst_cos_gap(got, want) -> float:
    return max(float(1.0 - (np.asarray(g, np.float64) * w).sum(-1).min())
               for g, w in zip(got, want))


# the tolerances of tests/test_moderncolbert.py: f32 compute differs
# from the reference only in summation order; bf16 rounds every matmul
# operand to 8 bits of mantissa
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1e-2)])
@pytest.mark.parametrize("output", ["docs", "queries"])
def test_colbertv2_matches_reference(params, colbert_ref, output, dtype,
                                     tol, batch):
    cfg = dataclasses.replace(COLBERT_SMOKE, trunk=dataclasses.replace(
        COLBERT_SMOKE.trunk, dtype=dtype))
    p, m = params["colbertv2"], dataclasses.asdict(cfg)
    toks = _batch(batch, cfg)
    if output == "docs":
        v, emit = encode_docs(p, jnp.asarray(toks), cfg)
        _, want_emit = colbert_ref.doc_input(toks, cfg.doc_maxlen)
        np.testing.assert_array_equal(np.asarray(emit), want_emit)
        got = [np.asarray(v)[i][want_emit[i]] for i in range(len(toks))]
        want = colbert_ref.encode_docs(p, m, toks)
    else:
        got, _ = encode_queries(p, jnp.asarray(toks[:, :6]), cfg)
        want = colbert_ref.encode_queries(p, m, toks[:, :6])
    assert _worst_cos_gap(got, want) < tol


@pytest.mark.parametrize("path", [
    "bench/configs/colbertv2.json", "bench/configs/gte-moderncolbert.json",
    "bench/tests/smoke/smoke.json", "bench/tests/smoke/smoke-modern.json"])
def test_bench_config_builds_program_config(path):
    """``bench/model.program_config`` accepts each of the benchmark's
    configuration files, and every value they set survives."""
    model = json.loads((ROOT / path).read_text())["model"]
    cfg = _load("bench/model.py", "bench_model").program_config(
        {"model": model})
    for key, value in model["trunk"].items():
        assert getattr(cfg.trunk, key) == value, key
    for key, value in model.items():
        if key != "trunk":
            assert getattr(cfg, key) == value, key
    assert cfg.trunk.d_head * cfg.trunk.n_heads == cfg.trunk.d_model
    for bad in ({"causal": True}, {"attn_shard": "sequence"}):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg.trunk, **bad)
