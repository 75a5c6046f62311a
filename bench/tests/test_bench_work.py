"""Operation and byte counts against plain loops at small shapes, and
the table of peaks."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import work  # noqa: E402


def loop_matmul_flops(m, k, n):
    """FLOPs of an [m, k] x [k, n] product, one multiply-add at a time."""
    f = 0
    for _ in range(m):
        for _ in range(n):
            f += 2 * k
    return f


def loop_encoder_flops(n_layers, d, ff, proj, L):
    f = 0
    for _ in range(n_layers):
        f += 4 * loop_matmul_flops(L, d, d)          # q, k, v, o
        f += loop_matmul_flops(L, d, L)              # scores
        f += loop_matmul_flops(L, L, d)              # weighted sum
        f += loop_matmul_flops(L, d, ff) + loop_matmul_flops(L, ff, d)
    return f + loop_matmul_flops(L, d, proj)


@pytest.mark.parametrize("lens", [[3], [4, 7], [1, 2, 5]])
def test_encoder_flops_match_a_loop(lens):
    n, d, ff, proj = 2, 8, 16, 4
    want = sum(loop_encoder_flops(n, d, ff, proj, L) for L in lens)
    assert work.encoder_flops(n, d, ff, proj, lens) == want


def test_maxsim_flops_match_a_loop():
    rng = np.random.default_rng(0)
    lq, dim, lens = 3, 5, [4, 2, 6]
    f = 0
    for L in lens:                       # every (query, doc) token pair
        for _ in range(lq):
            for _ in range(L):
                f += 2 * dim
    assert work.maxsim_flops(lq, dim, sum(lens)) == f
    q = rng.normal(size=(lq, dim))
    assert q.size * 2 * sum(lens) == f   # 2*dim per pair, lq*dim in q


def test_small_counts():
    assert work.centroid_score_flops(2, 3, 4, 5) == loop_matmul_flops(
        2 * 3, 5, 4)
    assert work.ward_gram_flops(6, 4) == loop_matmul_flops(6, 4, 6)
    assert work.codec_assign_flops(7, 3, 2) == loop_matmul_flops(7, 2, 3)


def test_packed_rerank_bytes_read_each_input_once():
    nq, lq, dim, bits, K, tokens = 2, 3, 32, 2, 8, 10
    flops, nbytes = work.packed_rerank_work(nq, lq, dim, bits, K, tokens)
    codes = tokens * (4 + dim * bits // 8)      # id + packed residual
    tables = K * dim * 4 + dim * 4 * 4          # centroids + bucket values
    queries = nq * lq * dim * 4
    assert nbytes == codes + tables + queries
    assert flops == work.maxsim_flops(lq, dim, tokens)


def test_probe_work():
    flops, nbytes = work.probe_work(2, 3, 4, 5, cand_tokens=7,
                                    stored_tokens=11)
    assert flops == work.centroid_score_flops(2, 3, 5, 4) + 2 * 3 * 7
    assert nbytes == 11 * 4 + 5 * 4 * 4 + 2 * 3 * 4 * 4


def test_roofline_share_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(200.0, 10.0, 4.0, peak) == (50.0, "compute")
    assert work.roofline_share(100.0, 30.0, 6.0, peak) == (50.0, "memory")


def test_peaks_table_is_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_every_peak_entry_names_its_source(tmp_path):
    table = json.loads(Path(work.PEAKS_FILE).read_text())
    for kind, p in table.items():
        assert p["source"] and p["bf16_flops_per_s"] > 0, kind
    (tmp_path / "p.json").write_text(json.dumps({"x": {"source": "s"}}))
    with pytest.raises(KeyError):
        work.peaks("TPU v5 lite", path=str(tmp_path / "p.json"))
