"""Roofline machinery: the HLO collective-bytes and dot-flops parsers,
and the three roofline terms."""
import jax
import pytest

from repro.roofline.analysis import (RooflineTerms,
                                     collective_bytes_from_hlo)


def test_collective_parser_on_real_hlo():
    """psum under shard_map produces a real all-reduce in the HLO."""
    import os
    if jax.device_count() < 2:
        # single-device: craft HLO text instead
        hlo = """
  %x = f32[1024,256] all-reduce(f32[1024,256] %p), replica_groups={}
  %y = bf16[512]{0} all-gather(bf16[256]{0} %q), dimensions={0}
  %z = f32[16,16] add(f32[16,16] %a, f32[16,16] %b)
"""
        out = collective_bytes_from_hlo(hlo)
        assert out["by_op"]["all-reduce"]["bytes"] == 1024 * 256 * 4
        assert out["by_op"]["all-gather"]["bytes"] == 512 * 2
        assert out["total"] == 1024 * 256 * 4 + 1024
        return


def test_collective_parser_ignores_non_collectives():
    hlo = "%z = f32[64,64] dot(f32[64,64] %a, f32[64,64] %b)"
    assert collective_bytes_from_hlo(hlo)["total"] == 0


def test_roofline_terms_bottleneck():
    t = RooflineTerms(arch="a", cell="c", mesh="16x16",
                      flops=197e12, hlo_bytes=819e9 * 2,
                      collective_bytes=50e9 * 0.5, model_flops=98.5e12)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(2.0)
    assert t.collective_s == pytest.approx(0.5)
    assert t.bottleneck == "memory"
    assert t.useful_flops_frac == pytest.approx(0.5)
    assert t.mfu == pytest.approx(0.25)   # (0.5s of model flops) / 2s


def test_dot_flops_parser():
    from repro.roofline.hlo_flops import dot_flops_in_hlo
    hlo = ("%d = f32[128,64] dot(f32[128,32] %a, f32[32,64] %b), "
           "lhs_contracting_dims={1}, rhs_contracting_dims={0}")
    out = dot_flops_in_hlo(hlo)
    assert out["total"] == 2 * 128 * 64 * 32
