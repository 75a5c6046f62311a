"""Kernel micro-benchmarks: wall-time of the jnp reference vs the Pallas
kernel in interpret mode is NOT meaningful on CPU (interpret mode is a
Python-level simulator), so this reports (a) the jnp reference wall time
as the CPU datapoint and (b) the kernel's VMEM working-set & arithmetic
intensity — the numbers that matter for the TPU target."""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.maxsim import maxsim_scores, maxsim_scores_blocked
from repro.roofline import hw


def _time(f, *args, n=2):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(f(*args))
    return (time.time() - t0) / n


def run(verbose: bool = True):
    rng = np.random.default_rng(0)
    print("\nKernel analysis (TPU v5e target)")
    rows = []
    for (nq, lq, nd, ld, dim, bq, bd) in [
            (16, 32, 2048, 256, 128, 8, 8),
            (32, 32, 8192, 256, 128, 8, 16)]:
        q = jnp.asarray(rng.normal(size=(nq, lq, dim)), jnp.float32)
        d = jnp.asarray(rng.normal(size=(nd, ld, dim)), jnp.float32)
        qm = jnp.ones((nq, lq), bool)
        dm = jnp.ones((nd, ld), bool)
        # blocked path: the big shapes would materialize a [Nq,Nd,Lq,Ld]
        # tensor (tens of GB) through the einsum reference
        t = _time(lambda a, b, c, e: maxsim_scores_blocked(
            a, b, c, e, block=512), q, qm, d, dm)
        flops = 2 * nq * lq * nd * ld * dim
        vmem = (bq * lq * dim + bd * ld * dim + bq * lq * bd * ld) * 4
        ai = flops / (q.nbytes + d.nbytes + nq * nd * 4)
        tpu_roof = flops / hw.PEAK_FLOPS_BF16
        print(f"maxsim q{nq}x{lq} d{nd}x{ld}: jnp-cpu {t*1e3:7.1f}ms | "
              f"kernel tile VMEM {vmem/2**20:5.2f}MiB, AI {ai:6.1f} "
              f"flop/B, v5e compute-roof {tpu_roof*1e6:6.1f}us")
        rows.append({"shape": (nq, lq, nd, ld, dim), "cpu_ms": t * 1e3,
                     "vmem_mb": vmem / 2**20, "ai": ai})
    rows += run_plaid_probe(rng)
    return rows


def run_plaid_probe(rng):
    """Doc-major centroid-interaction probe cell (kernels/plaid_probe):
    jnp reference wall time on CPU + the kernel tile's VMEM working set
    and arithmetic intensity for the TPU target."""
    from repro.kernels.plaid_probe.ops import plaid_probe_bag_scores

    rows = []
    for (nq, lq, k, n, bd) in [(8, 32, 256, 4096, 1024),
                               (32, 32, 256, 8192, 1024)]:
        cs = rng.normal(size=(nq, lq, k)).astype(np.float32)
        csp = jnp.asarray(np.where(cs >= 0.45, cs, 0.0))
        member = jnp.asarray((rng.random((k, n)) < 0.25).astype(np.float32))
        t = _time(lambda *a: plaid_probe_bag_scores(*a, impl="ref"),
                  csp, member)
        # per tile: resident pruned scores + member tile + output tile
        vmem = (nq * lq * k + k * bd + nq * bd) * 4
        flops = 2.0 * nq * lq * k * n
        ai = flops / (csp.nbytes + member.nbytes + nq * n * 4)
        print(f"plaid_probe q{nq}x{lq} K{k} docs{n}: "
              f"jnp-cpu {t*1e3:7.1f}ms | kernel tile VMEM "
              f"{vmem/2**20:5.2f}MiB, AI {ai:6.1f} flop/B")
        rows.append({"kernel": "plaid_probe", "shape": (nq, lq, k, n),
                     "cpu_ms": t * 1e3, "vmem_mb": vmem / 2**20, "ai": ai})
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
