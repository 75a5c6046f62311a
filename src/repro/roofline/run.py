"""Roofline rows for the retrieval kernels the served path runs.

    python -m repro.roofline.run --kernel packed_rerank --json packed.json
    python -m repro.roofline.run --kernel plaid_probe --json probe.json

``packed_rerank`` prices the fused compressed-domain rerank kernel
against the reconstruction baseline (``roofline/packed.py``);
``plaid_probe`` the device-resident candidate pipeline against the
host-gather baseline (``roofline/probe.py``). Both are analytic: op and
byte counts from the shapes, over the v5e peaks in ``roofline/hw.py``.
"""

import argparse
import json
import sys

from repro.roofline.analysis import HEADER


def run_packed_rerank(args) -> int:
    """``--kernel packed_rerank``: roofline rows for the fused
    compressed-domain rerank kernel vs the reconstruction baseline."""
    from repro.roofline.packed import packed_rerank_report
    shape = None
    if args.rerank_shape:
        keys = ("nq", "lq", "s", "ld", "dim", "k_centroids")
        vals = [int(v) for v in args.rerank_shape.split(",")]
        shape = dict(zip(keys, vals))
    bits = tuple(int(b) for b in args.bits.split(",") if b)
    report = packed_rerank_report(shape, bits_list=bits)
    print(HEADER, flush=True)
    for row in report["rows"]:
        print(row.pop("terms").row(), flush=True)
    for row in report["rows"]:
        if row["bits"] is not None:
            print(f"  bits={row['bits']}: "
                  f"{row['doc_bytes_per_token']} B/token vs "
                  f"{report['rows'][0]['doc_bytes_per_token']} B/token "
                  f"recon ({row['doc_bytes_ratio_vs_recon']:.1f}x), "
                  f"stream ratio {row['bytes_ratio_vs_recon']:.1f}x")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1)
    return 0


def run_plaid_probe(args) -> int:
    """``--kernel plaid_probe``: roofline rows for the device-resident
    candidate pipeline vs the host-gather (PCIe hop) baseline."""
    from repro.roofline.probe import plaid_probe_report
    shape = None
    if args.probe_shape:
        keys = ("nq", "lq", "k_centroids", "nprobe", "lmax", "c", "ld",
                "dim")
        vals = [int(v) for v in args.probe_shape.split(",")]
        shape = dict(zip(keys, vals))
    report = plaid_probe_report(shape)
    print(HEADER, flush=True)
    for row in report["rows"]:
        print(row.pop("terms").row(), flush=True)
    host, dev = report["rows"]
    print(f"  host hop: {host['host_hop_bytes']} B "
          f"({host['host_hop_s'] * 1e6:.1f} us PCIe) per batch; "
          f"device fused total {dev['total_s'] * 1e6:.1f} us vs host "
          f"{host['total_s'] * 1e6:.1f} us "
          f"({dev['speedup_vs_host']:.2f}x)")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", required=True,
                    choices=("packed_rerank", "plaid_probe"),
                    help="the kernel to price")
    ap.add_argument("--bits", default="2,4",
                    help="packed_rerank: codec widths to price")
    ap.add_argument("--rerank-shape", default=None,
                    help="packed_rerank: nq,lq,s,ld,dim,k_centroids")
    ap.add_argument("--probe-shape", default=None,
                    help="plaid_probe: nq,lq,k_centroids,nprobe,lmax,"
                         "c,ld,dim")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    if args.kernel == "packed_rerank":
        return run_packed_rerank(args)
    return run_plaid_probe(args)


if __name__ == "__main__":
    sys.exit(main())
