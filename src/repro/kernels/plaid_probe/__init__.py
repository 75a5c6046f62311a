from repro.kernels.plaid_probe.ops import plaid_probe_bag_scores  # noqa: F401
