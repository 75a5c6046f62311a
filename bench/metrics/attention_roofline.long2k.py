"""Share (%) of its roofline that the doc encoder's attention path
reaches: scores and weighted sums of the window's docs on their valid
keys in global layers and on the band in local ones (``work_band``),
q, k, v read and the output written once a layer, over the device time
of the ops under ``encoder/attention/global`` and ``.../local``."""
from bench import spans, work_band


def read(x):
    ev = spans.events()
    lens = x.get("doc_lens")
    if ev is None or lens is None or x["peak"] is None:
        return None
    t = sum(spans.scope_time_ns(ev, "encoder/attention/" + k) or 0.0
            for k in ("global", "local")) * 1e-9
    if t <= 0:
        return None
    trunk = x["model"]["trunk"]
    share, _ = x["work"].roofline_share(
        work_band.attention_flops(trunk, lens),
        work_band.attention_bytes(trunk, lens), t, x["peak"])
    return share
