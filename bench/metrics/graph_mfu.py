"""The whole invocation's share of the chip's roofline: the least time
the application's work needs (FLOPs at the peak rate, or compulsory
bytes at the peak HBM bandwidth, whichever is longer) over the traced
time per invocation, averaged over the cell's chips."""


def read(r):
    if not r.peaks:
        return None
    w, p = r.work, r.peaks
    flops_s = w["flops"] / p[f"{w['flops_peak']}_flops_per_s"]
    bytes_s = w["bytes"] / p["hbm_bytes_per_s"]
    return 100.0 * max(flops_s, bytes_s) / r.chips / (r.run_ms / 1e3)
