"""The h-index kernel's share of its roofline (%): the least time its
calls need at the chip's HBM bandwidth (``bench.work.hindex_bytes`` over
the ELL buckets the engine hands to ``hindex_rows``) over the summed
device time of its calls in the traced window."""

from bench import work


def read(run):
    if run.reduced is None:
        return None
    return work.roofline_pct(run.reduced.kernels, run.shapes, "hindex", run.peaks["hbm_bytes_per_s"])
