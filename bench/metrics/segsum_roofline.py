"""The blocked segment-sum kernel's share of its roofline (%): the least
time its calls need at the chip's HBM bandwidth (``bench.work.segsum_bytes``
over the slot mask and segment ids the engine hands to
``segment_sum_arrays``) over the summed device time of its calls in the
traced window."""

from bench import work


def read(run):
    if run.reduced is None:
        return None
    return work.roofline_pct(run.reduced.kernels, run.shapes, "segsum", run.peaks["hbm_bytes_per_s"])
