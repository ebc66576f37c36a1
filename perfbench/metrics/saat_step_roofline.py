"""saat_step_roofline: the SAAT steps' share of the chip's memory roofline.

The least time the chip needs to move the bytes the served rows had to read
(perfbench.roofline.saat_step_bytes: each real row's postings up to rho,
its query and its answer) at the HBM peak, over all device busy time in the
traced window. Bound by memory: the step does no arithmetic to speak of. A
kernel or an XLA fusion doing the work reads the same.
"""


def read(run):
    if run.saat_bytes is None or run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.saat_bytes / run.peaks["hbm_bytes_per_s"] / run.trace.busy_s
