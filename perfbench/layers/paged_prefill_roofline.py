"""Roofline share of the paged prefill attention kernel: the least time
the chip could take for the work the served chunks need
(``counts/paged_prefill.py``: max of FLOPs over the bf16 peak and bytes
over HBM bandwidth, summed over layers) over the kernel's device time in
the trace."""
from perfbench import readers


def read(facts, cfg, mix):
    f, b = readers.prefill_kernel_work(facts, cfg, mix)
    return readers.roofline_pct(facts, f, b, readers.PREFILL_KERNEL)
