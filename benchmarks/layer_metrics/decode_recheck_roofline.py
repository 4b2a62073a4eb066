"""ops/ kernels: the fused RS decode + re-encode + Merkle-roots
program's share of the chip's roofline over the traced part of the
window: its work reckoned from the tally's device bytes
(benchmarks/work_erasure.py: the matrix products against the bf16 peak,
the bytes read, written and hashed against the HBM bandwidth, the
larger time) over its device seconds."""

from benchmarks.layer_metrics._erasure import roofline
from benchmarks.work_erasure import decode_recheck_work

PROGRAMS = ("jit__decode_recheck_kernel",)


def read(run):
    return roofline(run, PROGRAMS, decode_recheck_work)
