"""ops/ kernels: the RS-only programs' (a proposer's encode, the
batched encodes and decodes) share of the chip's roofline over the
traced part of the window, as decode_recheck_roofline reads the fused
program's.  The three batched entry points are jits of vmaps of the two
single kernels, so the profiler may name them after those."""

from benchmarks.layer_metrics._erasure import roofline
from benchmarks.work_erasure import rs256_work

PROGRAMS = (
    "jit__encode_kernel", "jit__encode_kernel_batch", "jit__decode_kernel",
    "jit__decode_kernel_shared", "jit__decode_kernel_batch",
)


def read(run):
    return roofline(run, PROGRAMS, rs256_work)
