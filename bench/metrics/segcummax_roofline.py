"""The segmented-cummax kernel's share of its roofline, in %.

The kernel's least traffic per call is one read and one write of its
(K, W) int32 matrix and one read of the W segment starts:
2*K*W*4 + W*4 bytes.  It does a handful of int32 compare/select/max
operations per element and step; the chip's int32 vector peak is not
published, so the roofline here is HBM bandwidth alone, and the share is
(bytes / peak bandwidth) / kernel time.  K and W are read from the
kernel's output shape in the trace; without a shape there is no number.
"""
import re

_SHAPE = re.compile(r"s32\[(\d+),(\d+)\]")


def kernel_bytes(K: int, W: int) -> int:
    return 2 * K * W * 4 + W * 4


def read(ctx):
    s = ctx["summary"]
    if s is None or s.kernel_calls == 0 or s.kernel_s <= 0:
        return None
    shapes = [_SHAPE.search(t) for t in s.kernel_shapes]
    if not shapes or not all(shapes):
        return None
    # one shape is recorded per fixpoint execution: every round of an
    # execution runs the kernel on the same (K, W)
    per_run = [kernel_bytes(int(m.group(1)), int(m.group(2)))
               for m in shapes]
    total = sum(per_run) / len(per_run) * s.kernel_calls
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / s.kernel_s
