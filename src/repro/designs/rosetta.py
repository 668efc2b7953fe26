"""Designs of the Rosetta HLS benchmark suite (Zhou et al., "Rosetta: A
Realistic High-Level Synthesis Benchmark Suite for Software Programmable
FPGAs", FPGA 2018; github.com/cornell-zhang/rosetta).

``optical_flow`` is the ``#pragma HLS dataflow`` top of the suite's
``optical_flow.cpp``: nine pipelined loops (II = 1) connected by fourteen
streams, over a 5-frame window of ``height`` x ``width`` frames (the
suite's own frames are 436 x 1,024).  Each process keeps the source's loop
bounds, guards and order of accesses within an iteration.  The design
language charges one cycle per access, so an iteration with several
accesses takes several cycles where HLS issues them in one; an iteration
that makes no access is a ``Delay(1)`` bubble.  The arithmetic is an
integer stand-in for the source's fixed-point filters: no control flow
depends on a value, so values cannot move timing.

The x/y gradient path (``gradient_xy_calc``) lags its input by 2 rows and
2 columns, the z path (``gradient_z_calc``) does not, and
``gradient_weight_y`` consumes both in step: the z path has to buffer
about ``2 * width + 2`` pixels.  The source sizes ``gradient_z`` at
``max_width * 4`` for that reason; a depth row that leaves the z path
(``gradient_z`` plus a frame stream) less room deadlocks.
"""
from __future__ import annotations

from ..core.program import Delay, Emit, Program, Read, Write

GRAD_WEIGHTS = (1, -8, 0, 8, -1)        # the source's 5-tap derivative
GRAD_FILTER = (1, 6, 15, 20, 15, 6, 1)  # integer stand-in, 7 taps
TENSOR_FILTER = (1, 2, 1)               # integer stand-in, 3 taps
MASK = 0xFFFF                           # keeps the stand-in values small


def _pixel(k: int, r: int, c: int) -> int:
    """Frame ``k``'s pixel (r, c): a fixed 8-bit pattern that moves with k."""
    return ((r + 2 * k) * 37 + (c + 3 * k) * 11 + (r * c) % 7) & 0xFF


def optical_flow(height: int = 436, width: int = 1024,
                 depth: int = 1024, z_depth: int = 4096) -> Program:
    """Rosetta's optical flow: 9 processes, 14 streams (module docstring).

    ``depth`` is the streams' default depth and ``z_depth`` that of
    ``gradient_z`` (the source's ``max_width * 4``)."""
    H, W = height, width
    prog = Program("optical_flow", declared_type="A")
    frame1_a = prog.fifo("frame1_a", depth)
    frame2_a = prog.fifo("frame2_a", depth)
    frame3_a = prog.fifo("frame3_a", depth)
    frame3_b = prog.fifo("frame3_b", depth)
    frame4_a = prog.fifo("frame4_a", depth)
    frame5_a = prog.fifo("frame5_a", depth)
    gradient_x = prog.fifo("gradient_x", depth)
    gradient_y = prog.fifo("gradient_y", depth)
    gradient_z = prog.fifo("gradient_z", z_depth)
    y_filtered = prog.fifo("y_filtered", depth)
    filtered_gradient = prog.fifo("filtered_gradient", depth)
    out_product = prog.fifo("out_product", depth)
    tensor_y = prog.fifo("tensor_y", depth)
    tensor = prog.fifo("tensor", depth)

    @prog.module("frames_cp")
    def frames_cp():
        for r in range(H):
            for c in range(W):
                f = [_pixel(k, r, c) for k in range(5)]
                yield Write(frame1_a, f[0])
                yield Write(frame2_a, f[1])
                yield Write(frame3_a, f[2])
                yield Write(frame3_b, f[2])
                yield Write(frame4_a, f[3])
                yield Write(frame5_a, f[4])

    @prog.module("gradient_xy_calc")
    def gradient_xy_calc():
        buf = [[0] * (W + 2) for _ in range(3)]     # the last three rows
        for r in range(H + 2):
            row, up, up2 = buf[r % 3], buf[(r - 1) % 3], buf[(r - 2) % 3]
            for c in range(W + 2):
                access = False
                v = 0
                if r < H and c < W:
                    v = yield Read(frame3_a)
                    access = True
                row[c] = v
                if r >= 2 and c >= 2:
                    gx = (up[c] - up[c - 2]) & MASK
                    gy = (row[c - 1] - up2[c - 1]) & MASK
                    yield Write(gradient_x, gx)
                    yield Write(gradient_y, gy)
                    access = True
                if not access:
                    yield Delay(1)

    @prog.module("gradient_z_calc")
    def gradient_z_calc():
        w = GRAD_WEIGHTS
        for _ in range(H):
            for _ in range(W):
                f1 = yield Read(frame1_a)
                f2 = yield Read(frame2_a)
                f3 = yield Read(frame3_b)
                f4 = yield Read(frame4_a)
                f5 = yield Read(frame5_a)
                gz = (w[0] * f1 + w[1] * f2 + w[2] * f3 + w[3] * f4
                      + w[4] * f5) & MASK
                yield Write(gradient_z, gz)

    @prog.module("gradient_weight_y")
    def gradient_weight_y():
        taps = len(GRAD_FILTER)
        cols = [[0] * taps for _ in range(W)]       # a column's last 7 rows
        for r in range(H + 3):
            for c in range(W):
                access = False
                v = 0
                if r < H:
                    gx = yield Read(gradient_x)
                    gy = yield Read(gradient_y)
                    gz = yield Read(gradient_z)
                    v = (gx + 3 * gy + 5 * gz) & MASK
                    access = True
                col = cols[c]
                col.pop(0)
                col.append(v)
                if r >= 3:
                    acc = sum(k * x for k, x in zip(GRAD_FILTER, col))
                    yield Write(y_filtered, acc & MASK)
                    access = True
                if not access:
                    yield Delay(1)

    @prog.module("gradient_weight_x")
    def gradient_weight_x():
        for _ in range(H):
            win = [0] * len(GRAD_FILTER)
            for c in range(W + 3):
                v = (yield Read(y_filtered)) if c < W else 0
                win.pop(0)
                win.append(v)
                if c >= 3:
                    acc = sum(k * x for k, x in zip(GRAD_FILTER, win))
                    yield Write(filtered_gradient, acc & MASK)
                elif c >= W:
                    yield Delay(1)

    @prog.module("outer_product")
    def outer_product():
        for _ in range(H):
            for _ in range(W):
                g = yield Read(filtered_gradient)
                yield Write(out_product, (g * g + 7 * g) & MASK)

    @prog.module("tensor_weight_y")
    def tensor_weight_y():
        taps = len(TENSOR_FILTER)
        cols = [[0] * taps for _ in range(W)]
        for r in range(H + 1):
            for c in range(W):
                access = False
                v = 0
                if r < H:
                    v = yield Read(out_product)
                    access = True
                col = cols[c]
                col.pop(0)
                col.append(v)
                if r >= 1:
                    acc = sum(k * x for k, x in zip(TENSOR_FILTER, col))
                    yield Write(tensor_y, acc & MASK)
                    access = True
                if not access:
                    yield Delay(1)

    @prog.module("tensor_weight_x")
    def tensor_weight_x():
        for _ in range(H):
            win = [0] * len(TENSOR_FILTER)
            for c in range(W + 1):
                v = (yield Read(tensor_y)) if c < W else 0
                win.pop(0)
                win.append(v)
                if c >= 1:
                    acc = sum(k * x for k, x in zip(TENSOR_FILTER, win))
                    yield Write(tensor, acc & MASK)

    @prog.module("flow_calc")
    def flow_calc():
        for r in range(H):
            for c in range(W):
                t = yield Read(tensor)
                yield Emit(f"velocity[{r}][{c}]", (t * 3 - 1) & MASK)

    return prog


ROSETTA_DESIGNS = {
    "optical_flow": optical_flow,
}
