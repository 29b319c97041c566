"""``frontier_expand`` (``kernels/frontier.py``): one BFS level.

What the algorithm needs per call, from the op's shapes (edge operand
``s32[E]``, vertex result ``s32[V]``): one pass over the E edges, reading
each edge's source and destination index (4 bytes each) and validity and
frontier bits (a byte each), and one pass over the V vertices, reading
the visited flag and writing the reached flag (a byte each); per edge a
test and an OR.  The compute bound uses the chip's published op rate.
"""
from bench.roofline.shapes import result_and_operands

NAMES = ("frontier_expand",)
OPS_PEAK = "flops_per_s"


def cost(event, run):
    (_, vertices), operands = result_and_operands(event.name)
    e = operands[0][1][0]
    v = vertices[0]
    return 2 * e, e * (4 + 4 + 1 + 1) + v * 2
