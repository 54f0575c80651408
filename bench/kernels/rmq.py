"""Bytes and operations of one call of the batched RMQ Pallas kernel
(``src/repro/kernels/rmq/kernel.py``), from the call's shapes.

In the device trace a call is an ``XLA Ops`` event whose name is its HLO
instruction, ``%rmq_query.<n> = s32[B,2]{...} custom-call(...)``: the name
comes from the jitted wrapper ``kernels/rmq/ops.py::rmq_query``, and B is
the call's batch of lanes.

Per lane the kernel reads its range (2 int32), the two 128-lane blocks
that hold the range's ends (2 x 128 int32) and the four sparse-table
candidates (4 int32), and writes (pos, val) (2 int32): 264 int32, 1056
bytes. Its operations, per lane and block: the two compares and the and
of the mask, the masked select, the min, the equality compare, the lane
select and the second min, 8 vector operations per element, so 2048 over
both blocks, and 24 more to pick among the four candidates.
"""
import re

BLOCK = 128
BYTES_PER_LANE = (2 + 2 * BLOCK + 4 + 2) * 4
OPS_PER_LANE = 8 * 2 * BLOCK + 24
_CALL = re.compile(r"^%(rmq_query[\w.]*) = s32\[(\d+),2\]\S* custom-call\(")


def matches(op) -> bool:
    """Is this device operation a call of the rmq kernel?"""
    return _CALL.match(op.name) is not None


def lanes(op) -> int:
    """The call's batch of lanes, from its int32[B, 2] output shape."""
    return int(_CALL.match(op.name).group(2))


def cost(op):
    """-> (operations, bytes) of one call."""
    b = lanes(op)
    return b * OPS_PER_LANE, b * BYTES_PER_LANE
