"""Work that one call of the ``dp_clip`` kernel pair needs: per-example
clipping of a (c, D) float32 stack of flat gradients into a (D,) sum.

Bytes are what the algorithm needs: the stack read once, the accumulator
read and written once. Operations: a multiply-add per element for the
squared norms and one for the scaled sum.
"""


def call_bytes(c: int, D: int) -> int:
    return 4 * c * D + 2 * 4 * D


def call_flops(c: int, D: int) -> int:
    return 4 * c * D
