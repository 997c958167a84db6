"""The ``dp_clip`` kernel pair in a reduced trace: its two Mosaic kernels
(the squared-norm pass and the scale-accumulate pass of
``repro/kernels/dp_clip/kernel.py``), found by name."""
import re

NORM = re.compile(r"sq_norm", re.I)
ACC = re.compile(r"scale_acc", re.I)


def seconds(trace) -> float:
    return sum(v for k, v in trace["ops"].items()
               if NORM.search(k) or ACC.search(k))


def launches(trace) -> float:
    """Launches of the norm pass: one per call of the pair, each covering
    every client of the vmapped step."""
    return sum(v for k, v in trace["calls"].items() if NORM.search(k))
