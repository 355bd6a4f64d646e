"""Per cent of the HBM roofline reached by the fused counting passes: the
bytes they need (``bench/roofline.py``) over their device time, against
the chip's HBM bandwidth in ``bench/peaks.json``."""
from bench import roofline


def read(run):
    return roofline.fused_pass_share(run)
