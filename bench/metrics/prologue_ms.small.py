"""Mean host ms per call of the program's ``hybrid_sort.prologue`` span:
everything ``hybrid_sort`` does before it dispatches its program, the keys'
copy to the host and the live-bit reduce (``hybrid_sort.live_bit_window``)
among it.  Also prints how the device's idle time divides among the
program's spans (information)."""
from bench import stages


def read(run):
    return stages.prologue_ms(run)
