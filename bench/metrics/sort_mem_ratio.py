"""Device memory the sort holds beside the user's data, in inputs: the peak
bytes in use on the fullest chip after the window, less what the benchmark
itself held there during the last call (its pool, bytes in use before the
warm call, that call's input and the outputs kept for the check), over one
call's input bytes on that chip."""


def read(run):
    cell = run.cell
    per_chip = cell.n * cell.record_bytes / cell.chips
    return (run.peak_bytes - run.base_bytes) / per_chip
