"""Plain reference of the sort, and the comparison that decides ``correct``.

NumPy only: it imports nothing of the program under test.  A record is a
key with an optional value.  The sort guarantees ascending keys and that
every value leaves beside the key it came in with; equal keys may leave in
any order (the hybrid sort is not stable).  So both sides are compared in
one canonical form: keys sorted, and within a run of equal keys the values
sorted too.  Keys must then match exactly; the canonical records must match
exactly.
"""
from __future__ import annotations

import numpy as np


def canonical(keys: np.ndarray, vals):
    """Records ordered by key, then value: ``(keys, values or None)``."""
    keys = np.asarray(keys).reshape(-1)
    if vals is None:
        return np.sort(keys), None
    vals = np.asarray(vals).reshape(-1)
    if all(a.dtype.kind == "u" and a.dtype.itemsize <= 4 for a in (keys, vals)):
        # one sort of packed words, key in the high half: much faster
        rec = (keys.astype(np.uint64) << np.uint64(32)) | vals.astype(np.uint64)
        rec.sort()
        return ((rec >> np.uint64(32)).astype(keys.dtype),
                (rec & np.uint64(0xFFFFFFFF)).astype(vals.dtype))
    order = np.lexsort((vals, keys))
    return keys[order], vals[order]


def compare(want, keys_out, vals_out) -> dict:
    """Count what one answer gets wrong against ``want``, the reference's
    answer (``canonical`` of the input).

    ``keys_wrong``: positions whose key differs from the reference's (the
    whole length when the answer has the wrong length).  ``pairs_wrong``:
    positions whose canonical record differs, i.e. a value that left its
    key; only for answers with values.
    """
    want_keys, want_vals = want
    n = want_keys.shape[0]
    keys_out = np.asarray(keys_out).reshape(-1)
    with_values = want_vals is not None
    if keys_out.shape[0] != n or (
            with_values and np.asarray(vals_out).size != n):
        out = {"keys_wrong": n}
        if with_values:
            out["pairs_wrong"] = n
        return out
    out = {"keys_wrong": int(np.count_nonzero(keys_out != want_keys))}
    if with_values:
        got_keys, got_vals = canonical(keys_out, vals_out)
        out["pairs_wrong"] = int(np.count_nonzero(
            (got_keys != want_keys) | (got_vals != want_vals)))
    return out
