"""Engine layer, the wait for results: host time of the program's
``engine.d2h`` spans -- the blocking reads of the local step's results, the
host waiting on the device plus the copies -- per read quantum.  The
program's own spans (``repro.core.trace``), read from memory."""

import program_spans as ps


def read(view):
    rec = getattr(view, "program", None) or []
    return ps.per_read_quantum(rec, 1e3 * ps.total_s(rec, "engine.d2h"))
