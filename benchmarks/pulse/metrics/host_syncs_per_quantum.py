"""Service and engine layers: blocking device-to-host reads on the served
path (the program's ``host_syncs`` count: result reads, the eager ``init``'s
reads, the ``perms`` scan of a certified iterator's access-check elision)
per read quantum.  The program's own counter (``repro.core.trace``), read
from memory."""

import program_spans as ps


def read(view):
    rec = getattr(view, "program", None) or []
    return ps.per_read_quantum(rec, sum(a.get("host_syncs", 0) for *_, a in rec))
