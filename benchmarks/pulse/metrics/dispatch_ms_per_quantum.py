"""Engine layer, the offload and access-check elision decisions
(``PulseEngine.dispatch``, ``routing.can_elide_access_check``): host time of
the program's ``engine.dispatch`` spans per read quantum.  The program's own
spans (``repro.core.trace``), read from memory."""

import program_spans as ps


def read(view):
    rec = getattr(view, "program", None) or []
    return ps.per_read_quantum(rec, 1e3 * ps.total_s(rec, "engine.dispatch"))
