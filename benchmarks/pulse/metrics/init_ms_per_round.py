"""Service layer, request set-up: host time of the program's
``service.init`` spans -- the eager ``iterator.init`` of a group's fresh
requests and the reads of its results -- per round (``service.round``).
The program's own spans (``repro.core.trace``), read from memory."""

import program_spans as ps


def read(view):
    rec = getattr(view, "program", None) or []
    return ps.per_round_ms(rec, ps.total_s(rec, "service.init"))
