"""Service layer, retirement: host time of the program's ``service.apply``
spans (slot scatter, ``_fast_retire``) and ``service.emit`` spans
(``_drain_emit``'s accounting) per round (``service.round``).  The
program's own spans (``repro.core.trace``), read from memory."""

import program_spans as ps


def read(view):
    rec = getattr(view, "program", None) or []
    return ps.per_round_ms(
        rec, ps.total_s(rec, "service.apply") + ps.total_s(rec, "service.emit")
    )
