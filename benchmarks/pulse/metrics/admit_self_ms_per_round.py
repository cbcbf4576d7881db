"""Service layer, admission (``PulseService._admit``): host time of the
program's ``service.admit`` spans less their ``service.init`` children --
intake, preemption, write barriers, ``admission.admit`` and slot placement --
per round (``service.round``).  The program's own spans
(``repro.core.trace``), read from memory."""

import program_spans as ps


def read(view):
    rec = getattr(view, "program", None) or []
    return ps.per_round_ms(rec, ps.self_s(rec, "service.admit", "service.init"))
