"""Service layer, eager device work: device executables ("XLA Modules")
whose midpoint lies inside a ``pulse.service.*`` span and outside every
``pulse.engine.*`` span -- the ops of the eager ``init`` and its conversions
-- per ``pulse.service.round``, averaged over the chips (device trace, the
program's spans as its host events)."""

import program_spans as ps


def read(view):
    return ps.eager_programs(view.trace)
