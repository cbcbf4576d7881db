"""Device: the share of the traced window in which no operation ran, 1 minus
the union of the device's busy intervals over the window, averaged over the
chips (device trace)."""

import devtrace


def read(view):
    return None if view.trace is None else devtrace.idle_pct(view.trace)
