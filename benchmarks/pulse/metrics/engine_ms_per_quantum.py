"""Engine layer (``core/engine.PulseEngine.execute``) on read quanta: the mean
host wall time of one call, the chase and the host-to-device and
device-to-host copies around it included.  Host-clock spans around the
engine instance's ``execute``."""


def read(view):
    q = [t1 - t0 for _, t0, t1, a in view.spans.named("quantum.") if not a["writes"]]
    return 1e3 * sum(q) / len(q) if q else None
