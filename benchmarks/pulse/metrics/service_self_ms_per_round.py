"""Service layer (``serving/traversal_service``, ``serving/admission``): the
mean time of one ``PulseService.step`` minus the engine calls inside it --
admission, ``init`` of admitted requests, slot bookkeeping, retirement.
Host-clock spans taken around ``step`` and around the engine's ``execute``."""


def read(view):
    rounds = view.spans.named("round")
    if not rounds:
        return None
    quanta = sorted((t0, t1) for _, t0, t1, _ in view.spans.named("quantum."))
    total, k = 0.0, 0
    for _, r0, r1, _ in rounds:
        inner = 0.0
        while k < len(quanta) and quanta[k][0] < r0:
            k += 1
        j = k
        while j < len(quanta) and quanta[j][1] <= r1:
            inner += quanta[j][1] - quanta[j][0]
            j += 1
        total += (r1 - r0) - inner
    return 1e3 * total / len(rounds)
