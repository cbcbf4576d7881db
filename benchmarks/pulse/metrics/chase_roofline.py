"""Chase step (``core/iterator.execute_batched`` on XLA, or whatever program
serves read quanta): the share of the HBM roofline (`chase_roofline.tput`).

  bytes      the read quanta's lane iterations times the configuration's row
             bytes (16 B for a hash node); no padding counted
  least      bytes / (chips x HBM peak of lib/peaks.json)
  time       device time of the executables launched inside read-quantum
             spans, averaged over the chips (device trace)

A chase is bound by the latency of dependent loads, not by bandwidth, so
the share is small; it is the distance to what the memory could give."""

import devtrace


def read(view):
    if view.trace is None:
        return None
    reads = [a for _, _, _, a in view.spans.named("quantum.") if not a["writes"]]
    names = {n for n, _, _, a in view.spans.named("quantum.") if not a["writes"]}
    if not reads:
        return None
    dev_ns = {}
    for name in names:
        for d, ns in devtrace.in_spans_ns(view.trace, "bench." + name).items():
            dev_ns[d] = dev_ns.get(d, 0.0) + ns
    time_s = sum(dev_ns.values()) / max(1, len(dev_ns)) / 1e9
    if time_s <= 0:
        return None
    nbytes = sum(a["lane_iters"] for a in reads) * view.row_bytes
    least_s = nbytes / (view.chips * view.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / time_s
