"""Sum over the traced window's steps of the least time the chip could
take for each (the larger of operations over peak FLOP/s and bytes over
peak bandwidth, both counted by the configuration's family's
``step_cost``), over the device's busy time."""


def read(w):
    if w.peak is None or w.trace is None or not w.steps:
        return None
    busy = w.trace["busy_s"]
    if busy <= 0:
        return None
    least = 0.0
    for s in w.steps:
        f, b = w.family.step_cost(w.model, s)
        least += max(f / w.peak.flops_per_s, b / w.peak.hbm_bytes_per_s)
    return 100.0 * least / busy
