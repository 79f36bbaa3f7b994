"""Sum over the traced window's steps of the least time the chip could
take for each (the larger of operations over peak FLOP/s and bytes over
peak bandwidth; bytes are the weights read once per step plus the live
keys and values of each device row), over the device's busy time."""
from bench import flops


def read(w):
    if w.peak is None or w.trace is None or not w.steps:
        return None
    busy = w.trace["busy_s"]
    if busy <= 0:
        return None
    least = 0.0
    for s in w.steps:
        f, b = flops.step_cost(w.model, s.device_contexts, s.host_tokens,
                               s.prompt_tokens)
        least += max(f / w.peak.flops_per_s, b / w.peak.hbm_bytes_per_s)
    return 100.0 * least / busy
