"""The whole step's model operations over the window, as a share of the
chip's peak: every token emitted (device and host rows) at its live
context, plus the matrix operations of prompt tokens prefilled."""
from bench import flops


def read(w):
    if w.peak is None or not w.steps:
        return None
    m = w.model
    total = 0.0
    for s in w.steps:
        f, _ = flops.step_cost(m, s.device_contexts, s.host_tokens,
                               s.prompt_tokens)
        total += f
    return 100.0 * total / (w.seconds * w.peak.flops_per_s) if total else None
