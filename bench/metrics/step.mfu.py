"""The whole step's model operations over the window, as a share of the
chip's peak: every token emitted (device and host rows) at its live
context, plus the matrix operations of prompt tokens prefilled, counted
by the configuration's family (``step_cost``)."""


def read(w):
    if w.peak is None or not w.steps:
        return None
    total = 0.0
    for s in w.steps:
        f, _ = w.family.step_cost(w.model, s)
        total += f
    return 100.0 * total / (w.seconds * w.peak.flops_per_s) if total else None
