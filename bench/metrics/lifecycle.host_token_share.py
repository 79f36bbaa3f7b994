"""Share of the window's output tokens that host-tier rows emitted."""


def read(w):
    total = w.delta("device_tokens") + w.delta("host_tokens")
    if total <= 0:
        return None
    return 100.0 * w.delta("host_tokens") / total
