"""Wall seconds of the host executor's host-attention jobs (device-to-host
transfer plus paged attention) over the window, as a share of it."""


def read(w):
    busy = w.delta("host_busy_time")
    return 100.0 * busy / w.seconds if busy > 0 else None
