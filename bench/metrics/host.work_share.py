"""Wall seconds of the host executor's own work on host-attention jobs
(copying the step's QKV to the host, the pool append and paged
attention), leaving out its wait for the device step that makes the
QKV, over the window, as a share of it."""


def read(w):
    if "host_device_wait_time" not in w.counters1:
        return None
    work = w.delta("host_busy_time") - w.delta("host_device_wait_time")
    return 100.0 * work / w.seconds if work > 0 else None
