"""The share of the host executor's own work on host-attention jobs
(as in ``host.work_share``) that the engine thread never waited for:
100 * (1 - engine seconds blocked on the host tier / host work seconds).
It reads below 0 where the engine waited on jobs that had not started."""


def read(w):
    if "host_wait_time" not in w.counters1:
        return None
    work = w.delta("host_busy_time") - w.delta("host_device_wait_time")
    if work <= 0:
        return None
    return 100.0 * (1.0 - w.delta("host_wait_time") / work)
