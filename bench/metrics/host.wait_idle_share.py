"""Device idle while the engine thread waits for the host tier's
attention (``core/overlap_engine`` host executor), or runs a host job
itself, as a share (%) of the traced window."""
from bench.idle_by_span import idle_share

SPANS = ("apex.host_wait", "apex.host_job", "apex.host_job.device_wait",
         "apex.host_job.copy", "apex.host_job.attention")


def read(w):
    return idle_share(w.trace, SPANS)
