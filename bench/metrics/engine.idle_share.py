"""Device idle while the engine thread does the step's own work
(``serving/engine``): dispatching the device program, reading its tokens
back, the host cohort's token, finishing host prefill chunks, and the
step outside its phases; as a share (%) of the traced window."""
from bench.idle_by_span import idle_share

SPANS = ("apex.step", "apex.dispatch", "apex.sync", "apex.host_token",
         "apex.finish_chunks")


def read(w):
    return idle_share(w.trace, SPANS)
