"""Share of the window's engine iterations whose step ran the prefill
chunk program (fused with decode or alone), not the decode-only step."""


def read(w):
    # a program without the counter has nothing to read
    if "chunk_iterations" not in w.counters1:
        return None
    it = w.delta("iterations")
    if it <= 0:
        return None
    return 100.0 * w.delta("chunk_iterations") / it
