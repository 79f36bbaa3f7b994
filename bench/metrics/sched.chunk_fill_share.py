"""Share of the chunk buffer's token positions, over the window's chunk
calls, that held prompt tokens: prompt tokens prefilled through chunks
over the positions the chunk program computed."""


def read(w):
    # a program without the counter has nothing to read
    if "chunk_buffer_tokens" not in w.counters1:
        return None
    computed = w.delta("chunk_buffer_tokens")
    if computed <= 0:
        return None
    return 100.0 * w.delta("chunked_prefill_tokens") / computed
