"""Share of the window's iterations that Algorithm 1 decided as
async_overlap or asym_pipeline (the strategies that use the host tier)."""


def read(w):
    decided = sum(w.delta(f"strategy.{k}") for k in
                  ("gpu_only", "async_overlap", "asym_pipeline"))
    if decided <= 0:
        return None
    host = w.delta("strategy.async_overlap") + w.delta("strategy.asym_pipeline")
    return 100.0 * host / decided
