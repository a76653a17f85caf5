"""The scheduler's queue wait, 95th percentile, from the engine's own
``queue_wait_ms`` histogram over the window and drain (a bucketed
estimate: within one bucket of the sample's percentile)."""


def read(obs):
    q = obs.get("program_quantiles", {}).get("queue_wait_ms")
    return q["p95"] if q and q["count"] else None
