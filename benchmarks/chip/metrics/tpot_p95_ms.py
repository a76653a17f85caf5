"""95th percentile over the window's requests of (time of the last
token - time of the first) / (tokens - 1), host clock. The engine hands
tokens over in bursts of ``steps_per_sync``, so a raw gap between two
tokens would measure the burst, not the decode rate. Requests with one
token, or unfinished, have no such time."""
from benchmarks.chip.readers import percentile


def read(obs):
    if "requests" not in obs:
        return None
    return percentile([(r["last"] - r["first"]) * 1e3 / (r["n_out"] - 1)
                       for r in obs["requests"]
                       if r["last"] is not None and r["n_out"] > 1], 95)
