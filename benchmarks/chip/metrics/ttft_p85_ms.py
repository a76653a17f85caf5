"""85th percentile over every request due in the window of the time from
its due time (open loop) to the end of the engine step after which its
first token is visible on the host (host clock). A window of the chat
cell holds 82 requests; the 85th percentile has twelve of them beyond
it, where the 95th has four and spreads twice as widely from run to run
(PERF.md). A request that never showed a token counts with the time
until the drain ended."""
from benchmarks.chip.readers import percentile


def read(obs):
    if "requests" not in obs:
        return None
    end = obs["drain_end"]
    return percentile([((r["first"] if r["first"] is not None else end)
                        - r["due"]) * 1e3 for r in obs["requests"]], 85)
