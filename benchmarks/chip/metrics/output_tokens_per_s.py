"""Output tokens visible on the host by the window's end, over the
window (host clock)."""


def read(obs):
    if obs.get("window_tokens") is None:
        return None
    return obs["window_tokens"] / obs["window_s"]
