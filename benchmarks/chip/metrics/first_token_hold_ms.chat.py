"""How long the engine holds a fresh first token after syncing it to the
host: from the sync to the end of the engine step that produced it, when
the front end can see it (the rest of that step's decode burst and its
drain). The mean of the engine's ``first_token_hold_ms`` histogram over
the window and drain, in ms."""


def read(obs):
    p = obs.get("program", {})
    return p["first_token_hold_ms_mean"] if p.get(
        "first_token_hold_ms_count") else None
