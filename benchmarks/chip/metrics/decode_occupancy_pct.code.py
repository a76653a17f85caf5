"""How full the decode batch is while requests wait in the queue: active
slots over ``max_slots``, over the engine's decode calls made with a
non-empty queue (``decode_slots_backlogged`` /
(``decode_calls_backlogged`` x ``max_slots``)), in percent. Below 100%
something other than the slots, such as the KV pool, stops the
scheduler admitting."""


def read(obs):
    p = obs.get("program", {})
    calls = p.get("decode_calls_backlogged", 0)
    if not calls:
        return None
    return 100.0 * p["decode_slots_backlogged"] / (
        calls * obs["engine"]["max_slots"])
