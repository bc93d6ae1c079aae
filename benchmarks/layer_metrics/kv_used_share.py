"""Mean over the window's decode steps of the tokens resident in the live
sequences, over the tokens the KV pool reserves (slots x context a slot):
how much of the reserved cache the traffic fills. Read from the engine's
requests at each decode dispatch (harness span)."""


def read(rec):
    if rec.get("kind") != "serve" or not rec.get("kv_pool_tokens"):
        return None
    live = [s[3]["live_tokens"] for s in rec["spans"]
            if s[0] == "engine_dispatch"]
    if not live:
        return None
    return sum(live) / len(live) / rec["kv_pool_tokens"]
