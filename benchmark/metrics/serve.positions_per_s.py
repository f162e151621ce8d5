"""Positions advanced a second, prompt and answer alike, from the callers' own
stamps: the engine's rate of work whatever share of it is answer tokens, and
so steadier than `decode_tokens_per_s`, which counts the answers alone."""


def read(r):
    c = r["counters"]
    return c["positions"] / c["seconds"] if c["positions"] else None
