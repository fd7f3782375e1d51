"""Trained tokens per second: all tokens between the window's first and
last sync, over the time between the two."""


def read(obs, args, ctx):
    stamps = obs["syncs"]
    if len(stamps) < 2:
        return None
    return obs["tokens_per_sync"] * (len(stamps) - 1) / (stamps[-1] - stamps[0])
