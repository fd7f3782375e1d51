"""Programs compiled inside the window: entries the compile cache gained
between the window's two ends, plus the runtime's compile events in the
traced part of it."""


def read(obs, args, ctx):
    entries = obs["cache_entries"]
    events = (obs.get("trace") or {}).get("host_calls", {})
    return float(entries["t1"] - entries["t0"] + sum(events.values()))
