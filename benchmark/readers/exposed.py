"""Milliseconds per step in which a collective runs on a chip and no
other operation does: ``{"collective": "all-reduce"}``."""


def read(obs, args, ctx):
    tr = obs.get("trace")
    if not tr or not obs.get("traced_steps"):
        return None
    s = tr.get("exposed_s", {}).get(args["collective"])
    return None if s is None else 1000.0 * s / obs["traced_steps"]
