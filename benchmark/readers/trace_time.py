"""Device time of the programs or operations whose trace names match:
``{"line": "modules" | "ops", "match": regex, "per": "call" | "window",
"scale": x}``. Per call it is the mean device time of one such event."""

import re


def matched(obs, args):
    tr = obs.get("trace")
    if not tr:
        return None, None
    rx = re.compile(args["match"])
    secs = tr["modules" if args.get("line", "modules") == "modules" else "ops"]
    calls = tr["module_calls" if args.get("line", "modules") == "modules" else "op_calls"]
    s = sum(v for k, v in secs.items() if rx.search(k))
    n = sum(v for k, v in calls.items() if rx.search(k))
    return s, n


def read(obs, args, ctx):
    s, n = matched(obs, args)
    if not s:
        return None
    if args.get("per", "call") == "call":
        if not n:
            return None
        s /= n
    return float(args.get("scale", 1)) * s
