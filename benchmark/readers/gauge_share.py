"""Mean over the window's samples of one gauge as a share of another,
in percent: ``{"num": name, "den": name}``."""


def read(obs, args, ctx):
    shares = []
    for snap in (obs.get("counters") or {}).get("samples", []):
        den = snap.get(args["den"], {}).get("value", 0.0)
        if den > 0:
            shares.append(100.0 * snap.get(args["num"], {}).get("value", 0.0) / den)
    return sum(shares) / len(shares) if shares else None
