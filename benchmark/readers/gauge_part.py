"""Mean over the window's samples of one gauge as a share of the sum of
several, in percent: ``{"part": name, "of": [name, ...]}``. Nothing where
the program has none of them."""


def read(obs, args, ctx):
    shares = []
    for snap in (obs.get("counters") or {}).get("samples", []):
        whole = sum(snap.get(name, {}).get("value", 0.0) for name in args["of"])
        if whole > 0:
            shares.append(100.0 * snap.get(args["part"], {}).get("value", 0.0) / whole)
    return sum(shares) / len(shares) if shares else None
