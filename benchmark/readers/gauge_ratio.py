"""Mean over the window's samples of one gauge over another:
``{"num": name, "den": name, "den_add": a, "den_scale": s}`` reads
``num / ((den + a) * s)``. Nothing where the program lacks either gauge
or the first reads nothing."""


def read(obs, args, ctx):
    ratios = []
    for snap in (obs.get("counters") or {}).get("samples", []):
        num = snap.get(args["num"], {}).get("value", 0.0)
        den = snap.get(args["den"], {}).get("value", 0.0)
        if num > 0 and den > 0:
            ratios.append(num / ((den + float(args.get("den_add", 0)))
                                 * float(args.get("den_scale", 1))))
    return sum(ratios) / len(ratios) if ratios else None
