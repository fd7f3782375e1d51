"""Time to first text, in ms, from the instant a turn was due to its
first event that carried text, over every turn due inside the window;
a turn that failed, was refused or never showed text ranks as the
largest. ``{"q": 0.9}``."""

from benchmark import estimators


def samples(obs):
    good, failed = [], 0
    for rec in obs["records"]:
        if rec["kind"] != "load" or not (obs["t0"] <= rec["due"] < obs["t1"]):
            continue
        if rec["events"]:
            good.append(1000.0 * (rec["events"][0][0] - rec["due"]))
        else:
            failed += 1
    return good, failed


def read(obs, args, ctx):
    good, failed = samples(obs)
    limit_ms = 1000.0 * float(obs["traffic"].get("request_timeout_s", 120))
    return estimators.quantile_with_failures(
        good, failed, float(args["q"]), at_least=limit_ms if failed else 0.0
    )
