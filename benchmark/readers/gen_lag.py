"""How late the generator sent a turn after it was due, in ms:
``{"q": 0.9}``. A starved generator must not read as a fast server."""

from benchmark import estimators


def read(obs, args, ctx):
    lags = [
        1000.0 * (rec["sent"] - rec["due"]) for rec in obs["records"]
        if rec["kind"] == "load" and rec["sent"] is not None
        and obs["t0"] <= rec["due"] < obs["t1"]
    ]
    return estimators.quantile(lags, float(args["q"]))
