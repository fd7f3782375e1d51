"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips."""


def read(obs, args, ctx):
    tr = obs.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
