"""Peak device memory on the fullest chip as a share of its limit, in
percent (the allocator's peak, in use or reserved, whichever is larger)."""


def read(obs, args, ctx):
    dev = obs["device"]
    if ctx.platform != "tpu" or not dev.get("memory_limit_bytes"):
        return None
    return 100.0 * dev["memory_peak_bytes"] / dev["memory_limit_bytes"]
