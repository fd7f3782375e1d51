"""A number the generator observed itself, by key (set-up phases, the
trainer's input wait): ``{"key": "setup_s"}``."""


def read(obs, args, ctx):
    value = obs.get(args["key"])
    return None if value is None else float(value) * float(args.get("scale", 1))
