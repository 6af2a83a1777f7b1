"""A count or a host-clock statistic the driver took in the window
(``result["counters"][name]``), optionally scaled."""


def read(args, view):
    value = view.result["counters"].get(args["name"])
    if value is None:
        return None
    return value * args.get("scale", 1)
