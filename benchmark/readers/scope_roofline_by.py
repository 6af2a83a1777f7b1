"""A scope's share of its roofline, as ``scope_roofline`` reads it, with
the work counted by the module of ``harness/`` that ``module`` names
(``<work>_train_flops``, ``<work>_train_bytes``, and the experts' pair per
held assignment): that reader, run from a copy of its module that is this
call's own (``load_module`` makes a new module each time it is asked) with
the named work module in the place of the one it is wired to. Nothing
matched, as on a program without the scope: nothing returned."""

from harness import common


def read(args, view):
    base = common.load_module("readers", "scope_roofline", view.run.root)
    base.work = common.load_module("harness", args["module"], view.run.root)
    return base.read(args, view)
