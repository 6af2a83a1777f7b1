"""The share of the window's busy device time spent under the ``zoo_*``
scopes matching ``pattern``: ops XLA built are found through the join of
the trace's instruction names with the fused program's ``op_name``s
(``result["op_scopes"]``, from ``harness/hlo_scopes.py``), kernels by
their own tag. No join or nothing matched: nothing returned."""

from harness import hlo_scopes


def read(args, view):
    scopes = view.result.get("op_scopes")
    if view.trace is None or not scopes or view.trace["busy_s"] <= 0:
        return None
    seconds = hlo_scopes.seconds_under(view.trace, scopes, args["pattern"])
    if seconds <= 0:
        return None
    view.run.log(f"{args['pattern']}: {seconds:.4f} device s of "
                 f"{view.trace['busy_s']:.4f} busy")
    return 100.0 * seconds / view.trace["busy_s"]
