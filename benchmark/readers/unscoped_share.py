"""The share of the window's busy device time spent in ops that no
``zoo_*`` scope covers: no tag on the instruction by the join of the trace
with the fused program's ``op_name``s (``result["op_scopes"]``, from
``harness/hlo_scopes.py``), nor in a kernel's own text. No join: nothing
returned."""

from harness import hlo_scopes


def read(args, view):
    scopes = view.result.get("op_scopes")
    if view.trace is None or not scopes or view.trace["busy_s"] <= 0:
        return None
    seconds = sum(s for name, text, s, _ in view.trace["ops"]
                  if name not in scopes and not hlo_scopes.TAG.search(text))
    seconds /= view.trace["devices"]
    view.run.log(f"unscoped: {seconds:.4f} device s of "
                 f"{view.trace['busy_s']:.4f} busy")
    return 100.0 * seconds / view.trace["busy_s"]
