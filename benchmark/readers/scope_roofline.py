"""A scope's share of its roofline: the least time the chip could take
for the work the window required under the scope (the larger of
operations over peak and bytes over peak bandwidth, from the functions of
``harness/hybrid_decoder_work.py`` that ``work`` names, over the window's
steps) over the device seconds of the ops under the scopes matching
``pattern`` (``harness/hlo_scopes.py``). ``work``: ``gdn``, ``flash`` or
``experts``; the experts' work is per assignment that landed on a held
expert, which the program counted. Nothing matched: nothing returned."""

from harness import common, hlo_scopes
from harness import hybrid_decoder_work as work


def read(args, view):
    if view.trace is None or view.peaks is None:
        return None
    seconds = hlo_scopes.seconds_under(
        view.trace, view.result.get("op_scopes") or {}, args["pattern"])
    if seconds <= 0:
        return None
    ref = common.load_module("references", view.run.config["reference"],
                             view.run.root)
    sz, job, c = ref.sizes(view.run.config), view.run.traffic, \
        view.result["counters"]
    batch, seq, steps = job["batch_per_chip"], job["seq_len"], c["steps"]
    if args["work"] == "experts":
        held = c["moe_assignments_held"]
        ops = work.experts_train_flops(sz, held)
        moved = work.experts_train_bytes(sz, held, steps)
    else:
        ops = steps * getattr(work, args["work"] + "_train_flops")(
            sz, batch, seq)
        moved = steps * getattr(work, args["work"] + "_train_bytes")(
            sz, batch, seq)
    least = max(ops / view.peaks["bf16_flops"],
                moved / view.peaks["hbm_bytes_per_s"])
    view.run.log(f"{args['pattern']}: {seconds:.4f} device s; least "
                 f"{least:.4f} s ({ops / 1e12:.2f} TFLOP, "
                 f"{moved / 1e9:.2f} GB)")
    return 100.0 * least / seconds
