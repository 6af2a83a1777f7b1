"""A kernel's share of its roofline, from the device trace: the least time
the chip could take for the work the window required of the kernel (the
larger of operations over peak and bytes over peak bandwidth; both from
``harness/flops.py`` and ``harness/bytes.py``) over the device time of the
ops whose name or scope matches ``pattern``. Per chip: the work of one
chip's share of the batch over the mean device time. Nothing matched:
nothing returned."""

from harness import bytes as hbytes
from harness import common, flops, trace_reduce


def read(args, view):
    if view.trace is None or view.peaks is None:
        return None
    seconds = trace_reduce.seconds_matching(view.trace, args["pattern"])
    if seconds <= 0:
        return None
    view.run.log(f"{args['pattern']}: {seconds:.4f} device s in the window")
    sz = common.sizes(view.run.config)
    job, c = view.run.traffic, view.result["counters"]
    shape = (sz, job["batch_per_chip"], job["seq_len"])
    itemsize = args.get("itemsize", 2)
    ops = getattr(flops, args["flops"])(*shape) if args.get("flops") else 0
    moved = getattr(hbytes, args["bytes"])(*shape, itemsize)
    least = max(ops / view.peaks["bf16_flops"],
                moved / view.peaks["hbm_bytes_per_s"]) * c["steps"]
    return 100.0 * least / seconds
