"""Share, in %, of the HBM roofline reached by the device SBTS step's
count kernel (`kernels/sbts_step`, ``selection_counts_pallas``): the
least time its calls could take, from the benchmark's own count of the
bytes each must move at its shape, over their device time in the trace.
The memory bound is the one that applies: the kernel's integer work runs
on the vector unit, for which no peak is published."""

import re

from benchkit.roofline import selection_counts_work

KERNEL = r"^selection_counts_pallas$"
# The call's result, int32 [K, n_pad], opens its instruction text.
SHAPE = re.compile(r"= s32\[(\d+),(\d+)\]")


def read(run):
    tr = run.trace
    if tr is None:
        return None
    least = spent = 0.0
    for text, dur in tr.kernel_events(KERNEL, tr.window()):
        shape = SHAPE.search(text)
        if shape is None:
            return None
        k, n_pad = int(shape.group(1)), int(shape.group(2))
        least += selection_counts_work(n_pad, k).least_s(run.device_kind)[0]
        spent += dur
    return 100.0 * least / spent if spent > 0 else None
