"""``cold_roofline``: the cold read's share of its roofline, in %.

The least time the chip could take for the bytes of ``work/<work>.py``
(the configuration's ``cold_budget`` rows read and written once) at
``peak``, over the device's time in the cold read of one execution: the
self time of the ops under ``pattern`` (a wait the profiler shows inside a
``-done``, or as the turns of the loop that holds the fetches, is in
there), plus the time inside the transfers' ``-start`` / ``-done`` spans in
which NO op runs (``reducers/cold_wait.py``: a wait shown as a gap in the
device's line), each moment counted once. HBM bandwidth is
a loose bound for rows that cross the host link: the share says how far
the cold read is from a gather out of the chip's own memory.

Nothing to read is None, never 0.
"""

import re

from chipbench import readers, spec

QUANTITY = {"hbm_bytes_per_s": "bytes"}


def reduce(ctx, pattern, per, work, peak):
    if peak not in QUANTITY:
        raise ValueError(f"cold_roofline: no bound {peak!r}, "
                         f"has {sorted(QUANTITY)}")
    found = spec.plugin("reducers", "cold_wait").read(ctx, pattern)
    n = readers.count_of(ctx, per)
    if found is None or n is None or not ctx.get("peaks"):
        return None
    rx = re.compile(pattern)
    on_device = ctx["trace"].seconds(
        lambda o: rx.search(o.scope) is not None) or 0.0
    amount = readers.work_of(ctx["cell"], work)[QUANTITY[peak]]
    return 100.0 * (amount / ctx["peaks"][peak]) / ((on_device + found[1]) / n)
