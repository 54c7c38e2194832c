"""``scope_roofline``: a scope's share of its roofline, in %.

The device self time of the ops whose scope matches ``pattern``, per
``per`` (``steps``, ``batches``), against the least time the chip could
take for the work of one execution. The work is counted FROM THE CELL'S
SHAPES by ``work/<work>.py``, not from the operations the program happens
to use, so a kernel that replaces XLA's is judged against the same bytes.
``peak`` names the bound in ``peaks.json``: ``hbm_bytes_per_s`` (the
work's ``bytes``) or ``bf16_flops_per_s`` (its ``flops``).

    {"reducer": "scope_roofline",
     "args": {"pattern": "qt_gather", "per": "steps",
              "work": "frontier_gather", "peak": "hbm_bytes_per_s"}}

Nothing to read (no op under the scope, no count, no peaks) is None: the
metric is left out of the line, never 0.
"""

from chipbench import readers

QUANTITY = {"hbm_bytes_per_s": "bytes", "bf16_flops_per_s": "flops"}


def reduce(ctx, pattern, per, work, peak):
    if peak not in QUANTITY:
        raise ValueError(f"scope_roofline: no bound {peak!r}, "
                         f"has {sorted(QUANTITY)}")
    ms = readers.scope_ms(ctx, pattern, per)
    if not ms or not ctx.get("peaks"):
        return None
    amount = readers.work_of(ctx["cell"], work)[QUANTITY[peak]]
    return 100.0 * (amount / ctx["peaks"][peak]) / (1e-3 * ms)
