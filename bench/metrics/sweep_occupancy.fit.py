"""Occupancy of the fused CD kernel's lockstep sweeps: 100 x the greedy
steps the diagonal tiles applied over the step slots their groups held
(each group runs as long as its slowest tile), summed over the fits'
``cascade.level`` spans (``tile_steps``, ``step_slots``: program span
attributes, from the kernel's per-tile step counts). Low occupancy means
the tiles of a group exit far apart. Moves ``fit_s``.

No reading where no level span carries the counts (a program whose
kernel sweeps one tile at a time)."""


def read(r):
    levels = [e["args"] for e in r.spans_named("cascade.level")
              if "step_slots" in e.get("args", {})]
    slots = sum(a["step_slots"] for a in levels)
    if not slots:
        return None
    return 100.0 * sum(a["tile_steps"] for a in levels) / slots
