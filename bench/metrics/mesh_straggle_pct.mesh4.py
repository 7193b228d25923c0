"""Over the sharded levels, the share of pass slots in which a device had
finished its own partitions and waited for the slowest: sum of (max -
mean) of each ``cascade.level`` span's ``passes_by_device`` over the sum
of its max (program counter, carried by the level spans). Each device
runs its own while loop to its own partitions' convergence, so the level
lasts as long as its slowest device. Moves ``fit_s``.

No reading where no level span is ``sharded`` with ``passes_by_device``."""


def read(r):
    levels = [e["args"]["passes_by_device"]
              for e in r.spans_named("cascade.level")
              if e.get("args", {}).get("layout") == "sharded"
              and "passes_by_device" in e["args"]]
    slots = sum(max(p) for p in levels)
    if not slots:
        return None
    waited = sum(max(p) - sum(p) / len(p) for p in levels)
    return 100.0 * waited / slots
