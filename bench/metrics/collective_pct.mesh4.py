"""Exposed collective time: the device time of collective operations
(all-gather, all-reduce, collective-permute, all-to-all, reduce-scatter,
and the TPU's async collective start and done) during which that device
ran no other operation, over the traced window, averaged over the
devices (device trace). An operation counts by its own instruction name;
a fusion that overlaps compute with a collective in flight is compute.
Moves ``fit_s``.

The seconds each collective name took go to the notes."""
import re

COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|collective-permute|"
                        r"all-to-all|reduce-scatter|async-collective)"
                        r"[\w.-]*( |$)")


def _merged(ops, t0, t1) -> list:
    out: list = []
    for a, b in sorted((max(o.start, t0), min(o.end, t1)) for o in ops):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def exposed_ns(coll: list, rest: list) -> float:
    """Length of the ``coll`` intervals outside the ``rest`` intervals
    (both merged, so no instant counts twice)."""
    return sum((b - a) - sum(max(0.0, min(b, rb) - max(a, ra))
                             for ra, rb in rest)
               for a, b in coll)


def read(r):
    tr = r.trace
    if tr is None or not tr.devices:
        return None
    total, by_name = 0.0, {}
    for ops in tr.devices.values():
        coll = [o for o in ops if COLLECTIVE.search(o.name)]
        rest = [o for o in ops if not COLLECTIVE.search(o.name)]
        total += exposed_ns(_merged(coll, tr.t0, tr.t1),
                            _merged(rest, tr.t0, tr.t1))
        for o in coll:
            name = o.name.split(" ")[0]
            by_name[name] = by_name.get(name, 0.0) + (o.end - o.start) / 1e9
    top = sorted(by_name.items(), key=lambda p: -p[1])[:8]
    r.notes.append(f"collective_pct.mesh4: {sum(by_name.values())!r} s of "
                   f"collectives over the devices; by name {top}")
    n = len(tr.devices)
    return 100.0 * total / n / (tr.t1 - tr.t0)
