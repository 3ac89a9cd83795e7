"""Milliseconds a training step in which a collective ran on a chip and no
other op did, averaged over the chips: the ops named ``reduce-scatter*``,
``all-gather*`` or ``all-reduce*`` in the device trace, less the union of
every other op, over the window's steps."""
from lib.devtrace import length, op_name, subtract, union

COLLECTIVES = ("reduce-scatter", "all-gather", "all-reduce")


def read(ctx):
    steps = ctx.work.get("traced_steps", 0)
    if ctx.device is None or not ctx.device.ops or steps <= 0:
        return None
    per_chip, found = [], False
    for evs in ctx.device.ops.values():
        is_coll = [op_name(e.name).startswith(COLLECTIVES) for e in evs]
        found = found or any(is_coll)
        coll = union((e.start, e.end) for e, c in zip(evs, is_coll) if c)
        rest = union((e.start, e.end) for e, c in zip(evs, is_coll) if not c)
        per_chip.append(length(subtract(coll, rest)))
    if not found:
        return None
    return sum(per_chip) / len(per_chip) / 1e6 / steps
