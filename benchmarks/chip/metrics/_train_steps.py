"""Shared by the ``step_ms.l<n>.train`` readers: the window's train-step
programs, in the order they ran on the device, are the steps the window
dispatched, in the order it dispatched them (``ctx.counters["levels"]``)."""


def mean_ms(ctx, level: int):
    levels = ctx.counters.get("levels")
    if not levels:
        return None
    progs = [p for p in ctx.tracer.summary["programs"] if "train_step" in p[0]]
    if len(progs) != len(levels):
        names = sorted({p[0] for p in ctx.tracer.summary["programs"]})
        raise ValueError(f"{len(progs)} train-step programs in the trace for "
                         f"{len(levels)} dispatched steps; programs seen: {names}")
    t = [p[2] for p, lv in zip(progs, levels) if lv == level]
    return 1e3 * sum(t) / len(t) if t else None
