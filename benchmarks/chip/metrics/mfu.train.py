"""Model FLOPs of the train steps completed in the traced window (``counts/
model.py``: 3 x forward, no recomputation) over the trace's window and the
chip's bf16 peak, in %."""


def read(ctx):
    levels, flops = ctx.counters.get("levels"), ctx.counters.get("step_flops")
    if not levels:
        return None
    done = sum(flops[lv] for lv in levels)
    return 100.0 * done / ctx.tracer.summary["window_s"] / ctx.peaks["bf16_flops_per_s"]
