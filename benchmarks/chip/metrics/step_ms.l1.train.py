"""Device time per level-1 (coalesced) train step (``step_fn(1)``), in ms."""
import harness

_steps = harness.load_module("metrics", "_train_steps.py")


def read(ctx):
    return _steps.mean_ms(ctx, 1)
