"""Device time per level-0 train step (``VCycleRunner.step_fn(0)``), in ms."""
import harness

_steps = harness.load_module("metrics", "_train_steps.py")


def read(ctx):
    return _steps.mean_ms(ctx, 0)
