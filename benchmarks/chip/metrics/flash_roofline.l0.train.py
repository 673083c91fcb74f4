"""Share of their roofline that the level-0 steps' flash-attention kernels
reach, in %: the least time of the attention work they did (``counts/
flash.py``: per call the larger of its bytes over the HBM bandwidth and its
FLOPs over the bf16 peak; a forward per ``flash_attention_fwd`` call, a
backward per ``_dq`` and ``_dkv`` pair), summed over the window's calls,
over those calls' device time.  Nothing where the step runs none of them."""
import harness

flash = harness.load_module("counts", "flash.py")


def read(ctx):
    f = ctx.counters.get("flash")
    if not f or not sum(f["calls"].values()):
        return None
    calls = f["calls"]
    if calls["flash_attention_dq"] != calls["flash_attention_dkv"]:
        raise ValueError(f"unpaired backward kernels: {calls}")
    least = (calls["flash_attention_fwd"] * flash.bound_s(flash.forward(**f["shape"]), ctx.peaks)
             + calls["flash_attention_dq"] * flash.bound_s(flash.backward(**f["shape"]), ctx.peaks))
    return 100.0 * least / (1e-3 * sum(f["ms"].values()))
