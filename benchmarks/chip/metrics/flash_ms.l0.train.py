"""Device time per level-0 train step in the three Pallas flash-attention
kernels (``flash_attention_fwd``, ``_dq``, ``_dkv``), in ms; nothing where
the step runs none of them."""


def read(ctx):
    f = ctx.counters.get("flash")
    if not f or not f["steps"] or not sum(f["calls"].values()):
        return None
    return sum(f["ms"].values()) / f["steps"]
