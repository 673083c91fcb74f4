"""Causal-LM batches from the Markov chain of ``traffic/mlm.py``, made on
the device from the seed in one jitted call: ``rows x seq`` tokens per
batch, each labelled with the token that follows it; the last position of a
row has no successor and is labelled -1."""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

import harness

mlm = harness.load_module("traffic", "mlm.py")


@functools.partial(jax.jit, static_argnames=("n", "rows", "seq", "vocab", "branch"))
def batches(key, *, n: int, rows: int, seq: int, vocab: int,
            branch: int = 4) -> Dict[str, jax.Array]:
    """``n`` batches stacked on a leading axis: tokens and labels [n, rows, seq]."""
    toks = mlm.batches(key, n=n, rows=rows, seq=seq, vocab=vocab, mask_id=0,
                       mask_rate=0.0, branch=branch)["tokens"]
    last = jnp.full(toks.shape[:-1] + (1,), -1, jnp.int32)
    return {"tokens": toks, "labels": jnp.concatenate([toks[..., 1:], last], -1)}
