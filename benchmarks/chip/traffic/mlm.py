"""Masked-LM batches from a sparse first-order Markov chain, made on the
device from the seed in one jitted call.

The chain is the idea of the program's ``data/synthetic.py::MarkovLM`` (each
token has ``branch`` likely successors), rebuilt here from the seed so that
the benchmark owns its data: ``rows x seq`` tokens per batch, ``mask_rate`` of
positions replaced by ``mask_id`` and labelled, every other label -1.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "rows", "seq", "vocab", "mask_id",
                                             "mask_rate", "branch"))
def batches(key, *, n: int, rows: int, seq: int, vocab: int, mask_id: int,
            mask_rate: float, branch: int = 4) -> Dict[str, jax.Array]:
    """``n`` batches stacked on a leading axis: tokens and labels [n, rows, seq]."""
    k_succ, k_prob, k_draw = jax.random.split(key, 3)
    succ = jax.random.randint(k_succ, (vocab, branch), 0, vocab)
    logit = jax.random.normal(k_prob, (vocab, branch))

    def one(k):
        k0, k1, k2 = jax.random.split(k, 3)
        tok0 = jax.random.randint(k0, (rows,), 0, vocab)

        def step(tok, kk):
            nxt = succ[tok, jax.random.categorical(kk, logit[tok])]
            return nxt, nxt

        _, rest = jax.lax.scan(step, tok0, jax.random.split(k1, seq - 1))
        toks = jnp.concatenate([tok0[None], rest], 0).T
        mask = jax.random.bernoulli(k2, mask_rate, toks.shape)
        return {"tokens": jnp.where(mask, mask_id, toks).astype(jnp.int32),
                "labels": jnp.where(mask, toks, -1).astype(jnp.int32)}

    return jax.vmap(one)(jax.random.split(k_draw, n))
