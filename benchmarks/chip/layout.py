"""The program's parameter tree <-> the reference's layout.

The program keeps one stage of stacked blocks: ``embed/tok``,
``stages/stage_0/b0/{norm1,mixer,norm2,ffn}/...`` and ``final_norm``.  This
is the only file that knows those names; weights themselves are always made
by ``reference.init_params`` from the seed.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

BLOCK = {"ln1_s": ("norm1", "scale"), "ln1_b": ("norm1", "bias"),
         "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
         "bq": ("mixer", "bq"), "bk": ("mixer", "bk"), "bv": ("mixer", "bv"),
         "wo": ("mixer", "wo"), "bo": ("mixer", "bo"),
         "ln2_s": ("norm2", "scale"), "ln2_b": ("norm2", "bias"),
         "w_up": ("ffn", "w_up"), "b_up": ("ffn", "b_up"),
         "w_down": ("ffn", "w_down"), "b_down": ("ffn", "b_down")}


def to_program(r: Dict) -> Dict:
    block: Dict[str, Dict] = {}
    for name, (group, leaf) in BLOCK.items():
        block.setdefault(group, {})[leaf] = r["layers"][name]
    return {"embed": {"tok": r["tok"]}, "stages": {"stage_0": {"b0": block}},
            "final_norm": {"scale": r["lnf_s"], "bias": r["lnf_b"]}}


def from_program(p: Dict) -> Dict:
    block = p["stages"]["stage_0"]["b0"]
    return {"tok": p["embed"]["tok"], "lnf_s": p["final_norm"]["scale"],
            "lnf_b": p["final_norm"]["bias"],
            "layers": {name: block[g][leaf] for name, (g, leaf) in BLOCK.items()}}


def check_shapes(program_specs, ref_shapes) -> None:
    """Raises unless the program's Spec tree has exactly the reference's
    leaves and shapes."""
    import jax

    want = jax.tree.map(lambda s: tuple(s.shape), to_program(ref_shapes),
                        is_leaf=lambda x: hasattr(x, "shape"))
    got = jax.tree.map(lambda s: tuple(s.shape), program_specs,
                       is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes"))
    if want != got:
        raise ValueError(f"program parameter tree differs from the reference's:\n{got}\n{want}")


def leaves(r: Dict) -> Dict[str, np.ndarray]:
    out = {"tok": r["tok"], "lnf_s": r["lnf_s"], "lnf_b": r["lnf_b"]}
    out.update({f"layers/{n}": v for n, v in r["layers"].items()})
    return out


def program_config(config: Dict):
    """The program's ModelConfig: the registry entry at the file's sizes,
    with the file's overrides."""
    from repro.config import uniform_stages
    from repro.configs import get_config

    E, H, F, L, V = (config.get(k) or config.get(j) for k, j in (
        ("hidden_size", "n_embd"), ("num_attention_heads", "n_head"),
        ("intermediate_size", "n_inner"), ("num_hidden_layers", "n_layer"),
        ("vocab_size", "vocab_size")))
    cfg = get_config(config["registry"])
    return cfg.replace(d_model=E, n_heads=H, n_kv_heads=H, d_ff=F or 4 * E, vocab_size=V,
                       stages=uniform_stages(L, cfg.stages[0].pattern[0]),
                       **config["overrides"])
