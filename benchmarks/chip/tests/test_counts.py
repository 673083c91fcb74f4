"""FLOP counts: by hand at small shapes, and against the program's
``core/flops.py`` at the configurations' full shapes."""
import pytest

import harness

counts = harness.load_module("counts", "model.py")
ref = harness.load_module("reference.py")


def test_forward_flops_by_hand():
    d = {"E": 8, "H": 2, "D": 4, "F": 16, "L": 3, "V": 100, "Vpad": 128, "causal": False}
    # per layer q,k,v,o: 4 * 8*8 = 256 weights; mlp 2 * 8*16 = 256; unembed 128*8
    params = 3 * (256 + 256) + 1024
    assert counts.matmul_params(d) == params
    # 2 sequences of 5 tokens; encoder attends to all 5 keys: 2 products of
    # H*D per key per layer
    attn = 10 * 3 * 2 * 2 * (2 * 4) * 5
    assert counts.forward_flops(d, 2, 5) == 2 * params * 10 + attn
    assert counts.train_step_flops(d, 2, 5) == 3 * (2 * params * 10 + attn)
    causal = dict(d, causal=True)
    assert counts.forward_flops(causal, 2, 5) == 2 * params * 10 + attn / 2


# GPT-2 small (HF openai-community/gpt2) in the program's registry entry
GPT2 = {"registry": "gpt-base", "overrides": {}, "n_embd": 768, "n_layer": 12, "n_head": 12,
        "n_inner": None, "vocab_size": 50257}


@pytest.mark.parametrize("name,rows,seq", [("bert-large", 64, 128), ("gpt-base", 16, 1024)])
def test_matches_program_flops_at_full_size(name, rows, seq):
    from repro.core import flops
    from repro.models.api import build_model

    layout = harness.load_module("layout.py")
    c = GPT2 if name == "gpt-base" else harness.load_json("configs", name + ".json")
    cfg = layout.program_config(c)
    want = flops.train_step_flops(cfg, build_model(cfg).specs(), rows, seq)
    got = counts.train_step_flops(ref.dims(c), rows, seq)
    # core/flops.py also counts the stacked bias and LayerNorm vectors as
    # matrix weights; they are under 0.1% of the total
    assert got == pytest.approx(want, rel=1e-3)
    assert got < want

