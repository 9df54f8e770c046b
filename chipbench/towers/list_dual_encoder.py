"""How the program runs the ``list_dual_encoder`` reference's tower.

The program's ``list-dual-encoder`` configuration at the widths the
configuration's ``model`` block states, and the reference's weights
(``references/list_dual_encoder.py``) in the program's pytree layout.
"""
from __future__ import annotations

import dataclasses


def program_config(model):
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(model["arch"]),
        n_layers=model["n_layers"], d_model=model["d_model"],
        n_heads=model["n_heads"], d_ff=model["d_ff"],
        vocab_size=model["vocab_size"], max_len=model["max_len"],
        norm_eps=model["norm_eps"], param_dtype=model["param_dtype"],
        compute_dtype=model["compute_dtype"], spatial_t=model["spatial_t"],
        n_clusters=model["n_clusters"],
        index_mlp_hidden=tuple(model["index_mlp_hidden"]))


def _dense(w, b):
    return {"w": w, "b": b}


def program_params(w):
    """The benchmark's weights in the program's pytree layout (the same
    device arrays, no copy). → (rel_params, index_params, norm)."""
    import jax.numpy as jnp
    ly = w["layers"]
    tower = {
        "embed": w["tok_emb"], "pos_embed": w["pos_emb"],
        "blocks": {
            "ln1": {"scale": ly["ln1_g"], "bias": ly["ln1_b"]},
            "ln2": {"scale": ly["ln2_g"], "bias": ly["ln2_b"]},
            "attn": {n: _dense(ly[n], ly["b" + n[1]])
                     for n in ("wq", "wk", "wv", "wo")},
            "mlp": {"w1": _dense(ly["w1"], ly["b1"]),
                    "w2": _dense(ly["w2"], ly["b2"])}},
        "final_ln": {"scale": w["lnf_g"], "bias": w["lnf_b"]},
        "cls": _dense(w["cls_w"], w["cls_b"]),
    }
    rel = {"q_enc": tower, "o_enc": tower,
           "weight_mlp": [_dense(*p) for p in w["weight_mlp"]],
           "fixed_w": jnp.ones((2,), jnp.float32),
           "spatial": {"w_s": w["w_s"]}}
    index = {"mlp": [_dense(*p) for p in w["router"]]}
    norm = {"lo": jnp.zeros((2,), jnp.float32),
            "span": jnp.ones((2,), jnp.float32)}
    return rel, index, norm
