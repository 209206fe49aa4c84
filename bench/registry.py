"""The program's own configuration for a configuration file, and the
seeded weights placed as the program takes them."""
from __future__ import annotations

import dataclasses

import weights as W

# program ModelConfig field -> configuration file key
_SAME = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
         "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
         "vocab": "vocab_size", "n_layers": "num_hidden_layers",
         "hd": "head_dim", "norm_eps": "rms_norm_eps",
         "rope_theta": "rope_theta"}


def program_config(c: dict):
    """The registry entry ``c["registry"]``, cut as ``c["program"]``
    says, after checking that it runs the file's sizes."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(c["registry"]),
                              **c["program"]["overrides"])
    differ = {a: (getattr(cfg, a), c[b]) for a, b in _SAME.items()
              if getattr(cfg, a) != c[b]}
    if differ or cfg.act != "swiglu" or cfg.tie_embeddings \
            or cfg.cycle != ("attn",) or cfg.prefix_kinds:
        raise ValueError(f"{c['name']}: the program's {c['registry']} "
                         f"does not run the file's model: {differ}")
    return cfg


def place_params(c: dict, key, cfg, pc, shardings):
    """The weights of ``c`` from ``key``, made on the device in one
    jitted call, in the program's layout and placement."""
    import jax
    from repro.models.model import param_shapes
    want, _ = param_shapes(cfg, pc)
    got = jax.eval_shape(lambda k: W.program_tree(c, k), key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError(f"{c['name']}: seeded weights do not match the "
                         f"program's parameter tree")
    return jax.jit(lambda k: W.program_tree(c, k),
                   out_shardings=shardings)(key)
