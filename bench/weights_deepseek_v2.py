"""Seeded weights of a DeepSeek-V2 configuration (latent attention,
held experts), made on the device.

As ``weights.py`` does for the llama family: one jitted call lays out
the program's parameter tree in float32 from ``--seed``, and the plain
reference makes the same numbers again one layer at a time
(:func:`layer`).  Every tensor is ``normal(key) * scale`` with ``key =
fold_in(fold_in(seed_key, tensor_index), layer)``, and an expert's
tensors fold in the expert's published index as well, so that a chip's
share holds the same experts as the uncut model would.  The scales are
the program's initialisation's (fan-in ** -0.5; the attention output
also over sqrt(2 * layers); a unit-scale embedding; unit norms).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

TOP = ("embed", "head", "final_norm")
ATTN = ("ln1", "wq", "wkva", "kv_norm", "wkvb", "wo", "ln2")
DENSE = ("w1", "w3", "w2")
EXPERT = ("e_w1", "e_w3", "e_w2")
MOE = ("router",) + EXPERT + ("s_w1", "s_w3", "s_w2")
NAMES = TOP + ATTN + DENSE + MOE
# program tree position of each layer tensor
_IN_TREE = {"ln1": ("ln1", "w"), "wq": ("attn", "wq"),
            "wkva": ("attn", "wkva"), "kv_norm": ("attn", "kv_norm", "w"),
            "wkvb": ("attn", "wkvb"), "wo": ("attn", "wo"),
            "ln2": ("ln2", "w"), "w1": ("mlp", "w1"), "w3": ("mlp", "w3"),
            "w2": ("mlp", "w2"), "router": ("mlp", "router", "w"),
            "e_w1": ("mlp", "experts", "w1"),
            "e_w3": ("mlp", "experts", "w3"),
            "e_w2": ("mlp", "experts", "w2"),
            "s_w1": ("mlp", "shared", "w1"), "s_w3": ("mlp", "shared", "w3"),
            "s_w2": ("mlp", "shared", "w2")}


def dims(c: dict) -> dict:
    """The sizes the weights need, from a configuration file's keys."""
    held = c["held_experts"]
    h = c["num_attention_heads"]
    return {"d": c["hidden_size"], "v": c["vocab_size"], "h": h,
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "r": c["kv_lora_rank"], "vd": c["v_head_dim"],
            "f": c["intermediate_size"], "fe": c["moe_intermediate_size"],
            "fs": c["moe_intermediate_size"] * c["n_shared_experts"],
            "experts": c["published"]["n_routed_experts"],
            "held": c["n_routed_experts"],
            "first": held["chip"] * c["n_routed_experts"],
            "k": c["num_experts_per_tok"],
            "dense": c["first_k_dense_replace"],
            "layers": c["num_hidden_layers"]}


def spec(c: dict) -> Dict[str, Tuple[tuple, float]]:
    """name -> (shape of one layer's tensor, one expert's for experts;
    scale); scale 0 = ones."""
    n = dims(c)
    d, h, r, vd, f, fe, fs = (n["d"], n["h"], n["r"], n["vd"], n["f"],
                              n["fe"], n["fs"])
    q = h * (n["nope"] + n["rope"])
    return {
        "embed": ((n["v"], d), 1.0), "head": ((d, n["v"]), d ** -0.5),
        "final_norm": ((d,), 0.0),
        "ln1": ((d,), 0.0), "wq": ((d, q), d ** -0.5),
        "wkva": ((d, r + n["rope"]), d ** -0.5), "kv_norm": ((r,), 0.0),
        "wkvb": ((r, h * (n["nope"] + vd)), r ** -0.5),
        "wo": ((h * vd, d), (h * vd) ** -0.5 / math.sqrt(2 * n["layers"])),
        "ln2": ((d,), 0.0),
        "w1": ((d, f), d ** -0.5), "w3": ((d, f), d ** -0.5),
        "w2": ((f, d), f ** -0.5),
        "router": ((d, n["experts"]), d ** -0.5),
        "e_w1": ((d, fe), d ** -0.5), "e_w3": ((d, fe), d ** -0.5),
        "e_w2": ((fe, d), fe ** -0.5),
        "s_w1": ((d, fs), d ** -0.5), "s_w3": ((d, fs), d ** -0.5),
        "s_w2": ((fs, d), fs ** -0.5),
    }


def _key(key, name: str, index):
    import jax
    return jax.random.fold_in(jax.random.fold_in(key, NAMES.index(name)),
                              index)


def _tensor(key, name: str, index, shape, scale):
    import jax
    import jax.numpy as jnp
    if scale == 0.0:
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(_key(key, name, index), shape,
                             jnp.float32) * scale


def top(c: dict, key) -> Dict:
    """Embedding, head and final norm."""
    sp = spec(c)
    return {n: _tensor(key, n, 0, *sp[n]) for n in TOP}


def layer(c: dict, key, i, dense: bool) -> Dict:
    """Layer ``i``'s tensors (``i`` may be traced); an expert layer's
    expert tensors stacked over the held experts, in published order."""
    import jax
    import jax.numpy as jnp
    sp, n = spec(c), dims(c)
    out = {}
    for name in ATTN + (DENSE if dense else MOE):
        shape, scale = sp[name]
        if name in EXPERT:
            ids = n["first"] + jnp.arange(n["held"])
            k = _key(key, name, i)
            out[name] = jax.vmap(lambda e: jax.random.normal(
                jax.random.fold_in(k, e), shape, jnp.float32) * scale)(ids)
        else:
            out[name] = _tensor(key, name, i, shape, scale)
    return out


def _block(tensors: Dict) -> Dict:
    tree: Dict = {}
    for name, x in tensors.items():
        *path, leaf = _IN_TREE[name]
        node = tree
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def program_tree(c: dict, key) -> Dict:
    """All weights in the program's parameter layout: the leading dense
    layers unscanned (``prefix``), the expert layers scanned (tensors
    stacked to ``(layers, 1, ...)``).  Call under ``jax.jit``."""
    import jax
    import jax.numpy as jnp
    n = dims(c)
    prefix = [_block(layer(c, key, i, True)) for i in range(n["dense"])]
    stacked = jax.vmap(lambda i: layer(c, key, i, False))(
        jnp.arange(n["dense"], n["layers"]))
    t = top(c, key)
    return {"embed": {"w": t["embed"]}, "head": {"w": t["head"]},
            "final_norm": {"w": t["final_norm"]}, "prefix": prefix,
            "cycles": {"g0": _block({k: v[:, None]
                                     for k, v in stacked.items()})}}
