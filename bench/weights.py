"""Seeded weights of a llama-family configuration, made on the device.

The benchmark, not the program, makes the weights: one jitted call from
``--seed`` lays them out as the program's parameter tree, in float32 as
the program keeps its master copy.  The plain reference makes the same
numbers again from the same seed one layer at a time
(:func:`layer`), so it takes nothing that the program made.

Every tensor is ``normal(key) * scale`` with ``key = fold_in(fold_in(
seed_key, tensor_index), layer)``: a tensor's values do not depend on
how many layers are made together.  The scales are the program's
initialisation's (fan-in ** -0.5; the attention output also over
sqrt(2 * layers); a unit-scale embedding; unit norms).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

TOP = ("embed", "head", "final_norm")
LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3", "w2")
# program tree position of each layer tensor: (block, leaf)
_IN_TREE = {"ln1": ("ln1", "w"), "wq": ("attn", "wq"), "wk": ("attn", "wk"),
            "wv": ("attn", "wv"), "wo": ("attn", "wo"), "ln2": ("ln2", "w"),
            "w1": ("mlp", "w1"), "w3": ("mlp", "w3"), "w2": ("mlp", "w2")}


def dims(c: dict) -> dict:
    """The sizes the weights need, from a configuration file's keys."""
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    return {"d": d, "f": c["intermediate_size"], "v": c["vocab_size"],
            "q": h * hd, "kv": c["num_key_value_heads"] * hd,
            "layers": c["num_hidden_layers"]}


def spec(c: dict) -> Dict[str, Tuple[tuple, float]]:
    """name -> (shape of one layer's tensor, scale); scale 0 = ones."""
    n = dims(c)
    d, f, v, q, kv = n["d"], n["f"], n["v"], n["q"], n["kv"]
    return {
        "embed": ((v, d), 1.0), "head": ((d, v), d ** -0.5),
        "final_norm": ((d,), 0.0),
        "ln1": ((d,), 0.0), "wq": ((d, q), d ** -0.5),
        "wk": ((d, kv), d ** -0.5), "wv": ((d, kv), d ** -0.5),
        "wo": ((q, d), q ** -0.5 / math.sqrt(2 * n["layers"])),
        "ln2": ((d,), 0.0), "w1": ((d, f), d ** -0.5),
        "w3": ((d, f), d ** -0.5), "w2": ((f, d), f ** -0.5),
    }


def _tensor(key, name: str, index, shape, scale):
    import jax
    import jax.numpy as jnp
    if scale == 0.0:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(
        key, (TOP + LAYER).index(name)), index)
    return jax.random.normal(k, shape, jnp.float32) * scale


def top(c: dict, key) -> Dict:
    """Embedding, head and final norm."""
    sp = spec(c)
    return {n: _tensor(key, n, 0, *sp[n]) for n in TOP}


def layer(c: dict, key, i) -> Dict:
    """Layer ``i``'s tensors (``i`` may be traced)."""
    sp = spec(c)
    return {n: _tensor(key, n, i, *sp[n]) for n in LAYER}


def program_tree(c: dict, key) -> Dict:
    """All weights in the program's parameter layout (a dense llama
    block cycle of one kind, scanned: layer tensors stacked to
    ``(layers, 1, ...)``).  Call under ``jax.jit``."""
    import jax
    import jax.numpy as jnp
    L = dims(c)["layers"]
    stacked = jax.vmap(lambda i: layer(c, key, i))(jnp.arange(L))
    t = top(c, key)
    g0: Dict = {}
    for n in LAYER:
        block, leaf = _IN_TREE[n]
        g0.setdefault(block, {})[leaf] = stacked[n][:, None]
    return {"embed": {"w": t["embed"]}, "head": {"w": t["head"]},
            "final_norm": {"w": t["final_norm"]}, "prefix": [],
            "cycles": {"g0": g0}}


def named(tree) -> Dict:
    """The program-layout tree as ``name -> array`` (layer tensors keep
    their stacked layer axis)."""
    out = {n: tree[n]["w"] for n in TOP}
    g0 = tree["cycles"]["g0"]
    for n in LAYER:
        block, leaf = _IN_TREE[n]
        out[n] = g0[block][leaf]
    return out
