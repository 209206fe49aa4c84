"""Plain reference of the llama-family decoder that granite-8b (code)
is: pre-norm blocks of RMSNorm, grouped-query causal attention with
rotary positions (the two halves of each head rotated), and a SwiGLU
MLP; a final RMSNorm and an untied head.

Straight ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no cache, no batching tricks, no kernels.  It
imports nothing of the program and makes its weights itself from the
seed (``bench/weights.py``), one layer at a time, so that it fits on a
chip whose program state has been freed.

``precision="fp8"`` is the control: the same arithmetic with both
operands of every matrix product rounded to float8 (e4m3 forward, e5m2
for gradients, each tensor scaled by its own largest magnitude), the
step below the bfloat16 that the configuration states.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    big = jnp.finfo(dtype).max.astype(jnp.float32)
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / big
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x):
    return _round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _round(x, jnp.float8_e4m3fn), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(a, b, precision: str):
    return ein("...k,kn->...n", a, b, precision)


def ein(spec: str, a, b, precision: str):
    """A product of two operands, both rounded to float8 under the
    control's precision."""
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (..., S, H, hd); pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]     # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, precision: str, q_chunk: int = 256):
    """Causal grouped-query attention.  q (B, S, H, hd), k/v (B, S, Hkv,
    hd); query blocks of ``q_chunk`` rows, each recomputed in the
    backward pass."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    q_chunk = min(q_chunk, S)
    n = S // q_chunk
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        qc, start = args                             # (B, c, H, hd)
        s = ein("bqhd,bkhd->bhqk", qc, k, precision) * hd ** -0.5
        qpos = start + jnp.arange(q_chunk)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision)

    qs = q.reshape(B, n, q_chunk, H, hd).swapaxes(0, 1)
    out = jax.lax.map(one, (qs, jnp.arange(n) * q_chunk))
    return out.swapaxes(0, 1).reshape(B, S, H, hd)


def block(x, w, c: dict, precision: str):
    """One decoder layer over x (B, S, d), positions 0..S-1."""
    B, S, d = x.shape
    n = W.dims(c)
    hd = n["q"] // c["num_attention_heads"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(S)
    h = rmsnorm(x, w["ln1"], eps)
    q = mm(h, w["wq"], precision).reshape(B, S, -1, hd)
    k = mm(h, w["wk"], precision).reshape(B, S, -1, hd)
    v = mm(h, w["wv"], precision).reshape(B, S, -1, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    o = attention(q, k, v, precision).reshape(B, S, -1)
    x = x + mm(o, w["wo"], precision)
    h = rmsnorm(x, w["ln2"], eps)
    g = jax.nn.silu(mm(h, w["w1"], precision)) * mm(h, w["w3"], precision)
    return x + mm(g, w["w2"], precision)


def _frozen(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


# ---------------------------------------------------------------- serving
@lru_cache(maxsize=None)
def _serve_fns(cf: tuple, precision: str):
    c = dict(cf)

    @jax.jit
    def embed(key, tokens):
        return W.top(c, key)["embed"][tokens]

    @jax.jit
    def run_layer(key, i, x):
        return block(x, W.layer(c, key, i), c, precision)

    @jax.jit
    def score(key, x, rows, cols, tokens):
        """At (rows, cols): the best logit, the logit of each token set
        in ``tokens`` (k, n), and the argmax."""
        t = W.top(c, key)
        h = rmsnorm(x[rows, cols], t["final_norm"], c["rms_norm_eps"])
        lg = mm(h, t["head"], precision)                   # (n, vocab)
        picked = jnp.take_along_axis(lg[None], tokens[..., None], -1)[..., 0]
        return lg.max(-1), picked, lg.argmax(-1)

    return embed, run_layer, score


def serve_scores(c: dict, key, seqs: Sequence[np.ndarray],
                 positions: Sequence[np.ndarray],
                 tokens: Sequence[np.ndarray], *, seq_len: int,
                 max_positions: int, precision: str = "float32",
                 group: int = 2) -> List[Tuple[np.ndarray, ...]]:
    """Full, cache-free passes over ``seqs``.  For sequence ``j``, at
    each of ``positions[j]``: the best next-token logit, the logits of
    the token sets ``tokens[j]`` (``(k, len(positions[j]))``), and the
    argmax.  Every call has one shape: sequences are padded to
    ``seq_len`` and run ``group`` at a time, layer by layer (causal
    attention keeps the padding from the scored positions), and the
    positions of each are padded to ``max_positions``."""
    embed, run_layer, score = _serve_fns(_frozen(c), precision)
    n = len(seqs)
    groups = []
    for g in range(0, n, group):
        toks = np.zeros((group, seq_len), np.int32)
        for j, s in enumerate(seqs[g:g + group]):
            toks[j, :len(s)] = s
        groups.append(embed(key, toks))
    for i in range(c["num_hidden_layers"]):
        groups = [run_layer(key, i, x) for x in groups]
    out = []
    for j in range(n):
        m = len(positions[j])
        tk = np.asarray(tokens[j], np.int32).reshape(-1, m)
        pos = np.zeros(max_positions, np.int32)
        pos[:m] = positions[j]
        sets = np.zeros((tk.shape[0], max_positions), np.int32)
        sets[:, :m] = tk
        best, picked, top = score(key, groups[j // group],
                                  np.full(max_positions, j % group, np.int32),
                                  pos, sets)
        out.append((np.asarray(best)[:m], np.asarray(picked)[:, :m],
                    np.asarray(top)[:m]))
    return out


# ---------------------------------------------------------------- training
def init_params(c: dict, key) -> Dict:
    """Plain ``name -> array``: layer tensors stacked on a leading axis."""
    L = c["num_hidden_layers"]
    stacked = jax.vmap(lambda i: W.layer(c, key, i))(jnp.arange(L))
    return dict(W.top(c, key), **stacked)


def loss_sum(p: Dict, tokens, labels, c: dict, precision: str,
             chunk: int = 1024):
    """Summed next-token cross entropy over (B, S) tokens."""
    x = p["embed"][tokens]
    for i in range(c["num_hidden_layers"]):
        x = block(x, {n: p[n][i] for n in W.LAYER}, c, precision)
    x = rmsnorm(x, p["final_norm"], c["rms_norm_eps"])
    B, S, d = x.shape
    chunk = min(chunk, S)
    xs = x.reshape(B, S // chunk, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, S // chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def ce(args):
        xc, lc = args
        lg = mm(xc, p["head"], precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
        return jnp.sum(lse - picked)

    return jnp.sum(jax.lax.map(ce, (xs, ls)))


def lr_at(oc: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    warm = min(step / max(oc["warmup_steps"], 1), 1.0)
    prog = min(max((step - oc["warmup_steps"])
                   / max(oc["total_steps"] - oc["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + np.cos(np.pi * prog))
    return oc["lr"] * warm * (oc["min_lr_ratio"]
                              + (1 - oc["min_lr_ratio"]) * cos)


def train_reference(c: dict, oc: dict, key, batches: Sequence[Tuple],
                    precision: str = "float32", devices=None):
    """AdamW over ``batches`` (each ``(tokens, labels)`` of the global
    batch) from the seeded weights.  Rows are split over ``devices``.

    Returns (losses per step, per-tensor norm of the clipped gradient
    that the first step's update used, per-tensor norm of the weights'
    change after the last step)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = devices or jax.devices()[:1]
    mesh = Mesh(np.asarray(devices), ("rows",))
    rows = NamedSharding(mesh, P("rows", None))
    repl = NamedSharding(mesh, P())

    p = jax.jit(lambda k: init_params(c, k), out_shardings=repl)(key)

    @jax.jit
    def grad(p, tokens, labels):
        n = tokens.size
        loss, g = jax.value_and_grad(
            lambda q: loss_sum(q, tokens, labels, c, precision) / n)(p)
        norm = jnp.sqrt(sum(jnp.sum(v * v) for v in g.values()))
        if oc.get("grad_clip") is not None:
            s = jnp.minimum(1.0, oc["grad_clip"] / jnp.maximum(norm, 1e-9))
            g = {k: v * s for k, v in g.items()}
        return loss, g

    @partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, lr, t):
        b1, b2 = oc["b1"], oc["b2"]
        out = {}, {}, {}
        for k in p:
            mk = b1 * m[k] + (1 - b1) * g[k]
            vk = b2 * v[k] + (1 - b2) * g[k] * g[k]
            upd = (mk / (1 - b1 ** t)) / (jnp.sqrt(vk / (1 - b2 ** t))
                                          + oc["eps"]) \
                + oc["weight_decay"] * p[k]
            out[0][k], out[1][k], out[2][k] = p[k] - lr * upd, mk, vk
        return out

    norms = jax.jit(lambda t: {k: jnp.linalg.norm(v.ravel())
                               for k, v in t.items()})
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, gnorm = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, g = grad(p, jax.device_put(tokens, rows),
                       jax.device_put(labels, rows))
        if t == 1:
            gnorm = {k: float(x) for k, x in norms(g).items()}
        p, m, v = adam(p, g, m, v, np.float32(lr_at(oc, t)), np.float32(t))
        losses.append(float(loss))
        del g
    # the weights the steps started from, made again from the seed
    delta = jax.jit(lambda a, k0: norms(
        jax.tree.map(jnp.subtract, a, init_params(c, k0))))(p, key)
    return losses, gnorm, {k: float(x) for k, x in delta.items()}
