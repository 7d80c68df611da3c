"""Plain reference of ``qwen1_5_0_5b_train``: Qwen2 (Qwen1.5) training
with AdamW, in float32 at the ``highest`` matmul precision.

It follows the published architecture (``Qwen2ForCausalLM``): token
embedding; per layer RMSNorm, causal multi-head attention with q/k/v
biases and rotary embedding (rotate-half, base ``rope_theta``), residual,
RMSNorm, SwiGLU MLP, residual; a final RMSNorm and the output head.  The
configuration's departures hold here too: an untied output head, the
softmax over the padded vocabulary, and weight decay on every leaf stored
with rank >= 2.  The weights are laid out as the program keeps them (the
layers stacked on a leading axis), since the benchmark makes them once
for both.

``init_weights`` makes the weights from a key in one jitted call, in the
type they are served in.  ``train_steps`` runs the reference; with
``matmul="fp8"`` every matrix product's operands are rounded to
float8_e4m3 first (the control); ``rows`` keeps the first rows of each
batch only (a planted fault).  Memory: the float32 parameters, both Adam
moments and one gradient (about 10 GB at the published sizes), with each
layer and each loss chunk rematerialized.
"""

from __future__ import annotations

import json
import math
from functools import partial
from typing import Any, Callable, Dict, Sequence

import jax
import jax.numpy as jnp
from jax import lax

Tree = Dict[str, Any]
LOSS_CHUNK = 512


def _shapes(cfg: Dict[str, Any]) -> Tree:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    L, V = cfg["num_hidden_layers"], cfg["padded_vocab_size"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((V, d), bf, "embed"),
        "final_norm": {"scale": ((d,), f32, "scale")},
        "lm_head": ((V, d), bf, "head"),
        "layers": {
            "attn": {
                "wq": ((L, d, h * hd), bf, "w"), "wk": ((L, d, kv * hd), bf, "w"),
                "wv": ((L, d, kv * hd), bf, "w"), "wo": ((L, h * hd, d), bf, "w"),
                "bq": ((L, h * hd), f32, "bias"), "bk": ((L, kv * hd), f32, "bias"),
                "bv": ((L, kv * hd), f32, "bias"),
            },
            "ln1": {"scale": ((L, d), f32, "scale")},
            "ln2": {"scale": ((L, d), f32, "scale")},
            "mlp": {"w_gate": ((L, d, f), bf, "w"), "w_up": ((L, d, f), bf, "w"),
                    "w_down": ((L, f, d), bf, "w")},
        },
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def init_params(key, cfg: Dict[str, Any]) -> Tree:
    """Random weights (see the configuration's ``assumed``); call jitted."""
    specs = _shapes(cfg)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    d = cfg["hidden_size"]

    def one(k, spec):
        shape, dtype, kind = spec
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "w":
            v = z / math.sqrt(shape[-2])
        elif kind == "embed":
            v = z
        elif kind == "head":
            v = z / math.sqrt(d)
        elif kind == "scale":
            v = 1.0 + 0.1 * z
        else:
            v = 0.1 * z
        return v.astype(dtype)

    return jax.tree.unflatten(treedef, [one(k, s) for k, s in
                                        zip(keys, leaves)])


@jax.custom_vjp
def _round_fp8(x):
    """``x`` rounded to float8_e4m3 (no scaling); the gradient passes
    straight through, so the backward products take the rounded forward
    operands against float32 cotangents."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


_round_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


def _dot(matmul: str):
    hi = lax.Precision.HIGHEST
    if matmul == "float32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=hi)
    if matmul == "fp8":
        return lambda spec, a, b: jnp.einsum(
            spec, _round_fp8(a), _round_fp8(b), precision=hi)
    raise ValueError(f"matmul must be float32 or fp8, got {matmul!r}")


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, H, hd), rotate-half with positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, dot, h, p):
    B, S, d = h.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    eps = cfg["rms_norm_eps"]
    x = _rms(h, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = (dot("bsd,de->bse", x, a["wq"]) + a["bq"]).reshape(B, S, H, hd)
    k = (dot("bsd,de->bse", x, a["wk"]) + a["bk"]).reshape(B, S, KV, hd)
    v = (dot("bsd,de->bse", x, a["wv"]) + a["bv"]).reshape(B, S, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = dot("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    h = h + dot("bse,ed->bsd", o.reshape(B, S, H * hd), a["wo"])
    x = _rms(h, p["ln2"]["scale"], eps)
    m = p["mlp"]
    g = jax.nn.silu(dot("bsd,df->bsf", x, m["w_gate"]))
    u = dot("bsd,df->bsf", x, m["w_up"])
    return h + dot("bsf,fd->bsd", g * u, m["w_down"])


def loss_fn(params: Tree, tokens, labels, cfg: Dict[str, Any],
            matmul: str = "float32"):
    """Mean next-token NLL over every position (softmax over the padded
    vocabulary, as the configuration states)."""
    dot = _dot(matmul)
    h = params["embed"][tokens]

    def body(h, p):
        return jax.checkpoint(partial(_layer, cfg, dot))(h, p), None

    h, _ = lax.scan(body, h, params["layers"])
    h = _rms(h, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    B, S, d = h.shape
    n = S // LOSS_CHUNK
    hc = jnp.moveaxis(h.reshape(B, n, LOSS_CHUNK, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, LOSS_CHUNK), 1, 0)

    def chunk(acc, inp):
        x, lab = inp
        logits = dot("btd,vd->btv", x, params["lm_head"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], -1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    nll, _ = lax.scan(jax.checkpoint(chunk), jnp.zeros((), jnp.float32),
                      (hc, lc))
    return nll / (B * S)


def _lr(opt: Dict[str, Any], t):
    t = t.astype(jnp.float32)
    warm = opt["lr"] * t / max(opt["warmup_steps"], 1)
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    cos = opt["lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"])
                       * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(t < opt["warmup_steps"], warm, cos)


def _leaf_norms(tree: Tree) -> Tree:
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def _adamw_step(cfg, opt, matmul, params, mu, nu, tokens, labels, t):
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, labels, cfg,
                                          matmul)
    graw = _leaf_norms(g)
    gnorm = jnp.sqrt(sum(jnp.square(x) for x in jax.tree.leaves(graw)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    b1, b2 = opt["b1"], opt["b2"]
    tf = t.astype(jnp.float32)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf
    lr = _lr(opt, t)
    # the clipped gradient is g * scale, folded into the moments so that
    # no second gradient-sized tree is held
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * (x * scale), mu, g)
    nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * jnp.square(x * scale),
                      nu, g)

    def upd(p, m, n):
        decay = opt["weight_decay"] if p.ndim >= 2 else 0.0
        return p - lr * ((m / bc1) / (jnp.sqrt(n / bc2) + opt["eps"])
                         + decay * p)

    params = jax.tree.map(upd, params, mu, nu)
    gclip = jax.tree.map(lambda x: x * scale, graw)
    return params, mu, nu, loss, gclip, graw


def _delta_norms(cfg, params, p0):
    return jax.tree.map(lambda p, q: jnp.sqrt(jnp.sum(jnp.square(
        p - q.astype(jnp.float32)))), params, p0)


_JITTED: Dict[str, Callable] = {}


def _jit(fn, cfg: Dict[str, Any], *static, **jit_kw) -> Callable:
    """``fn`` bound to ``cfg`` and ``static``, jitted once per process."""
    k = json.dumps([fn.__name__, cfg, static], sort_keys=True)
    if k not in _JITTED:
        _JITTED[k] = jax.jit(partial(fn, cfg, *static), **jit_kw)
    return _JITTED[k]


def _init(cfg, key):
    return init_params(key, cfg)


def init_weights(key, cfg: Dict[str, Any]) -> Tree:
    """The weights of ``key``, always from one compiled program: a second
    compile of the same computation may round differently on the chip,
    and the parameters' change is measured against these."""
    return _jit(_init, cfg)(key)


def train_steps(key, batches: Sequence[Dict[str, Any]], cfg: Dict[str, Any],
                opt: Dict[str, Any], matmul: str = "float32",
                rows: int = 0) -> Dict[str, Any]:
    """Run ``len(batches)`` AdamW steps from ``init_params(key)``.

    Returns the loss of each step, the per-leaf norms of the first
    gradient as the optimizer takes it (after clipping) and before
    clipping, and the per-leaf norms of the parameters' change."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          init_weights(key, cfg))
    zeros = jax.tree.map(jnp.zeros_like, params)
    mu, nu = zeros, jax.tree.map(jnp.zeros_like, params)
    del zeros
    step = _jit(_adamw_step, cfg, opt, matmul, donate_argnums=(0, 1, 2))
    out: Dict[str, Any] = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            tok, lab = b["tokens"], b["labels"]
            if rows:
                tok, lab = tok[:rows], lab[:rows]
            params, mu, nu, loss, gclip, graw = step(
                params, mu, nu, jnp.asarray(tok), jnp.asarray(lab),
                jnp.asarray(i + 1, jnp.int32))
            out["loss"].append(float(loss))
            if i == 0:
                out["grad"] = _to_host(gclip)
                out["grad_raw"] = _to_host(graw)
        del mu, nu
        out["delta"] = delta_norms_host(params, key, cfg)
    return out


def _to_host(tree: Tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


_leaf_norms_jit = jax.jit(_leaf_norms)


def leaf_norms_host(tree: Tree) -> Dict[str, float]:
    """Per-leaf float32 norms of any tree of this layout, by leaf path."""
    return _to_host(_leaf_norms_jit(tree))


def delta_norms_host(params: Tree, key, cfg: Dict[str, Any]
                     ) -> Dict[str, float]:
    """Per-leaf norms of ``params - init_weights(key)``."""
    return _to_host(_jit(_delta_norms, cfg)(params, init_weights(key, cfg)))
