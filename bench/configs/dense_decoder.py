"""Plain float32 reference of a dense decoder (Llama / Phi-3 layout), and
the benchmark's random weights for it.

Pre-norm blocks: RMSNorm, grouped-query attention with rotate-half RoPE on
the first ``partial_rotary_factor`` of each head's dims, a causal softmax
scaled by ``1/sqrt(head_dim)``, the output projection, RMSNorm, and a SiLU
gated MLP; a final RMSNorm and the LM head (the embedding, transposed,
when tied).  The RMSNorm gain is stored as an offset from 1 (``1 + w``),
which is how the served program stores it.  Every sum runs in float32 at
``HIGHEST`` matmul precision, from the bf16 weights upcast one layer at a
time.  With ``kv_format`` ``"int8"`` in the configuration, attention keeps
the int8 KV format's arithmetic: K and V rounded to int8 per (position, KV
head), each query row and each row of probabilities times V's scales
rounded to int8 before its dot, every value and scale held in float32.
With ``weight_format`` ``"int8"`` every matrix (the LM head included) is
first rounded to int8 per output column, symmetric, one precision below
the served bf16: the control that ``correct`` has to fail.  Nothing here
imports the program.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block: scores stay (H, 512, S)


def init_weights(key, shapes):
    """Random weights of the given shape tree: matrices N(0, 1/fan_in),
    the embedding N(0, 0.02**2), norm gain offsets N(0, 0.1**2)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            std = 0.1
        elif name == "['embed']":
            std = 0.02
        else:
            std = 1.0 / math.sqrt(s.shape[-2])
        k = jax.random.fold_in(key, i)
        out.append(jax.random.normal(k, s.shape, s.dtype)
                   * jnp.asarray(std, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta, rot):
    """Rotate-half RoPE on the first ``rot`` dims of (S, H, D)."""
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _dims(conf):
    H, Hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    D = conf.get("head_dim") or conf["hidden_size"] // H
    rot = int(D * conf.get("partial_rotary_factor", 1.0))
    return H, Hk, D, rot


def _int8_rows(x, lo=-127):
    """Symmetric int8 over the last axis, as the int8 KV format stores K
    and V per (position, KV head) and quantizes each query row and each
    row of scale-weighted probabilities before its integer dot (paper
    section 2.2): integer values as float32, and the row scales."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-20 if lo == 0 else 1e-8)
    s = s / 127.0
    return jnp.clip(jnp.round(x / s[..., None]), lo, 127), s


def _matrix(w, conf):
    """A stored ``(in, out)`` matrix as float32, in int8 per output column
    where the configuration asks for int8 weights."""
    w = w.astype(jnp.float32)
    if conf.get("weight_format") != "int8":
        return w
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _layer(x, lw, conf):
    """One decoder block over a whole (S, d) float32 sequence."""
    H, Hk, D, rot = _dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    f32 = functools.partial(_matrix, conf=conf)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lw["attn_norm"]["scale"], eps)
    q = jnp.dot(h, f32(lw["attn"]["wq"]), precision=HI).reshape(S, H, D)
    k = jnp.dot(h, f32(lw["attn"]["wk"]), precision=HI).reshape(S, Hk, D)
    v = jnp.dot(h, f32(lw["attn"]["wv"]), precision=HI).reshape(S, Hk, D)
    q, k = _rope(q, pos, theta, rot), _rope(k, pos, theta, rot)
    q = q.reshape(S, Hk, H // Hk, D)
    int8 = conf.get("kv_format") == "int8"
    if int8:
        k, k_s = _int8_rows(k)
        v, v_s = _int8_rows(v)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        causal = pos[None, :] <= pos[lo:lo + Q_BLOCK, None]
        if not int8:
            s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI) / math.sqrt(D)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI))
            continue
        qb, q_s = _int8_rows(qb)
        s = (jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI)
             * jnp.moveaxis(q_s, 0, -1)[..., None] * k_s.T[:, None, None, :]
             / math.sqrt(D))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1) * v_s.T[:, None, None, :]
        w, w_s = _int8_rows(w, lo=0)
        outs.append(jnp.einsum("hgqk,khd->qhgd", w, v, precision=HI)
                    * jnp.moveaxis(w_s, -1, 0)[..., None])
    o = jnp.concatenate(outs, axis=0).reshape(S, H * D)
    x = x + jnp.dot(o, f32(lw["attn"]["wo"]), precision=HI)
    h = _rms(x, lw["mlp_norm"]["scale"], eps)
    g = jnp.dot(h, f32(lw["mlp"]["gate"]), precision=HI)
    u = jnp.dot(h, f32(lw["mlp"]["up"]), precision=HI)
    return x + jnp.dot(jax.nn.silu(g) * u, f32(lw["mlp"]["down"]),
                       precision=HI)


def _final_hidden(weights, conf, tokens, at):
    """Final-normed hidden states at positions ``at`` of ``tokens``."""
    x = weights["embed"][tokens].astype(jnp.float32)

    def body(x, lw):
        return _layer(x, lw, conf), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    return _rms(x[at], weights["final_norm"]["scale"], conf["rms_norm_eps"])


def _head(weights, h, conf):
    head = weights.get("lm_head")
    head = _matrix(weights["embed"].T if head is None else head, conf)
    return jnp.dot(h, head, precision=HI)


@functools.partial(jax.jit, static_argnums=(1,))
def logits(weights, items, tokens):
    """(S, V) reference logits of a whole (S,) sequence; ``items`` is
    :func:`conf_items` of the configuration."""
    conf = dict(items)
    at = jnp.arange(tokens.shape[0])
    return _head(weights, _final_hidden(weights, conf, tokens, at), conf)


@functools.partial(jax.jit, static_argnums=(1,))
def _gaps(weights, items, tokens, at, targets, columns):
    """Reference logits at positions ``at`` of ``tokens`` -> for each, the
    gap by which ``targets``' logit lies below the best, the best, and the
    logits at ``columns``."""
    conf = dict(items)
    out = _head(weights, _final_hidden(weights, conf, tokens, at), conf)
    best = jnp.max(out, axis=-1)
    got = jnp.take_along_axis(out, targets[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(out, axis=-1), out[:, columns]


def conf_items(conf):
    """The sizes the reference reads, hashable (a static jit argument)."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "partial_rotary_factor", "rms_norm_eps", "rope_theta",
            "kv_format", "weight_format")
    return tuple((k, conf[k]) for k in keys if k in conf)


def logit_gaps(weights, conf, prompt, served, columns=(0,), seq_bucket=512,
               gen_bucket=512, targets=None):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the served token is the
    reference's argmax), the reference's argmax, and its logits at
    ``columns`` ``(G, C)``.  The sequence is the prompt and every served
    token but the last; it is right-padded to a multiple of ``seq_bucket``
    (causal attention keeps the compared positions independent of the
    padding), so a few programs serve every request.  ``targets``, when
    given, are the tokens whose gaps are read in place of the served ones
    (the control's choices at the same positions)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    S = -(-len(seq) // seq_bucket) * seq_bucket
    G = -(-len(served) // gen_bucket) * gen_bucket
    toks = np.zeros(S, np.int32)
    toks[:len(seq)] = seq
    at = np.zeros(G, np.int32)
    at[:len(served)] = len(prompt) - 1 + np.arange(len(served))
    tgt = np.zeros(G, np.int32)
    tgt[:len(served)] = served if targets is None else targets
    gaps, best, cols = _gaps(weights, conf_items(conf), jnp.asarray(toks),
                             jnp.asarray(at), jnp.asarray(tgt),
                             jnp.asarray(np.asarray(columns, np.int32)))
    n = len(served)
    return np.asarray(gaps)[:n], np.asarray(best)[:n], np.asarray(cols)[:n]
