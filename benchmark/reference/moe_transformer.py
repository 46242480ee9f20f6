"""Plain reference of a stack of attention + mixture-of-experts blocks and
its AdamW train step, written from the architecture's equations in
``jax.numpy`` alone.

Blocks (``attn_moe``, ``attn_local_moe``): pre-norm residual,
``x + Attn(RMSNorm(x))`` then ``x + MoE(RMSNorm(x))``.  RMSNorm scales by
``1 + g``.  Attention: grouped-query heads, rotary embedding on the two
halves of each head, causal mask (and a sliding window for the ``local``
kind), softmax in float32.  MoE: router softmax over the experts, top-k,
gates renormalised to sum 1, SwiGLU experts ``(silu(x W1) * (x W2)) W3``;
every expert is applied to every token and weighted by its gate (zero for
the experts a token is not routed to), so no routing structure is needed.
Loss: next-token cross-entropy plus, per MoE block, the load-balance loss
``E * sum_e f_e * P_e`` and the router z-loss.  Optimizer: global-norm
clipping, linear warm-up into cosine decay, AdamW with decoupled weight
decay on every leaf.

Parameters are a flat dict keyed by path (``layers/0/moe/w1``), stacked
over the pattern's groups.  ``cd`` is the dtype activations are kept in and
``md`` the dtype matrix products read their operands in (``cd`` unless
given; products accumulate in float32).  An 8-bit float ``md`` rounds each
operand under a per-tensor scale, in the backward products too, as 8-bit
training does.  The caller sets the matmul precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SUPPORTED = ("attn_moe", "attn_local_moe")
#: keys of the model section this reference implements; any other key
#: with a non-default value is refused rather than silently ignored.
_DEFAULTS = {"qk_norm": False, "post_norms": False, "attn_softcap": 0.0,
             "final_softcap": 0.0, "causal": True, "input_kind": "tokens",
             "ffn_act": "swiglu", "local_global_period": 0}


def _dims(m: dict) -> dict:
    for k, v in _DEFAULTS.items():
        if m.get(k, v) != v:
            raise ValueError(f"reference does not implement {k}={m[k]!r}")
    pattern = tuple(m["block_pattern"])
    if any(kind not in SUPPORTED for kind in pattern):
        raise ValueError(f"reference implements {SUPPORTED}, got {pattern}")
    d, H = m["d_model"], m["num_heads"]
    return {"d": d, "V": m["vocab_size"], "H": H, "Hkv": m["num_kv_heads"],
            "dh": m.get("head_dim") or d // H, "E": m["num_experts"],
            "k": m["top_k"], "h": m["moe_d_ff"], "pattern": pattern,
            "G": m["num_layers"] // len(pattern),
            "window": m.get("sliding_window", 0),
            "theta": m.get("rope_theta", 10_000.0),
            "aux_w": m.get("aux_loss_weight", 0.01),
            "z_w": m.get("z_loss_weight", 1e-3)}


def param_table(m: dict) -> dict:
    """Path -> shape of every parameter."""
    D = _dims(m)
    d, V, H, Hkv, dh, E, h, G = (D[k] for k in
                                 ("d", "V", "H", "Hkv", "dh", "E", "h", "G"))
    t = {"embed": (V, d), "unembed": (d, V), "final_norm": (d,)}
    for j, _ in enumerate(D["pattern"]):
        p = f"layers/{j}/"
        t.update({p + "ln1": (G, d), p + "ln2": (G, d),
                  p + "attn/wq": (G, d, H * dh), p + "attn/wk": (G, d, Hkv * dh),
                  p + "attn/wv": (G, d, Hkv * dh), p + "attn/wo": (G, H * dh, d),
                  p + "moe/wg": (G, d, E), p + "moe/w1": (G, E, d, h),
                  p + "moe/w2": (G, E, d, h), p + "moe/w3": (G, E, h, d)})
    return t


def _rms(x, g):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 / jnp.sqrt(var + 1e-6) * (1.0 + g.astype(jnp.float32))
            ).astype(x.dtype)


def _rope(x, theta):
    """x: (S, heads, dh); position of row i is i."""
    S, _, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _dot(a, b, spec):
    if spec is None:
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _round8(x, md):
    """``x`` rounded to the 8-bit float ``md`` under one scale for the whole
    tensor, which maps its largest magnitude to ``md``'s largest finite
    value; returned in float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, float(jnp.finfo(md).max) / amax, 1.0)
    return (x * s).astype(md).astype(jnp.float32) / s


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dot8(a, b, spec, md):
    """A product whose operands, forward and backward (the cotangent and the
    saved operands), are 8-bit floats under per-tensor scales."""
    return _dot(_round8(a, md), _round8(b, md), spec)


def _dot8_fwd(a, b, spec, md):
    qa, qb = _round8(a, md), _round8(b, md)
    return _dot(qa, qb, spec), (qa, qb)


def _dot8_bwd(spec, md, res, g):
    _, vjp = jax.vjp(lambda x, y: _dot(x, y, spec), *res)
    return vjp(_round8(g, md))


_dot8.defvjp(_dot8_fwd, _dot8_bwd)


def _mm(md, cd):
    md = jnp.dtype(md)

    def mm(a, b, spec=None):
        if md.itemsize == 1:
            out = _dot8(a.astype(jnp.float32), b.astype(jnp.float32), spec, md)
        else:
            out = _dot(a.astype(md), b.astype(md), spec)
        return out.astype(cd)
    return mm


def _attention_row(x, w, D, window, mm):
    """One sequence: x (S, d) -> (S, d)."""
    S = x.shape[0]
    H, Hkv, dh = D["H"], D["Hkv"], D["dh"]
    q = _rope(mm(x, w["wq"]).reshape(S, H, dh), D["theta"])
    k = _rope(mm(x, w["wk"]).reshape(S, Hkv, dh), D["theta"])
    v = mm(x, w["wv"]).reshape(S, Hkv, dh)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=1)          # query head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=1)
    s = mm(q.astype(jnp.float32) / math.sqrt(dh), k,
           "qhd,khd->hqk").astype(jnp.float32)
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = ki <= qi
    if window:
        mask &= ki > qi - window
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm(p, v, "hqk,khd->qhd")
    return mm(o.reshape(S, H * dh), w["wo"])


def _moe(x, w, D, mm):
    """x: (L, d) -> ((L, d), aux loss, tokens routed to each expert)."""
    E, k = D["E"], D["k"]
    cd = x.dtype
    logits = mm(x, w["wg"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    L = x.shape[0]
    gate = jnp.zeros((L, E), jnp.float32).at[
        jnp.arange(L)[:, None], top_e].set(top_p).astype(cd)
    frac_tokens = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0) / (L * k)
    aux = (D["aux_w"] * E * jnp.sum(frac_tokens * probs.mean(axis=0))
           + D["z_w"] * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))

    @jax.checkpoint
    def expert(y, e):
        a = mm(x, w["w1"][e])
        b = mm(x, w["w2"][e])
        out = mm(a * jax.nn.sigmoid(a) * b, w["w3"][e])
        return y + gate[:, e][:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return y, aux, frac_tokens * (L * k)


def loss(params: dict, tokens, labels, m: dict, cd=jnp.float32, md=None):
    """(loss, {"ce": cross-entropy, "counts": tokens routed to each expert
    of the first MoE block}) of a (B, S) batch."""
    D = _dims(m)
    mm = _mm(md or cd, cd)
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cd) * math.sqrt(D["d"])
    aux = jnp.zeros((), jnp.float32)
    counts = []
    for g in range(D["G"]):
        for j, kind in enumerate(D["pattern"]):
            w = {n.split("/", 2)[2]: v[g] for n, v in params.items()
                 if n.startswith(f"layers/{j}/")}
            att = {n[5:]: v for n, v in w.items() if n.startswith("attn/")}
            moe = {n[4:]: v for n, v in w.items() if n.startswith("moe/")}
            window = D["window"] if kind == "attn_local_moe" else 0
            h = _rms(x, w["ln1"])
            row = jax.checkpoint(
                lambda r: _attention_row(r, att, D, window, mm))
            x = x + jax.lax.map(row, h)
            y, a, c = _moe(_rms(x, w["ln2"]).reshape(B * S, -1), moe, D, mm)
            x = x + y.reshape(B, S, -1)
            aux = aux + a
            counts.append(c)
    x = _rms(x, params["final_norm"])

    @jax.checkpoint
    def row_nll(args):
        xr, lr = args
        logits = mm(xr[:-1], params["unembed"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lr[1:, None], axis=-1))

    ce = jnp.sum(jax.lax.map(row_nll, (x, labels))) / (B * (S - 1))
    return ce + aux, {"ce": ce, "counts": counts[0]}


def _lr(step, t: dict):
    step = step.astype(jnp.float32)
    peak, warm, total = t["learning_rate"], t["warmup_steps"], t["total_steps"]
    frac = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warm, peak * step / max(warm, 1), cos)


def train_step(params, mu, nu, step, tokens, labels, m: dict, t: dict,
               cd=jnp.float32, md=None):
    """One step: returns (params, mu, nu, loss, clipped gradient, tokens
    routed to each expert of the first MoE block)."""
    (l, out), g = jax.value_and_grad(loss, has_aux=True)(
        params, tokens, labels, m, cd, md)
    g = {n: v.astype(jnp.float32) for n, v in g.items()}
    gnorm = jnp.sqrt(sum(jnp.sum(v * v) for v in g.values()))
    g = {n: v * jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(gnorm, 1e-9))
         for n, v in g.items()}
    lr = _lr(step, t)
    n = (step + 1).astype(jnp.float32)
    b1, b2 = t["b1"], t["b2"]
    new_p, new_m, new_v = {}, {}, {}
    for name, p in params.items():
        mm = b1 * mu[name] + (1 - b1) * g[name]
        vv = b2 * nu[name] + (1 - b2) * g[name] ** 2
        upd = (mm / (1 - b1 ** n)) / (jnp.sqrt(vv / (1 - b2 ** n)) + t["eps"])
        new_p[name] = (p - lr * (upd + t["weight_decay"] * p)).astype(p.dtype)
        new_m[name], new_v[name] = mm, vv
    return new_p, new_m, new_v, l, g, out["counts"]
