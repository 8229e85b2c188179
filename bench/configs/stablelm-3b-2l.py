"""Plain reference of stablelm-3b-4e1t (2 of its 32 identical layers).

Per layer (pre-norm): x += Wo·attn(rope(LN1(x)·Wq), rope(LN1(x)·Wk),
LN1(x)·Wv); x += W_down·(silu(LN2(x)·W_gate) ⊙ LN2(x)·W_up). LayerNorm with
scale and bias, eps 1e-5. Rotary embedding on the first 25% of each head's
80 dims (20), rotate-half form, θ = 10000. Causal softmax attention over 32
heads of 80, no q/k/v bias. Final LayerNorm, untied output projection, mean
token cross entropy. Weights stored in ``param_dtype`` (norms in float32):
embeddings N(0, 0.02²), projections N(0, 1/fan_in) with fan_in the
projection's input size as the program lays the tensors out; one PRNG key
per leaf in the order of the sorted parameter names, split once more over
the stacked layers.
"""
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _dims(config):
    m = config["model"]
    d, h = m["d_model"], m["num_heads"]
    return d, h, d // h, m["d_ff"], m["vocab_size"], m["num_layers"]


def _layout(config):
    """Leaf: (shape without the layer axis, init, stacked)."""
    d, h, hd, f, v, n = _dims(config)
    fan = lambda axis: ("fan_in", axis)
    return {
        "dense_layers": {
            "attn": {"wk": ((d, h, hd), fan(0)), "wo": ((h, hd, d), fan(1)),
                     "wq": ((d, h, hd), fan(0)), "wv": ((d, h, hd), fan(0))},
            "ln1": {"bias": ((d,), ("zeros",)), "scale": ((d,), ("ones",))},
            "ln2": {"bias": ((d,), ("zeros",)), "scale": ((d,), ("ones",))},
            "mlp": {"wi_gate": ((d, f), fan(0)), "wi_up": ((d, f), fan(0)),
                    "wo": ((f, d), fan(0))},
        },
        "embed": {"embedding": ((v, d), ("normal",)),
                  "unembed": ((d, v), ("normal",))},
        "final_norm": {"bias": ((d,), ("zeros",)), "scale": ((d,), ("ones",))},
    }


def _init_one(key, shape, how, dtype):
    if how[0] == "zeros":
        return jnp.zeros(shape, F32)
    if how[0] == "ones":
        return jnp.ones(shape, F32)
    std = 0.02 if how[0] == "normal" else 1.0 / math.sqrt(shape[how[1]])
    # stored in the configuration's dtype, held in float32
    return (std * jax.random.normal(key, shape)).astype(dtype).astype(F32)


def init_params(key, config):
    n = _dims(config)[-1]
    dtype = jnp.dtype(config["model"]["param_dtype"])
    lay = _layout(config)
    flat, treedef = jax.tree.flatten(
        lay, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    paths = jax.tree_util.tree_flatten_with_path(
        lay, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))[0]
    keys = jax.random.split(key, len(flat))
    vals = []
    for kk, (path, (shape, how)) in zip(keys, paths):
        if path[0].key == "dense_layers":
            lk = jax.random.split(kk, n)
            vals.append(jax.vmap(
                lambda k_: _init_one(k_, shape, how, dtype))(lk))
        else:
            vals.append(_init_one(kk, shape, how, dtype))
    return jax.tree.unflatten(treedef, vals)


def _ln(p, x, eps):
    x = x.astype(F32)
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, rot, theta):
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * inv              # (S, half)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def loss(params, batch, config, pol):
    m = config["model"]
    d, h, hd, f, v, n = _dims(config)
    eps = m["norm_eps"]
    rot = int(hd * m["rotary_pct"])
    rot -= rot % 2
    tokens = batch["tokens"]
    B, S = tokens.shape
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = pol.act(jnp.take(params["embed"]["embedding"], tokens, axis=0))
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], params["dense_layers"])
        a = lp["attn"]
        hx = pol.act(_ln(lp["ln1"], x, eps))
        q = _rope(pol.einsum("bsd,dhk->bshk", hx, a["wq"]), pos, rot,
                  m["rope_theta"])
        k = _rope(pol.einsum("bsd,dhk->bshk", hx, a["wk"]), pos, rot,
                  m["rope_theta"])
        val = pol.einsum("bsd,dhk->bshk", hx, a["wv"])
        s = pol.einsum("bqhk,bshk->bhqs", q, k).astype(F32) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -1e30)
        pr = pol.act(jax.nn.softmax(s, axis=-1))
        o = pol.einsum("bhqs,bshk->bqhk", pr, val)
        x = x + pol.einsum("bqhk,hkd->bqd", o, a["wo"])
        hx = pol.act(_ln(lp["ln2"], x, eps))
        mp = lp["mlp"]
        g = jax.nn.silu(pol.einsum("bsd,df->bsf", hx, mp["wi_gate"]))
        u = pol.einsum("bsd,df->bsf", hx, mp["wi_up"])
        x = x + pol.einsum("bsf,fd->bsd", pol.act(g * u), mp["wo"])
    hx = pol.act(_ln(params["final_norm"], x, eps))
    logits = pol.einsum("bsd,dv->bsv", hx, params["embed"]["unembed"])
    logits = logits.astype(F32)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def products(config, traffic):
    """Multiply-adds per sample (one sequence of ``seq_len`` tokens) of each
    matrix product of the forward pass. Attention counts the causal half:
    query i meets i + 1 keys."""
    d, h, hd, f, v, n = _dims(config)
    S = int(traffic["seq_len"])
    pairs = S * (S + 1) // 2
    per_layer = [(S * d * d, False)] * 4 + [(S * d * f, False)] * 3 + [
        (pairs * d, False)] * 2
    return per_layer * n + [(S * d * v, False)]


def n_params(config):
    d, h, hd, f, v, n = _dims(config)
    return n * (4 * d * d + 3 * d * f + 4 * d) + 2 * v * d + 2 * d
