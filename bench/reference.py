"""Plain float32 reference of the BlendFL round and of the serving routes.

Written from the method's description (BlendFL, arXiv:2510.13266, Alg. 1)
and the configuration files, in straightforward ``jax.numpy``; it imports
nothing of the program. Its parts:

- encoder f_m: x (B, S, F) -> h (B, d), the configuration's encoder, whose
  weights and equations its module ``bench/encoders/<enc_type>.py`` holds
  (``common.encoder``);
- unimodal head g_m: h W + b; fusion head g_M: gelu([h_A, h_B] W + b) W' + b';
- task loss: mean sigmoid cross-entropy over labels (multilabel) or softmax
  cross-entropy (multiclass), a masked mean over live rows;
- Adam (b1 0.9, b2 0.999, eps 1e-8, no decay) on each client's stacked
  groups, one step counter per optimizer shared by the groups it steps;
- one round: unimodal step on both modalities, one split (VFL) step through
  the server head over the aligned fragmented rows, one paired step, then
  BlendAvg (Eq. 9-11) scored by negative validation loss, the blended model
  broadcast to every client and the server head re-seeded from the blend;
- serving: the four routes, the VFL route passing its two feature uploads
  and its score download through the top-k + int8 wire codec per row.

Weights are drawn from the seed by the same key schedule the configuration
documents (``init_models``). ``precision`` picks how matrix products run:
``highest`` (float32), ``high`` (three bfloat16 passes, emulated so that
every backend computes the same thing) or ``bf16`` (one pass).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.common import encoder as encoder_module

B1, B2, EPS = 0.9, 0.999, 1e-8


# ------------------------------------------------------------ arithmetic --

def _bf(a):
    """``a`` rounded to bfloat16's 8 significant bits, kept in float32.
    ``reduce_precision`` is not folded away by compilers that allow
    excess precision, as a float32 -> bfloat16 -> float32 round trip is."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def mm(a, b, precision: str):
    """a @ b in the stated precision (float32 accumulation throughout)."""
    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hi)
    a_hi, b_hi = _bf(a), _bf(b)
    if precision == "bf16":
        return jnp.matmul(a_hi, b_hi, precision=hi)
    if precision == "high":
        a_lo, b_lo = _bf(a - a_hi), _bf(b - b_hi)
        return (jnp.matmul(a_hi, b_hi, precision=hi)
                + jnp.matmul(a_hi, b_lo, precision=hi)
                + jnp.matmul(a_lo, b_hi, precision=hi))
    raise ValueError(f"unknown precision {precision!r}")


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def linear(p, x, prec):
    return mm(x, p["w"], prec) + p["b"]


def encode(cfg: dict, p, x, prec):
    """f_m(x): (B, S, F) -> (B, d) by the configuration's encoder."""
    return encoder_module(cfg).apply(p, x, cfg, prec)


def fusion(p, h_a, h_b, prec):
    h = gelu(linear(p["mix"], jnp.concatenate([h_a, h_b], axis=-1), prec))
    return linear(p["out"], h, prec)


def row_loss(logits, y, kind):
    if kind == "multiclass":
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, jnp.argmax(y, -1)[..., None], -1)
        return lse - pick[..., 0]
    bce = (jnp.maximum(logits, 0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.mean(bce, axis=-1)


def masked_mean(rows, m):
    return jnp.sum(rows * m) / jnp.maximum(jnp.sum(m), 1.0)


def probs(logits, kind):
    if kind == "multiclass":
        return jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(logits)


# ------------------------------------------------------------------ init --

def dense_init(key, d_in, d_out):
    return {"w": jax.random.normal(key, (d_in, d_out)) * (1.0 / jnp.sqrt(d_in)),
            "b": jnp.zeros((d_out,), jnp.float32)}


def fusion_init(key, d, out):
    k1, k2 = jax.random.split(key)
    return {"mix": dense_init(k1, 2 * d, d), "out": dense_init(k2, d, out)}


def init_models(key, cfg: dict) -> dict:
    """{f_A, f_B, g_A, g_B, g_M} from one key: split into five, in that
    order f_A, f_B, g_A, g_B, g_M; an encoder uses its key as its module's
    ``init`` documents; the fusion head splits its key into two (mix,
    out); each head weight is N(0, 1/d_in), each head bias 0."""
    d, out = cfg["d_hidden"], cfg["n_labels"]
    enc = encoder_module(cfg)
    ks = jax.random.split(key, 5)
    return {"f_A": enc.init(ks[0], cfg["feat_a"], cfg),
            "f_B": enc.init(ks[1], cfg["feat_b"], cfg),
            "g_A": dense_init(ks[2], d, out), "g_B": dense_init(ks[3], d, out),
            "g_M": fusion_init(ks[4], d, out)}


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (the low 32 bits
    make the key, the rest is folded in)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


# ------------------------------------------------------------- training --

def _adam(p, g, mu, nu, step, lr, flag=None):
    mu2 = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu2 = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    t = step.astype(jnp.float32)
    bc1, bc2 = 1 - B1 ** t, 1 - B2 ** t
    p2 = jax.tree.map(lambda w, m, v: w - lr * ((m / bc1)
                                                / (jnp.sqrt(v / bc2) + EPS)),
                      p, mu2, nu2)
    if flag is None:
        return p2, mu2, nu2

    def keep(new, old):
        return jax.tree.map(lambda a, b: jnp.where(
            flag.reshape(flag.shape + (1,) * (a.ndim - 1)), a, b), new, old)
    return keep(p2, p), keep(mu2, mu), keep(nu2, nu)


def make_round(cfg: dict, precision: str):
    """round(state, batch) -> (state', losses) for one full-participation
    BlendAvg round without a wire codec."""
    kind, lr = cfg["kind"], cfg["lr"]
    prec = precision

    def encoder(f, x):
        return encode(cfg, f, x, prec)

    def uni_loss(f, g, x, y, m):
        return masked_mean(row_loss(linear(g, encoder(f, x), prec), y, kind), m)

    def pair_loss(fa, fb, gm, xa, xb, y, m):
        logits = fusion(gm, encoder(fa, xa), encoder(fb, xb), prec)
        return masked_mean(row_loss(logits, y, kind), m)

    def step_groups(st, grads, names, flags):
        st = dict(st)
        step = st["step"] + 1
        for k in names:
            st["models"][k], st["mu"][k], st["nu"][k] = _adam(
                st["models"][k], grads[k], st["mu"][k], st["nu"][k], step, lr,
                flags[k])
        st["step"] = step
        return st

    def phase1(st, b):
        def total(p):
            la = jax.vmap(uni_loss)(p["f_A"], p["g_A"], b["partial_a"],
                                    b["partial_ya"], b["partial_ma"])
            lb = jax.vmap(uni_loss)(p["f_B"], p["g_B"], b["partial_b"],
                                    b["partial_yb"], b["partial_mb"])
            return jnp.sum(la) + jnp.sum(lb), (la, lb)
        params = {k: st["models"][k] for k in ("f_A", "g_A", "f_B", "g_B")}
        (_, (la, lb)), g = jax.value_and_grad(total, has_aux=True)(params)
        na, nb = b["partial_ma"].sum(1), b["partial_mb"].sum(1)
        st = step_groups(st, g, params, {"f_A": na > 0, "g_A": na > 0,
                                         "f_B": nb > 0, "g_B": nb > 0})
        wa, wb = (na > 0).astype(jnp.float32), (nb > 0).astype(jnp.float32)
        loss = ((jnp.sum(la * wa) + jnp.sum(lb * wb))
                / jnp.maximum(jnp.sum(wa) + jnp.sum(wb), 1.0))
        return st, loss

    def phase2(st, b):
        d = cfg["d_hidden"]

        def joint(p, gmv):
            ha = jax.vmap(encoder)(p["f_A"], b["frag_a"])
            hb = jax.vmap(encoder)(p["f_B"], b["frag_b"])
            ha = ha.reshape(-1, d)
            hb = hb.reshape(-1, d)[b["perm_b"]]
            y = b["frag_y"].reshape(ha.shape[0], -1)
            return masked_mean(row_loss(fusion(gmv, ha, hb, prec), y, kind),
                               b["frag_w"])
        params = {k: st["models"][k] for k in ("f_A", "f_B")}
        loss, (g, g_srv) = jax.value_and_grad(joint, argnums=(0, 1))(
            params, st["server"])
        st = step_groups(st, g, params, {"f_A": b["part_a"], "f_B": b["part_b"]})
        live = jnp.any(b["frag_w"] > 0)
        sstep = st["srv_step"] + 1
        p2, mu2, nu2 = _adam(st["server"], g_srv, st["srv_mu"], st["srv_nu"],
                             sstep, lr)
        pick = lambda new, old: jax.tree.map(  # noqa: E731
            lambda a, c: jnp.where(live, a, c), new, old)
        st = dict(st, server=pick(p2, st["server"]),
                  srv_mu=pick(mu2, st["srv_mu"]), srv_nu=pick(nu2, st["srv_nu"]),
                  srv_step=jnp.where(live, sstep, st["srv_step"]))
        return st, loss

    def phase3(st, b):
        def total(p):
            l = jax.vmap(pair_loss)(p["f_A"], p["f_B"], p["g_M"], b["paired_a"],
                                    b["paired_b"], b["paired_y"], b["paired_m"])
            return jnp.sum(l), l
        params = {k: st["models"][k] for k in ("f_A", "f_B", "g_M")}
        (_, l), g = jax.value_and_grad(total, has_aux=True)(params)
        n = b["paired_m"].sum(1)
        st = step_groups(st, g, params, {k: n > 0 for k in params})
        w = (n > 0).astype(jnp.float32)
        return st, jnp.sum(l * w) / jnp.maximum(jnp.sum(w), 1.0)

    return phase1, phase2, phase3, uni_loss, pair_loss


def _omega(scores, gscore):
    delta = scores - gscore
    delta = jnp.where(jnp.isnan(delta), -jnp.inf, delta)
    w = jnp.where(delta > 0, delta, 0.0)
    tot = jnp.sum(w)
    return jnp.where(tot > 0, w / jnp.maximum(tot, 1e-12), 0.0), tot > 0


def _blend(glob, cands, omega, up):
    return jax.tree.map(
        lambda g, c: jnp.where(up, jnp.tensordot(omega, c, axes=1,
                                                 precision="highest"), g),
        glob, cands)


def train_rounds(cfg: dict, batches: list, val: dict, seed: int,
                 precision: str = "highest") -> dict:
    """Run the reference for ``len(batches)`` rounds from the seeded
    initial weights. Returns the per-round losses, the initial and the
    final global models, and the optimizer's first moments after round 1.

    Candidate scoring runs one candidate at a time, so the reference's
    device memory stays near one candidate's activations."""
    phase1, phase2, phase3, uni_loss, pair_loss = make_round(cfg, precision)
    C = batches[0]["partial_ma"].shape[0]
    base = jax.jit(lambda k: init_models(k, cfg))(seed_key(seed))
    zeros = jax.tree.map(jnp.zeros_like, base)
    stack = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jnp.broadcast_to(x, (C,) + x.shape), t)
    st = {"models": stack(base), "mu": stack(zeros), "nu": stack(zeros),
          "step": jnp.zeros((), jnp.int32), "server": base["g_M"],
          "srv_mu": zeros["g_M"], "srv_nu": zeros["g_M"],
          "srv_step": jnp.zeros((), jnp.int32)}
    st["models"], st["mu"], st["nu"] = (dict(st["models"]), dict(st["mu"]),
                                        dict(st["nu"]))
    glob = base
    p1, p2, p3 = jax.jit(phase1), jax.jit(phase2), jax.jit(phase3)
    ones = jnp.ones(val["val_y"].shape[0], jnp.float32)
    vy = jnp.asarray(val["val_y"])
    va, vb = jnp.asarray(val["val_a"]), jnp.asarray(val["val_b"])
    uni = jax.jit(lambda f, g, x: -uni_loss(f, g, x, vy, ones))
    enc = jax.jit(lambda f, x: encode(cfg, f, x, precision))
    kind = cfg["kind"]
    multi = jax.jit(lambda gm, ha, hb: -masked_mean(
        row_loss(fusion(gm, ha, hb, precision), vy, kind), ones))
    row = lambda t, i: jax.tree.map(lambda x: x[i], t)  # noqa: E731

    losses, mu1 = [], None
    for r, b in enumerate(batches):
        b = {k: (None if v is None else jnp.asarray(v)) for k, v in b.items()}
        st, l_uni = p1(st, b)
        st, l_vfl = p2(st, b)
        st, l_pair = p3(st, b)
        losses.append({"loss_uni": float(l_uni), "loss_vfl": float(l_vfl),
                       "loss_paired": float(l_pair)})
        if r == 0:
            mu1 = jax.device_get({"clients": st["mu"], "server": st["srv_mu"]})
        new = dict(glob)
        for m, x in (("A", va), ("B", vb)):
            f, g = st["models"][f"f_{m}"], st["models"][f"g_{m}"]
            scores = jnp.stack([uni(row(f, i), row(g, i), x) for i in range(C)])
            omega, up = _omega(scores, uni(glob[f"f_{m}"], glob[f"g_{m}"], x))
            new[f"f_{m}"] = _blend(glob[f"f_{m}"], f, omega, up)
            new[f"g_{m}"] = _blend(glob[f"g_{m}"], g, omega, up)
        ha, hb = enc(new["f_A"], va), enc(new["f_B"], vb)
        cands = jax.tree.map(lambda s, e: jnp.concatenate([s, e[None]]),
                             st["models"]["g_M"], st["server"])
        scores = jnp.stack([multi(row(cands, i), ha, hb) for i in range(C + 1)])
        omega, up = _omega(scores, multi(glob["g_M"], ha, hb))
        new["g_M"] = _blend(glob["g_M"], cands, omega, up)
        glob = new
        st["models"] = dict(stack(new))
        st["server"] = new["g_M"]
    return {"losses": losses, "mu1": mu1, "global0": jax.device_get(base),
            "global": jax.device_get(glob)}


# -------------------------------------------------------------- serving --

def codec_roundtrip(x, frac: float, quantize: bool = True):
    """Per-row wire round trip: keep the ceil(frac * n) entries of largest
    magnitude (ties at the threshold kept), round each kept entry to the
    row's symmetric int8 grid (scale = the row's largest magnitude), and
    decode."""
    n = x.shape[-1]
    k = max(1, min(n, math.ceil(frac * n)))
    ax = jnp.abs(x)
    top = jax.lax.top_k(ax, k)[0]
    scale = jnp.maximum(top[:, :1], 1e-30)
    keep = ax >= top[:, k - 1:k]
    if quantize:
        x = jnp.clip(jnp.round(x * (127.0 / scale)), -127, 127) \
            * (scale * (1.0 / 127.0))
    return jnp.where(keep, x, 0.0)


def serve_scores(cfg: dict, route: str, precision: str, frac: float):
    """``scores(models, server, x_a, x_b)``, jitted: the scores of one
    request's rows on one route."""
    kind = cfg["kind"]

    def enc(f, x):
        return encode(cfg, f, x, precision)

    def scores(models, server, x_a, x_b):
        p = precision
        if route == "unimodal_A":
            return probs(linear(models["g_A"], enc(models["f_A"], x_a), p), kind)
        if route == "unimodal_B":
            return probs(linear(models["g_B"], enc(models["f_B"], x_b), p), kind)
        ha, hb = enc(models["f_A"], x_a), enc(models["f_B"], x_b)
        if route == "multimodal":
            return probs(fusion(models["g_M"], ha, hb, p), kind)
        ha, hb = codec_roundtrip(ha, frac), codec_roundtrip(hb, frac)
        return codec_roundtrip(probs(fusion(server, ha, hb, p), kind), frac)
    return jax.jit(scores)


def serve_models(seed: int, cfg: dict):
    """The served client models and VFL server head: the seed's key split
    in two, the client models from the first half (``init_models``), the
    server head from the second (``fusion_init``)."""
    k_models, k_server = jax.random.split(seed_key(seed))
    return (jax.jit(lambda k: init_models(k, cfg))(k_models),
            jax.jit(lambda k: fusion_init(k, cfg["d_hidden"],
                                          cfg["n_labels"]))(k_server))
