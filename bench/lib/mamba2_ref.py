"""Plain reference of the journaled model's training: a Mamba2 stack,
its loss, its gradients and three AdamW steps, in float32 at the highest
matmul precision, written from the layer equations and imported from
nothing of the program.

Weights and token batches are made here from the seed
(``make_params``/``make_batch``); the benchmark hands the same arrays to
the program, so both start from the same state.  The parameter tree is
the program's layout: ``embed.w [V,D]``, per layer (stacked on a leading
axis) ``ln1.w``, ``ssm.{in_proj [D, 2di+2GN+H], conv_w [W, di+2GN],
conv_b, A_log, D_skip, dt_bias, out_norm [di], out_proj [di, D]}``, and
``final_norm.w``.

The layer, as the configuration runs it (departures from the published
Mamba2 block are listed in the configuration file):

    u      = rmsnorm(h) * (1 + ln1.w)
    z, x, B, C, dt = split(u @ in_proj)
    x, B, C = silu(causal_depthwise_conv(x || B || C) + conv_b)
    dt     = softplus(dt + dt_bias);   a = -exp(A_log) * dt
    y_i    = sum_{j<=i} exp(sum_{j<k<=i} a_k) (C_i . B_j) dt_j x_j + D x_i
    h     += ((rmsnorm(y) * (1 + out_norm)) * silu(z)) @ out_proj

The scan is taken in blocks of 128 positions (a block length of its
own, not the kernel's chunk): in full within a block, through the
carried state across blocks.  One row of the batch runs at a time, each
layer rematerialised in the backward pass, so that it fits.  The
loss is the mean token cross-entropy of the tied output head.

``precision="fp8"`` is the control: every matrix product takes its
operands (and, in the backward pass, its cotangent) rounded to
float8_e4m3fn, the step below the bfloat16 the configuration computes
in.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .common import rng_for, seed_words


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["d_model"]
    di = cfg["expand"] * d
    p = cfg["headdim"]
    return dict(D=d, di=di, P=p, H=di // p, N=cfg["d_state"],
                G=cfg["ngroups"], W=cfg["d_conv"], V=cfg["vocab_size"],
                L=cfg["n_layer"])


def param_shapes(cfg: Dict) -> Dict:
    k = dims(cfg)
    D, di, H, N, G, W, L = (k["D"], k["di"], k["H"], k["N"], k["G"],
                            k["W"], k["L"])
    conv = di + 2 * G * N
    layer = {"ln1": {"w": (D,)},
             "ssm": {"in_proj": (D, 2 * di + 2 * G * N + H),
                     "conv_w": (W, conv), "conv_b": (conv,),
                     "A_log": (H,), "D_skip": (H,), "dt_bias": (H,),
                     "out_norm": (di,), "out_proj": (di, D)}}
    stacked = jax.tree_util.tree_map(lambda s: (L, *s), layer,
                                     is_leaf=lambda s: isinstance(s, tuple))
    return {"embed": {"w": (k["V"], D)}, "blocks": {"l0": stacked},
            "final_norm": {"w": (D,)}}


def make_params(cfg: Dict, seed: int):
    """Seeded weights in one jitted call on the default device (f32, the
    configuration's parameter type).  Matrices ~ N(0, min(0.02,
    fan_in^-1/2)^2); A = 1..16 uniform (A_log its log); dt_bias the
    inverse softplus of a 1e-3..1e-1 uniform; D = 1; norm gains and
    biases 0."""
    shapes = param_shapes(cfg)
    flat, tdef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    dims_ = [s for _, s in flat]
    key = jax.random.key(int(seed_words(seed, 1)[0]))

    def init(key):
        keys = jax.random.split(key, len(dims_))
        out = []
        for name, shape, k in zip(names, dims_, keys):
            if "A_log" in name:
                v = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               1.0, 16.0))
            elif "dt_bias" in name:
                u = jax.random.uniform(k, shape, jnp.float32, 1e-3, 1e-1)
                v = u + jnp.log(-jnp.expm1(-u))
            elif "D_skip" in name:
                v = jnp.ones(shape, jnp.float32)
            elif "norm" in name or "ln1" in name or "conv_b" in name:
                v = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = shape[-2]
                v = jax.random.normal(k, shape, jnp.float32) * \
                    min(0.02, fan_in ** -0.5)
            out.append(v)
        return jax.tree_util.tree_unflatten(tdef, out)

    return jax.jit(init)(key)


def make_batch(cfg: Dict, seed: int, step: int, batch: int, seq: int
               ) -> Dict[str, np.ndarray]:
    """Uniform random tokens; labels are the next token, the last
    position ignored (-1).  Every row of every step differs."""
    rng = rng_for(seed, 7, step)
    toks = rng.integers(0, cfg["vocab_size"], (batch, seq), dtype=np.int32)
    labels = np.full((batch, seq), -1, np.int32)
    labels[:, :-1] = toks[:, 1:]
    return {"tokens": toks, "labels": labels}


# -- matrix products ---------------------------------------------------- #
_HI = lax.Precision.HIGHEST


def _q8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@jax.custom_vjp
def _mm8(a, b):
    return jnp.matmul(_q8(a), _q8(b), precision=_HI)


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    g8 = _q8(g)
    da = jnp.matmul(g8, jnp.swapaxes(_q8(b), -1, -2), precision=_HI)
    db = jnp.matmul(jnp.swapaxes(_q8(a), -1, -2), g8, precision=_HI)
    # broadcast batch dims of b (weights) are summed out
    while db.ndim > b.ndim:
        db = db.sum(0)
    return da, db


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def matmul(precision: str):
    if precision == "fp8":
        return _mm8
    return functools.partial(jnp.matmul, precision=_HI)


# -- model ---------------------------------------------------------------- #
def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _ssd(x, dt, a, Bm, Cm, q, mm):
    """y_i = sum_{j<=i} exp(A_i - A_j) (C_i . B_j) dt_j x_j, A the running
    sum of ``a``, for one row: x [S,H,P], dt and a [S,H], B and C
    [S,G,N].  Within each block of ``q`` positions the sum is taken in
    full; across blocks through the carried state h [H,P,N]."""
    S, H, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    rep = H // G
    nb = S // q
    xdt = (x * dt[:, :, None]).reshape(nb, q, H, P)
    Bh = jnp.repeat(Bm, rep, axis=1).reshape(nb, q, H, N)
    Ch = jnp.repeat(Cm, rep, axis=1).reshape(nb, q, H, N)
    ab = a.reshape(nb, q, H)
    i = jnp.arange(q)
    causal = i[:, None] >= i[None, :]

    def block(h, inp):
        xd, Bb, Cb, aa = inp
        A = jnp.cumsum(aa, axis=0)                                # [q, H]
        diff = A[:, None, :] - A[None, :, :]                      # [i, j, H]
        decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
        cb = mm(jnp.transpose(Cb, (1, 0, 2)),
                jnp.transpose(Bb, (1, 2, 0)))                     # [H, i, j]
        w = cb * jnp.transpose(decay, (2, 0, 1))
        y = mm(w, jnp.transpose(xd, (1, 0, 2)))                   # [H, i, P]
        y = y + jnp.exp(A).T[:, :, None] * mm(
            jnp.transpose(Cb, (1, 0, 2)), jnp.transpose(h, (0, 2, 1)))
        tail = jnp.exp(A[-1][None, :] - A)                        # [q, H]
        upd = mm(jnp.transpose(xd, (1, 2, 0)),
                 jnp.transpose(Bb * tail[:, :, None], (1, 0, 2)))  # [H,P,N]
        h = jnp.exp(A[-1])[:, None, None] * h + upd
        return h, jnp.transpose(y, (1, 0, 2))

    _, ys = lax.scan(block, jnp.zeros((H, P, N), jnp.float32),
                     (xdt, Bh, Ch, ab))
    return ys.reshape(S, H, P)


def _layer(h, p, k, eps, mm):
    """One residual Mamba2 layer on a single row ``h [S, D]``."""
    S = h.shape[0]
    di, H, P, N, G, W = k["di"], k["H"], k["P"], k["N"], k["G"], k["W"]
    u = _rmsnorm(h, p["ln1"]["w"], eps)
    zx = mm(u, p["ssm"]["in_proj"])
    z = zx[:, :di]
    xbc = zx[:, di:2 * di + 2 * G * N]
    dt = zx[:, 2 * di + 2 * G * N:]
    xp = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(xp[i:i + S] * p["ssm"]["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["ssm"]["conv_b"])
    x = xbc[:, :di].reshape(S, H, P)
    Bm = xbc[:, di:di + G * N].reshape(S, G, N)
    Cm = xbc[:, di + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["ssm"]["dt_bias"])              # [S, H]
    a = -jnp.exp(p["ssm"]["A_log"]) * dt                         # [S, H]
    y = _ssd(x, dt, a, Bm, Cm, min(k["Q"], S), mm)
    y = y + x * p["ssm"]["D_skip"][None, :, None]
    y = _rmsnorm(y.reshape(S, di), p["ssm"]["out_norm"], eps)
    y = y * jax.nn.silu(z)
    return h + mm(y, p["ssm"]["out_proj"])


def row_loss(params, tokens, labels, k, eps, mm):
    """Summed cross-entropy and count of one row."""
    h = params["embed"]["w"][tokens]
    body = jax.checkpoint(lambda h, p: (_layer(h, p, k, eps, mm), None))
    h, _ = lax.scan(body, h, params["blocks"]["l0"])
    h = _rmsnorm(h, params["final_norm"]["w"], eps)
    logits = mm(h, params["embed"]["w"].T)                       # [S, V]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                               axis=-1)[:, 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask), jnp.sum(mask)


def loss_and_grad_fn(cfg: Dict, precision: str, block: int = 128):
    k = dict(dims(cfg), Q=block)
    eps = cfg["norm_eps"]
    mm = matmul(precision)

    @jax.jit
    def f(params, tokens, labels):
        (s, n), g = jax.value_and_grad(
            lambda p: row_loss(p, tokens, labels, k, eps, mm),
            has_aux=True)(params)
        return s, n, g

    return f


def adamw_step(params, grads, m, v, step: int, opt: Dict):
    """One AdamW step as the configuration states it: global-norm clip,
    warmup-cosine learning rate, bias correction, decoupled weight decay
    on every leaf of two or more dimensions."""
    lr = opt["lr"]
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"]) /
                   max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + np.cos(np.pi * prog))
    lr = lr * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    t = step + 1.0
    b1, b2 = opt["b1"], opt["b2"]

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        if p.ndim >= 2:
            u = u + opt["weight_decay"] * p
        return p - lr * u, m, v, g

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def leaf_norms(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32))))) for p, x in flat}


def reference_run(cfg: Dict, opt: Dict, seed: int, batch: int, seq: int,
                  steps: int = 3, precision: str = "f32"):
    """Losses of ``steps`` steps, per-leaf norms of the first step's
    clipped gradient, and per-leaf norms of the parameters' change over
    the steps."""
    f = loss_and_grad_fn(cfg, precision)
    p0 = make_params(cfg, seed)
    params = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses: List[float] = []
    g_first = None
    for s in range(steps):
        b = make_batch(cfg, seed, s, batch, seq)
        tot, cnt, grads = 0.0, 0.0, None
        for r in range(batch):
            ls, n, g = f(params, jnp.asarray(b["tokens"][r]),
                         jnp.asarray(b["labels"][r]))
            tot, cnt = tot + ls, cnt + n
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda x: x / cnt, grads)
        losses.append(float(tot / cnt))
        params, m, v, gc = adamw_step(params, grads, m, v, s, opt)
        if s == 0:
            g_first = leaf_norms(gc)
        del grads, gc
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, p0))
    return {"losses": losses, "grad": g_first, "change": change}


def gap(prog: Dict[str, float], ref: Dict[str, float],
        keep=None, own: bool = False) -> Tuple[float, str]:
    """Worst leaf's |‖prog‖ - ‖ref‖| over the larger of that leaf's
    reference norm and the median leaf's, or with ``own`` over that
    leaf's reference norm alone."""
    names = [n for n in ref if keep is None or n in keep]
    med = 0.0 if own or not names else \
        float(np.median([ref[n] for n in names]))
    worst, at = 0.0, ""
    for n in names:
        g = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if g > worst:
            worst, at = g, n
    return worst, at


def compared(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The four numbers compared.  ``change_gap`` leaves out the leaves
    whose reference gradient is under a thousandth of the median
    leaf's, and holds the others at the median leaf's scale.
    ``own_change_gap`` holds every leaf at its own scale: no leaf of
    this model has a gradient that is nought but for rounding, and
    under Adam a leaf with a small gradient moves as far as any."""
    g = ref["grad"]
    med = float(np.median(list(g.values())))
    keep = {n for n, v in g.items() if v >= 1e-3 * med}
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(prog["losses"], ref["losses"]))
    gg, g_at = gap(prog["grad"], g)
    c, c_at = gap(prog["change"], ref["change"], keep)
    o, o_at = gap(prog["change"], ref["change"], own=True)
    return {"loss_gap": loss, "grad_gap": gg, "change_gap": c,
            "own_change_gap": o, "grad_gap_leaf": g_at,
            "change_gap_leaf": c_at, "own_change_gap_leaf": o_at,
            "excluded": sorted(set(g) - keep)}
