"""Plain reference of one pipeline stage of Nemotron-H training: its
Mamba-2, expert and attention layers, the loss, the gradients and three
AdamW steps, in float32 at the highest matmul precision, written from
the layer equations and imported from nothing of the program.

Weights and token batches are made here from the seed
(``make_params``/``make_batch``); the benchmark hands the same arrays to
the program, so both start from the same state.  The parameter tree is
the program's layout: ``embed.w [V,D]``, ``lm_head.w [D,V]``,
``final_norm.w``, and per layer ``blocks.l<i>`` (one stacked block, a
leading axis of 1) with ``ln1.w`` and one mixer:

    M  ssm.{in_proj [D, 2di+2GN+H], conv_w [W, di+2GN], conv_b, A_log,
           D_skip, dt_bias, out_norm [di], out_proj [di, D]}
    E  ffn.{router [D, E], experts.{wi [held, D, 1, F], wo [held, F, D]},
           shared.{wi [D, 1, Fs], wo [Fs, D]}}
    *  attn.{wq [D, KV, H/KV, hd], wk [D, KV, hd], wv, wo [KV, H/KV, hd, D]}

Each layer is  h += mixer(rmsnorm(h) * (1 + ln1.w)):

    M  z, x, B, C, dt = split(u @ in_proj)
       x, B, C = silu(causal_depthwise_conv(x || B || C) + conv_b)
       dt = softplus(dt + dt_bias);   a = -exp(A_log) * dt
       y_i = sum_{j<=i} exp(sum_{j<k<=i} a_k) (C_i . B_j) dt_j x_j + D x_i
       out = groupnorm_G(y * silu(z)) @ out_proj   (RMSNorm over each of
             the G groups' di/G channels, gains 1 + out_norm)
    E  s = sigmoid(u @ router) over all E experts; the top k by s; gates
       g = 2.5 * s_top / sum(s_top); out = sum over the top k that are
       held of g_e relu(u @ wi_e)^2 @ wo_e, plus relu(u @ wi_s)^2 @ wo_s
    *  causal softmax attention, 32 query heads sharing 2 KV heads of
       128, scale 1/sqrt(128), no rotary embedding; out = o @ wo

The scan is taken in blocks of 128 positions, attention in blocks of
256 queries, each against every key with the causal mask, and the loss
in blocks of 1024 positions; the experts are a loop over the held ones,
each gathering the rows routed to it.
One row of the batch runs at a time, each layer rematerialised in the
backward pass, so that it fits.  The loss is the mean token
cross-entropy over the vocabulary slice.

``precision="fp8"`` is the control: every matrix product takes its
operands (and, in the backward pass, its cotangent) rounded to
float8_e4m3fn, the step below the bfloat16 the configuration computes
in.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .common import rng_for, seed_words
from .mamba2_ref import _rmsnorm, adamw_step, gap, matmul
from .mamba2_ref import compared as _compared

SCAN_BLOCK = 128
ATTN_BLOCK = 256
LOSS_BLOCK = 1024


def dims(cfg: Dict) -> Dict:
    """The configuration's sizes under short names."""
    H = cfg["mamba_num_heads"]
    P = cfg["mamba_head_dim"]
    return dict(D=cfg["hidden_size"], di=H * P, H=H, P=P,
                N=cfg["ssm_state_size"], G=cfg["n_groups"],
                W=cfg["conv_kernel"], V=cfg["vocab_size"],
                E=cfg["n_routed_experts_published"],
                held=cfg["n_routed_experts"], off=cfg["expert_offset"],
                K=cfg["num_experts_per_tok"], F=cfg["moe_intermediate_size"],
                Fs=cfg["moe_shared_expert_intermediate_size"],
                QH=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], scale=cfg["routed_scaling_factor"],
                pattern=cfg["hybrid_override_pattern"],
                eps=cfg["layer_norm_epsilon"])


def param_shapes(cfg: Dict) -> Dict:
    k = dims(cfg)
    D, di, H, N, G, W = k["D"], k["di"], k["H"], k["N"], k["G"], k["W"]
    conv = di + 2 * G * N
    kinds = {
        "M": {"ssm": {"in_proj": (D, 2 * di + 2 * G * N + H),
                      "conv_w": (W, conv), "conv_b": (conv,),
                      "A_log": (H,), "D_skip": (H,), "dt_bias": (H,),
                      "out_norm": (di,), "out_proj": (di, D)}},
        "E": {"ffn": {"router": (D, k["E"]),
                      "experts": {"wi": (k["held"], D, 1, k["F"]),
                                  "wo": (k["held"], k["F"], D)},
                      "shared": {"wi": (D, 1, k["Fs"]),
                                 "wo": (k["Fs"], D)}}},
        "*": {"attn": {"wq": (D, k["KV"], k["QH"] // k["KV"], k["hd"]),
                       "wk": (D, k["KV"], k["hd"]),
                       "wv": (D, k["KV"], k["hd"]),
                       "wo": (k["KV"], k["QH"] // k["KV"], k["hd"], D)}},
    }
    blocks = {}
    for i, c in enumerate(k["pattern"]):
        layer = dict(kinds[c], ln1={"w": (D,)})
        blocks[f"l{i}"] = jax.tree_util.tree_map(
            lambda s: (1, *s), layer, is_leaf=lambda s: isinstance(s, tuple))
    return {"embed": {"w": (k["V"], D)}, "blocks": blocks,
            "final_norm": {"w": (D,)}, "lm_head": {"w": (D, k["V"])}}


def make_params(cfg: Dict, seed: int):
    """Seeded weights in one jitted call on the default device (f32, the
    configuration's parameter type).  Matrices ~ N(0, 0.02^2); A = 1..16
    uniform (A_log its log); dt_bias the inverse softplus of a
    1e-3..1e-1 uniform (the published time_step_min/max); D = 1; norm
    gains (stored as w of 1 + w) and the conv bias 0."""
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
                v = jax.random.normal(k, shape, jnp.float32) * 0.02
            out.append(v)
        return jax.tree_util.tree_unflatten(tdef, out)

    return jax.jit(init)(key)


def make_batch(cfg: Dict, seed: int, step: int, batch: int, seq: int
               ) -> Dict[str, np.ndarray]:
    """Uniform random tokens of the vocabulary slice; labels are the
    next token, the last position ignored (-1)."""
    rng = rng_for(seed, 7, step)
    toks = rng.integers(0, cfg["vocab_size"], (batch, seq), dtype=np.int32)
    labels = np.full((batch, seq), -1, np.int32)
    labels[:, :-1] = toks[:, 1:]
    return {"tokens": toks, "labels": labels}


# -- layers, one row h [S, D] ------------------------------------------- #
def _ssd(x, dt, a, Bm, Cm, q, mm):
    """y_i = sum_{j<=i} exp(A_i - A_j) (C_i . B_j) dt_j x_j, A the running
    sum of ``a``, for one row: x [S,H,P], dt and a [S,H], B and C
    [S,G,N], head h reading group h // (H/G).  Within each block of
    ``q`` positions the sum is taken in full; across blocks through the
    carried state h [H,P,N].  Each block is rematerialised in the
    backward pass."""
    S, H, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    rep = H // G
    nb = S // q
    xdt = (x * dt[:, :, None]).reshape(nb, q, H, P)
    i = jnp.arange(q)
    causal = i[:, None] >= i[None, :]

    @jax.checkpoint
    def block(h, inp):
        xd, Bb, Cb, aa = inp                  # [q,H,P], [q,G,N] x2, [q,H]
        A = jnp.cumsum(aa, axis=0)                                # [q, H]
        diff = A[:, None, :] - A[None, :, :]                      # [i, j, H]
        decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
        cb = mm(jnp.transpose(Cb, (1, 0, 2)),
                jnp.transpose(Bb, (1, 2, 0)))                     # [G, i, j]
        w = jnp.repeat(cb, rep, axis=0) * jnp.transpose(decay, (2, 0, 1))
        y = mm(w, jnp.transpose(xd, (1, 0, 2)))                   # [H, i, P]
        Ch = jnp.repeat(jnp.transpose(Cb, (1, 0, 2)), rep, axis=0)
        y = y + jnp.exp(A).T[:, :, None] * mm(
            Ch, jnp.transpose(h, (0, 2, 1)))                      # [H, i, P]
        tail = jnp.exp(A[-1][None, :] - A)                        # [q, H]
        Bh = jnp.repeat(Bb, rep, axis=1) * tail[:, :, None]       # [q, H, N]
        upd = mm(jnp.transpose(xd, (1, 2, 0)),
                 jnp.transpose(Bh, (1, 0, 2)))                    # [H, P, N]
        h = jnp.exp(A[-1])[:, None, None] * h + upd
        return h, jnp.transpose(y, (1, 0, 2))

    _, ys = lax.scan(block, jnp.zeros((H, P, N), jnp.float32),
                     (xdt, Bm.reshape(nb, q, G, N), Cm.reshape(nb, q, G, N),
                      a.reshape(nb, q, H)))
    return ys.reshape(S, H, P)


def _mamba(h, p, k, mm):
    S = h.shape[0]
    di, H, P, N, G, W = k["di"], k["H"], k["P"], k["N"], k["G"], k["W"]
    s = p["ssm"]
    u = _rmsnorm(h, p["ln1"]["w"], k["eps"])
    zx = mm(u, s["in_proj"])
    z = zx[:, :di]
    xbc = zx[:, di:2 * di + 2 * G * N]
    dt = zx[:, 2 * di + 2 * G * N:]
    xp = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(xp[i:i + S] * s["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + s["conv_b"])
    x = xbc[:, :di].reshape(S, H, P)
    Bm = xbc[:, di:di + G * N].reshape(S, G, N)
    Cm = xbc[:, di + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + s["dt_bias"])
    a = -jnp.exp(s["A_log"]) * dt
    y = _ssd(x, dt, a, Bm, Cm, min(SCAN_BLOCK, S), mm)
    y = y + x * s["D_skip"][None, :, None]
    g = (y.reshape(S, di) * jax.nn.silu(z)).reshape(S, G, di // G)
    g = _rmsnorm(g, s["out_norm"].reshape(G, di // G), k["eps"])
    return h + mm(g.reshape(S, di), s["out_proj"])


def _attention(h, p, k, mm):
    S, D = h.shape
    KV, hd = k["KV"], k["hd"]
    R = k["QH"] // KV
    a = p["attn"]
    u = _rmsnorm(h, p["ln1"]["w"], k["eps"])
    q = mm(u, a["wq"].reshape(D, -1)).reshape(S, KV, R, hd)
    kk = mm(u, a["wk"].reshape(D, -1)).reshape(S, KV, hd)
    v = mm(u, a["wv"].reshape(D, -1)).reshape(S, KV, hd)
    kT = jnp.broadcast_to(jnp.transpose(kk, (1, 2, 0))[:, None],
                          (KV, R, hd, S))
    vv = jnp.broadcast_to(jnp.transpose(v, (1, 0, 2))[:, None],
                          (KV, R, S, hd))
    bq = min(ATTN_BLOCK, S)
    pos = jnp.arange(S)

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * bq, bq, axis=0)
        sc = mm(jnp.transpose(qb, (1, 2, 0, 3)), kT) / np.sqrt(hd)
        qpos = i * bq + jnp.arange(bq)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), vv)          # [KV,R,bq,hd]

    o = lax.map(block, jnp.arange(S // bq))                  # [nb,KV,R,bq,hd]
    o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(S, -1)
    return h + mm(o, a["wo"].reshape(-1, D))


def _experts(h, p, k, mm):
    """Returns (h, rows routed to each held expert [held])."""
    S, D = h.shape
    f = p["ffn"]
    u = _rmsnorm(h, p["ln1"]["w"], k["eps"])
    scores = jax.nn.sigmoid(mm(u, f["router"]))              # [S, E]
    top, idx = lax.top_k(scores, k["K"])
    gates = k["scale"] * top / jnp.sum(top, axis=-1, keepdims=True)
    upad = jnp.concatenate([u, jnp.zeros((1, D), u.dtype)])

    @jax.checkpoint
    def expert(out, xs):                      # one held expert
        wi, wo, e = xs
        hit = idx == k["off"] + e                             # [S, K]
        g = jnp.sum(jnp.where(hit, gates, 0.0), axis=-1)      # [S]
        sel = jnp.any(hit, axis=-1)
        rows = jnp.nonzero(sel, size=S, fill_value=S)[0]
        he = jnp.square(jax.nn.relu(mm(upad[rows], wi)))      # gathered
        gpad = jnp.concatenate([g, jnp.zeros((1,), g.dtype)])[rows]
        out = out.at[rows].add(mm(he, wo) * gpad[:, None], mode="drop")
        return out, jnp.sum(sel)

    out, counts = lax.scan(expert, jnp.zeros_like(h),
                           (f["experts"]["wi"][:, :, 0], f["experts"]["wo"],
                            jnp.arange(k["held"])))
    hs = jnp.square(jax.nn.relu(mm(u, f["shared"]["wi"][:, 0])))
    out = out + mm(hs, f["shared"]["wo"])
    return h + out, counts


def row_loss(params, tokens, labels, k, mm):
    """Summed cross-entropy, its count and the held experts' rows per
    expert layer [n_E, held] of one row."""
    h = params["embed"]["w"][tokens]
    counts = []
    for i, c in enumerate(k["pattern"]):
        p = jax.tree_util.tree_map(lambda x: x[0], params["blocks"][f"l{i}"])
        if c == "M":
            h = jax.checkpoint(lambda h, p: _mamba(h, p, k, mm))(h, p)
        elif c == "*":
            h = jax.checkpoint(lambda h, p: _attention(h, p, k, mm))(h, p)
        else:
            h, n = jax.checkpoint(lambda h, p: _experts(h, p, k, mm))(h, p)
            counts.append(n)
    h = _rmsnorm(h, params["final_norm"]["w"], k["eps"])
    S = h.shape[0]
    bq = min(LOSS_BLOCK, S)

    @jax.checkpoint
    def block(xs):                            # the loss of bq positions
        hb, lb = xs
        logits = mm(hb, params["lm_head"]["w"])              # [bq, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[:, None],
                                   axis=-1)[:, 0]
        return jnp.sum((lse - gold) * (lb >= 0))

    nll = lax.map(block, (h.reshape(S // bq, bq, -1),
                          labels.reshape(S // bq, bq)))
    return jnp.sum(nll), (jnp.sum(labels >= 0).astype(jnp.float32),
                          jnp.stack(counts))


def loss_and_grad_fn(cfg: Dict, precision: str):
    """f(params, tokens, labels, acc) -> (summed loss, count, rows per
    expert layer and held expert, acc + gradient); ``acc`` is donated,
    so that a row's gradient is added in place."""
    k = dims(cfg)
    mm = matmul(precision)

    def f(params, tokens, labels, acc):
        (s, (n, counts)), g = jax.value_and_grad(
            lambda p: row_loss(p, tokens, labels, k, mm),
            has_aux=True)(params)
        return s, n, counts, jax.tree_util.tree_map(jnp.add, acc, g)

    return jax.jit(f, donate_argnums=3)


def reference_run(cfg: Dict, opt: Dict, seed: int, batch: int, seq: int,
                  steps: int = 3, precision: str = "f32"):
    """Losses of ``steps`` steps, per-leaf norms of the first step's
    clipped gradient and of the parameters' change over the steps, and
    the rows the held experts were routed (``rows``: summed over steps,
    layers and experts; ``max_rows``: the largest held expert's rows of
    each step and layer, summed).  Through a step's rows the device
    holds the parameters and one gradient, updated in place; Adam's two
    moments and the starting parameters wait on the host."""
    def update(s):
        def u(p, g, m, v, cnt):
            g = jax.tree_util.tree_map(lambda x: x / cnt, g)
            p, m, v, gc = adamw_step(p, g, m, v, s, opt)
            return p, m, v, jax.tree_util.tree_map(
                lambda x: jnp.sqrt(jnp.sum(x * x)), gc)
        return jax.jit(u, donate_argnums=(0, 1, 2, 3))

    with jax.default_matmul_precision("highest"):
        f = loss_and_grad_fn(cfg, precision)
        params = make_params(cfg, seed)
        p0 = jax.device_get(params)
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
        m = v = jax.tree_util.tree_map(np.zeros_like, p0)     # on the host
        losses: List[float] = []
        g_first = None
        rows = max_rows = 0
        for s in range(steps):
            b = make_batch(cfg, seed, s, batch, seq)
            tot, cnt, counts = 0.0, 0.0, 0
            grads = zeros(params)
            for r in range(batch):
                ls, n, c, grads = f(params, jnp.asarray(b["tokens"][r]),
                                    jnp.asarray(b["labels"][r]), grads)
                tot, cnt = tot + float(ls), cnt + float(n)
                counts = counts + np.asarray(c)
            losses.append(tot / cnt)
            rows += int(counts.sum())
            max_rows += int(counts.max(axis=1).sum())
            params, m, v, gnorm = update(s)(params, grads,
                                            jax.device_put(m),
                                            jax.device_put(v),
                                            jnp.float32(cnt))
            m, v = jax.device_get((m, v))
            if s == 0:
                g_first = _flat(gnorm)
            del grads
        del m, v
        change = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))(
            params, jax.device_put(p0))
    return {"losses": losses, "grad": g_first, "change": _flat(change),
            "rows": rows, "max_rows": max_rows}


def _flat(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}


def compared(prog: Dict, ref: Dict) -> Dict:
    """The numbers ``mamba2_ref.compared`` gives, plus ``rows_gap``: the
    larger relative gap of the held experts' rows and of the largest held
    expert's rows, the program's counters against the reference's
    routing (a layer that drops routed rows reads low on both)."""
    out = _compared(prog, ref)
    out["rows_gap"] = max(
        abs(prog["rows"] - ref["rows"]) / max(ref["rows"], 1),
        abs(prog["max_rows"] - ref["max_rows"]) / max(ref["max_rows"], 1))
    return out


__all__ = ["dims", "param_shapes", "make_params", "make_batch",
           "reference_run", "compared", "gap"]
