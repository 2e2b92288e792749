"""Plain reference of the pre-LN decoder LM that both configurations run.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
with no kernel, cache or batching, written from the model's equations and
importing nothing of the program under test:

  embed     z_0 = E[tokens]                      (no positional embedding)
  layer     z_{n+1} = z_n + h_n * g_n * F(z_n)   (neural-ODE Euler step)
  F(z)      a = Attn(Norm1(z));  F = a + MLP(Norm2(z + a))
  Attn      q, k, v projections, optional per-head RMS qk-norm, rotary
            embedding (half-split), causal softmax(q k^T / sqrt(hd)) v, o
  MLP       GELU (tanh form): W_out gelu(W_in x)
            SwiGLU:           W_out (silu(W_gate x) * W_in x)
  head      logits = Norm_f(z_N) U^T, U = E when the embeddings are tied
  loss      mean token cross-entropy

The layers sit in three stacks: ``open`` and ``close`` buffer layers with
h = 1, and the ``mid`` trunk with the trunk step ``h`` and a 0/1 gate per
layer. Training follows the layer-parallel algorithm: the trunk's forward
is a serial solve (or MGRIT V-cycles) and its gradient comes from the
adjoint equation solved by MGRIT with the configured iteration count
(``fwd_iters``/``bwd_iters``; 0 = exact serial). AdamW with global-norm
clipping and a warmup-cosine schedule updates every leaf but the gates.

``mm`` selects the arithmetic of every matrix product: "f32" (the
reference) or "fp8" (operands rounded to float8 e4m3 with a per-tensor
scale: the control, one precision step below the configurations' bf16).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # layernorm | rmsnorm
    act: str             # gelu | silu
    qk_norm: bool
    tie: bool
    rope_theta: float
    n_open: int
    n_close: int
    n_mid: int           # real trunk layers
    n_mid_padded: int    # trunk layers incl. identity padding (gate 0)
    h: float             # trunk step
    cf: int = 4
    fwd_iters: int = 0
    bwd_iters: int = 1


def dims_from(model: Dict[str, Any], mgrit: Dict[str, Any]) -> Dims:
    """Dims from a configuration file's ``program`` section."""
    if mgrit.get("levels", 2) != 2:
        raise ValueError("the reference's MGRIT has two levels only")
    n_mid = model["n_layers"] - mgrit["n_open"] - mgrit["n_close"]
    pad = mgrit.get("pad_to", 0)
    n_pad = -(-n_mid // pad) * pad if pad else n_mid
    return Dims(
        n_layers=model["n_layers"], d_model=model["d_model"],
        n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
        head_dim=model.get("head_dim") or model["d_model"] // model["n_heads"],
        d_ff=model["d_ff"], vocab=model["vocab_size"], norm=model["norm"],
        act=model["act"], qk_norm=model.get("qk_norm", False),
        tie=model.get("tie_embeddings", False),
        rope_theta=model.get("rope_theta", 10000.0),
        n_open=mgrit["n_open"], n_close=mgrit["n_close"], n_mid=n_mid,
        n_mid_padded=n_pad, h=mgrit.get("h", 1.0), cf=mgrit.get("cf", 4),
        fwd_iters=mgrit.get("fwd_iters", 0),
        bwd_iters=mgrit.get("bwd_iters", 1))


# ---------------------------------------------------------------------------
# Parameters: shapes and initial scales (the benchmark's weights generator
# reads these; the program's tree has the same nesting)
# ---------------------------------------------------------------------------


def block_shapes(d: Dims) -> Dict[str, Any]:
    """{name: (shape, init)}; init is ("normal", std) | "ones" | "zeros"."""
    out_std = 0.02 / max(1.0, math.sqrt(math.log(2 * d.n_layers)))
    norm = {"scale": ((d.d_model,), "ones")}
    if d.norm == "layernorm":
        norm["bias"] = ((d.d_model,), "zeros")
    attn = {
        "wq": ((d.d_model, d.n_heads, d.head_dim), ("normal", 0.02)),
        "wk": ((d.d_model, d.n_kv_heads, d.head_dim), ("normal", 0.02)),
        "wv": ((d.d_model, d.n_kv_heads, d.head_dim), ("normal", out_std)),
        "wo": ((d.n_heads, d.head_dim, d.d_model), ("normal", out_std)),
    }
    if d.qk_norm:
        attn["q_norm"] = ((d.head_dim,), "ones")
        attn["k_norm"] = ((d.head_dim,), "ones")
    mlp = {"w_in": ((d.d_model, d.d_ff), ("normal", 0.02)),
           "w_out": ((d.d_ff, d.d_model), ("normal", out_std))}
    if d.act == "silu":
        mlp["w_gate"] = ((d.d_model, d.d_ff), ("normal", 0.02))
    return {"ln1": dict(norm), "attn": attn, "ln2": dict(norm), "mlp": mlp}


def param_shapes(d: Dims) -> Dict[str, Any]:
    """The whole tree: leaves are (shape, init); stacked leaves carry the
    layer count first. ``mid.gate`` is 1 on real layers, 0 on padding."""
    def stacked(n):
        return jax.tree.map(lambda s: ((n,) + s[0], s[1]), block_shapes(d),
                            is_leaf=lambda x: isinstance(x, tuple)
                            and isinstance(x[0], tuple))
    embed = {"tok": ((d.vocab, d.d_model), ("normal", 0.02))}
    if not d.tie:
        embed["out"] = ((d.vocab, d.d_model), ("normal", 0.02))
    final = {"scale": ((d.d_model,), "ones")}
    if d.norm == "layernorm":
        final["bias"] = ((d.d_model,), "zeros")
    tree = {"embed": embed, "final_norm": final,
            "mid": {"params": stacked(d.n_mid_padded),
                    "gate": ((d.n_mid_padded,), ("gate", d.n_mid))}}
    if d.n_open:
        tree["open"] = stacked(d.n_open)
    if d.n_close:
        tree["close"] = stacked(d.n_close)
    return tree


def is_shape_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------


def _f8(x):
    """Round to float8 e4m3 with a per-tensor absmax scale."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(eq: str, a, b, mode: str):
    """Every matrix product of the reference goes through here."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _f8(a), _f8(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def norm(p, x, d: Dims):
    if d.norm == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + EPS) \
        * p["scale"]


def rope(x, positions, theta: float):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv       # (S, half)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(p, x, d: Dims, mode: str):
    B, S, _ = x.shape
    q = mm("bsd,dhk->bshk", x, p["wq"], mode)
    k = mm("bsd,dhk->bshk", x, p["wk"], mode)
    v = mm("bsd,dhk->bshk", x, p["wv"], mode)
    if d.qk_norm:
        q = q / jnp.sqrt(jnp.mean(q * q, -1, keepdims=True) + EPS) \
            * p["q_norm"]
        k = k / jnp.sqrt(jnp.mean(k * k, -1, keepdims=True) + EPS) \
            * p["k_norm"]
    pos = jnp.arange(S)
    q, k = rope(q, pos, d.rope_theta), rope(k, pos, d.rope_theta)
    g = d.n_heads // d.n_kv_heads
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = mm("bqhk,bshk->bhqs", q, k, mode) / math.sqrt(d.head_dim)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bhqs,bshk->bqhk", a, v, mode)
    return mm("bqhk,hkd->bqd", o, p["wo"], mode)


def mlp(p, x, d: Dims, mode: str):
    h = mm("bsd,df->bsf", x, p["w_in"], mode)
    if d.act == "silu":
        g = mm("bsd,df->bsf", x, p["w_gate"], mode)
        h = g * jax.nn.sigmoid(g) * h
    else:
        h = gelu_tanh(h)
    return mm("bsf,fd->bsd", h, p["w_out"], mode)


def block_F(p, z, d: Dims, mode: str):
    a = attention(p["attn"], norm(p["ln1"], z, d), d, mode)
    return a + mlp(p["mlp"], norm(p["ln2"], z + a, d), d, mode)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _scan_layers(stack, z, d: Dims, mode: str, h, gates=None):
    """z through a stack of layers: z += h * gate * F(z)."""
    @jax.checkpoint
    def step(z, xs):
        p, g = xs
        return z + h * g * block_F(_f32(p), z, d, mode), None
    n = jax.tree.leaves(stack)[0].shape[0]
    g = jnp.ones((n,), jnp.float32) if gates is None else gates
    z, _ = jax.lax.scan(step, z, (stack, g.astype(jnp.float32)))
    return z


def head(params, z, d: Dims, mode: str):
    zf = norm(_f32(params["final_norm"]), z, d)
    u = params["embed"]["tok"] if d.tie else params["embed"]["out"]
    return mm("bsd,vd->bsv", zf, u, mode)


def xent(logits, labels):
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


def embed(params, tokens):
    return params["embed"]["tok"].astype(jnp.float32)[tokens]


def logits_serial(params, tokens, d: Dims, mode: str = "f32"):
    """Full causal forward of (B, S) tokens through every layer."""
    z = embed(params, tokens)
    if d.n_open:
        z = _scan_layers(params["open"], z, d, mode, 1.0)
    z = _scan_layers(params["mid"]["params"], z, d, mode, d.h,
                     params["mid"]["gate"])
    if d.n_close:
        z = _scan_layers(params["close"], z, d, mode, 1.0)
    return head(params, z, d, mode)


# ---------------------------------------------------------------------------
# Layer-parallel gradient: MGRIT over the trunk, forward and adjoint
# ---------------------------------------------------------------------------


def _serial_solve(step, stacked, z0, h, g=None):
    """z_{n+1} = step(slot_n, z_n, h) (+ g_n); states[n] = z_n."""
    def body(z, xs):
        slot, gn = xs
        z2 = step(slot, z, h)
        return (z2 if gn is None else z2 + gn), z
    zT, states = jax.lax.scan(body, z0, (stacked, g))
    return states, zT


def _take(tree, idx):
    return jax.tree.map(lambda a: a[idx], tree)


def _mgrit(step, stacked, z0, h, cf: int, iters: int):
    """Two-level FAS MGRIT with FCF relaxation: coarse-grid start, then
    ``iters`` V-cycles (the last one ends in an F-relaxation)."""
    N = jax.tree.leaves(stacked)[0].shape[0]
    J = N // cf
    chunk = jax.tree.map(lambda a: a.reshape((J, cf) + a.shape[1:]), stacked)
    coarse = _take(stacked, slice(None, None, cf))

    def f_relax(Zc):
        def one(z, slots):
            out = [z]
            for i in range(cf - 1):
                z = step(_take(slots, i), z, h)
                out.append(z)
            return jnp.stack(out)
        return jax.vmap(one)(Zc, chunk)

    def c_step(U):
        return jax.vmap(lambda s, u: step(s, u, h))(_take(chunk, (
            slice(None), cf - 1)), U[:, cf - 1])

    cs, zT = _serial_solve(step, coarse, z0, h * cf)
    U = f_relax(cs)
    for it in range(max(iters, 1)):
        Zc = U[:, 0]
        W = c_step(f_relax(Zc))
        Zc = jnp.concatenate([z0[None], W[:-1]])
        zT = W[-1]
        W = c_step(f_relax(Zc))
        u0 = jnp.concatenate([Zc, zT[None]])
        g_c = W - jax.vmap(lambda s, u: step(s, u, h * cf))(coarse, u0[:-1])
        cst, czT = _serial_solve(step, coarse, z0, h * cf, g=g_c)
        e = jnp.concatenate([cst, czT[None]]) - u0
        Zc, zT = Zc + e[:-1], zT + e[-1]
        U = f_relax(Zc) if it == max(iters, 1) - 1 else U.at[:, 0].set(Zc)
    return U.reshape((N,) + U.shape[2:]), zT


def lp_grads(params, batch, d: Dims, mode: str = "f32"):
    """(loss, grads) with the trunk's gradient from the MGRIT adjoint."""
    tokens, labels = batch["tokens"], batch["labels"]
    mid = params["mid"]
    h = d.h

    def pre(p):
        z = embed(p, tokens)
        return _scan_layers(p["open"], z, d, mode, 1.0) if d.n_open else z

    def post(p, zT):
        z = _scan_layers(p["close"], zT, d, mode, 1.0) if d.n_close else zT
        return xent(head(p, z, d, mode), labels)

    def F(p, z):
        return block_F(_f32(p), z, d, mode)

    def fwd(slot, z, hh):
        return z + hh * slot["gate"] * F(slot["params"], z)

    z0, pre_vjp = jax.vjp(pre, params)
    fslots = {"params": mid["params"], "gate": mid["gate"]}
    if d.fwd_iters <= 0:
        states, zT = _serial_solve(fwd, fslots, z0, h)
    else:
        states, zT = _mgrit(fwd, fslots, z0, h, d.cf, d.fwd_iters)
    loss, post_vjp = jax.vjp(post, params, zT)
    g_post, lamN = post_vjp(jnp.ones_like(loss))

    def adj(slot, lam, hh):
        _, vjp = jax.vjp(lambda z: F(slot["params"], z), slot["z"])
        return lam + hh * slot["gate"] * vjp(lam)[0]

    rev = lambda a: jnp.flip(a, 0)
    aslots = {"params": jax.tree.map(rev, mid["params"]),
              "gate": rev(mid["gate"]), "z": rev(states)}
    if d.bwd_iters <= 0:
        mu, lam0 = _serial_solve(adj, aslots, lamN, h)
    else:
        mu, lam0 = _mgrit(adj, aslots, lamN, h, d.cf, d.bwd_iters)
    lam_next = rev(mu)                       # lam_next[n] = lambda_{n+1}

    def pgrad(p, gate, z, lam):
        _, vjp = jax.vjp(lambda pp: F(pp, z), p)
        return vjp(h * gate * lam)[0]
    g_mid = jax.vmap(pgrad)(mid["params"], mid["gate"], states, lam_next)
    (g_pre,) = pre_vjp(lam0)
    grads = jax.tree.map(lambda a, b: a + b, g_pre, g_post)
    grads["mid"] = {"params": jax.tree.map(lambda a, b: a + b,
                                           grads["mid"]["params"], g_mid),
                    "gate": jnp.zeros_like(mid["gate"])}
    return loss, grads


def serial_grads(params, batch, d: Dims, mode: str = "f32"):
    def loss(p):
        return xent(logits_serial(p, batch["tokens"], d, mode),
                    batch["labels"])
    val, grads = jax.value_and_grad(loss)(params)
    grads["mid"]["gate"] = jnp.zeros_like(grads["mid"]["gate"])
    return val, grads


# ---------------------------------------------------------------------------
# AdamW (global-norm clipping, linear warmup then cosine to 10%)
# ---------------------------------------------------------------------------


def lr_at(opt: Dict[str, Any], step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * t)))


def adamw(params, grads, m, v, step: int, opt: Dict[str, Any]):
    """One update; returns (params, m, v, clipped grads)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = lr_at(opt, step)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, a, b):
        return p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + opt["eps"])
                         + opt["weight_decay"] * p)
    new = jax.tree.map(upd, params, m, v)
    new["mid"]["gate"] = params["mid"]["gate"]
    return new, m, v, grads


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in flat}


def train_readings(params0, batches: List[Dict[str, np.ndarray]], d: Dims,
                   opt: Dict[str, Any], lp: bool, mode: str = "f32"):
    """The readings that decide a training cell: the loss of each step,
    each leaf's norm of the first (clipped) gradient, and each leaf's norm
    of the parameters' change after ``len(batches)`` steps."""
    gfn = jax.jit(lambda p, b: (lp_grads if lp else serial_grads)(
        p, b, d, mode))
    ufn = jax.jit(lambda p, g, m, v, s: adamw(p, g, m, v, s, opt),
                  static_argnums=(4,))
    p = _f32(params0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, first = [], None
    for i, b in enumerate(batches):
        loss, g = gfn(p, {k: jnp.asarray(a) for k, a in b.items()})
        p, m, v, gc = ufn(p, g, m, v, i + 1)
        losses.append(float(loss))
        if first is None:
            first = {k: float(x) for k, x in leaf_norms(gc).items()}
    change = {k: float(x) for k, x in leaf_norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), p, params0)).items()}
    return losses, first, change


# ---------------------------------------------------------------------------
# Serving: logits of whole sequences, one at a time
# ---------------------------------------------------------------------------


def make_logits_fn(d: Dims, mode: str):
    """jitted (params, tokens (S,)) -> logits (S, vocab)."""
    @jax.jit
    def fn(params, tokens):
        return logits_serial(params, tokens[None], d, mode)[0]
    return fn
