"""The plain reference: the configurations' forward pass, loss and AdamW
step in straightforward ``jax.numpy`` at float32, matrix products at
``highest`` precision, with no cache, batching, chunking or kernel.

This module is the reference of every configuration file that names no
other (``"reference"``, see ``spec.py``); it reads two kinds, Mamba-2
and dense attention with a gated MLP.  A configuration of another
architecture brings its own module under ``references/``, which may
import this one, and meets the same contract:

  serving (``serve.compare``)
    ``Sizes.from_file(config_file)``: the sizes, from the configuration
    file as a dict; it raises on a file it cannot read
    ``init_weights(sz, seed)``: the weights the program draws from that
    seed, drawn here by the initialization the program documents
    ``forward_logits(sz, w, tokens, pad_to, dtype="f32")``: float32
    logits of every position; ``dtype="fp8"`` is the control
  training (``train.compare``)
    ``train_readings(sz, seed, batches, job, dtype="f32", frozen=False)``
    -> ``{"losses", "first_grad", "raw_grad", "change"}``, the norms per
    leaf under the program's leaf names.  The controls call it with
    ``dtype="fp8"``, with ``frozen=True`` (a step that returns its state
    unchanged) and on the first half of each batch.

A reference imports nothing of the program and takes nothing the program
made: no weights, scales or tables.  Its sizes come from the configuration
file and its weights from the seed, by the initialization the program
documents (one key split 64 ways, drawn in the order of the parameter
tree, each weight a normal draw times its scale, stored in bfloat16), so
that the same seed gives the same weights on both sides without one side
handing them to the other.

``dtype="fp8"`` is the serving cell's control (and a reading for the
training cells): the same computation in float8, the precision below the
configurations' bfloat16, as float8 training runs it:
both operands of every matrix product rounded to e4m3 on the way forward,
the product's cotangent rounded to e5m2 on the way back, each with one
scale per tensor.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from spec import FIELDS, HERE, padded_vocab

F32 = jnp.float32
NORM_EPS = 1e-6
#: the configuration keys each kind reads
KEYS = {"ssm": ("n_layer", "d_model", "vocab_size", "d_state", "expand",
                "headdim", "d_conv", "torch_dtype"),
        "dense": ("num_hidden_layers", "hidden_size", "vocab_size",
                  "num_attention_heads", "num_key_value_heads", "head_dim",
                  "intermediate_size", "rope_theta", "rms_norm_eps",
                  "torch_dtype")}


@dataclasses.dataclass(frozen=True)
class Sizes:
    kind: str            # "dense" (attention + gated MLP) or "ssm" (Mamba-2)
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    rope_theta: float = 10_000.0
    d_state: int = 0
    expand: int = 2
    headdim: int = 64
    d_conv: int = 4
    norm_eps: float = NORM_EPS
    dtype: str = "bfloat16"   # the weights' stored type

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.headdim

    @classmethod
    def from_file(cls, config_file: dict) -> "Sizes":
        """The sizes of a Mamba-2 file (one with ``n_layer``) or a dense
        one; a file that names another reference, maps a key this module
        reads to another field, or lacks a key of its kind is an error."""
        name = config_file.get("name")
        named = os.path.normpath(os.path.join(
            HERE, config_file.get("reference", "reference.py")))
        if named != os.path.abspath(__file__):
            raise ValueError(f"{name}: its reference is {named}, not this one")
        c = config_file["config"]
        kind = "ssm" if "n_layer" in c else "dense"
        remapped = sorted(k for k, f in config_file.get("fields", {}).items()
                          if k in KEYS[kind] and FIELDS.get(k) != f)
        missing = [k for k in KEYS[kind] if k not in c]
        faults = ([f"its fields remap {remapped}"] if remapped else []) + \
            ([f"it lacks {missing}"] if missing else [])
        if faults:
            raise ValueError(f"{name}: read as {kind} layers, but "
                             + " and ".join(faults))
        if kind == "ssm":
            return cls(kind="ssm", n_layers=c["n_layer"], d_model=c["d_model"],
                       vocab=padded_vocab(c), d_state=c["d_state"],
                       expand=c["expand"], headdim=c["headdim"],
                       d_conv=c["d_conv"], dtype=c["torch_dtype"])
        return cls(kind="dense", n_layers=c["num_hidden_layers"],
                   d_model=c["hidden_size"], vocab=padded_vocab(c),
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
                   d_ff=c["intermediate_size"], rope_theta=c["rope_theta"],
                   norm_eps=c["rms_norm_eps"], dtype=c["torch_dtype"])


# ------------------------------------------------------------------- init
def init_weights(sz: Sizes, seed: int) -> Dict:
    """Weights from the seed, in the configuration's stored type (norms,
    decays and skips in float32), laid out (layer, ...) per leaf."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    bf = jnp.dtype(sz.dtype)
    L, D = sz.n_layers, sz.d_model

    def normal(k, shape, scale):  # op by op, as the program's init runs
        return (jax.random.normal(k, shape, F32) * scale).astype(bf)

    def dense(*shape, scale=None):
        return normal(next(keys), shape,
                      scale if scale is not None else shape[-2] ** -0.5)

    w = {"embed": dense(sz.vocab, D, scale=0.02),
         "final_norm": jnp.zeros((D,), F32),
         "ln1": jnp.zeros((L, D), F32)}
    if sz.kind == "dense":
        H, KV, dh = sz.n_heads, sz.n_kv_heads, sz.d_head
        w["ln2"] = jnp.zeros((L, D), F32)
        w["wq"] = dense(L, D, H * dh)
        w["wk"] = dense(L, D, KV * dh)
        w["wv"] = dense(L, D, KV * dh)
        w["wo"] = dense(L, H * dh, D)
        w["w_gate"] = dense(L, D, sz.d_ff)
        w["w_up"] = dense(L, D, sz.d_ff)
        w["w_down"] = dense(L, sz.d_ff, D)
    else:
        di, ns, nh = sz.d_inner, sz.d_state, sz.ssm_heads
        w["in_proj"] = dense(L, D, 2 * di + 2 * ns + nh)
        w["conv_w"] = dense(L, sz.d_conv, di + 2 * ns, scale=0.5)
        w["A_log"] = jnp.log(jnp.broadcast_to(
            jnp.linspace(1.0, 16.0, nh), (L, nh))).astype(F32)
        w["D"] = jnp.ones((L, nh), F32)
        w["dt_bias"] = jnp.zeros((L, nh), F32)
        w["norm"] = jnp.zeros((L, di), F32)
        w["out_proj"] = dense(L, di, D)
    return w


LAYER_LEAVES = {"ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down", "in_proj", "conv_w", "A_log", "D", "dt_bias", "norm",
                "out_proj"}


# ------------------------------------------------------------------ maths
def _round(x, dtype, largest: float):
    """Round to a float8 type with one scale per tensor: the tensor's
    largest magnitude maps to the type's largest finite value."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / largest, 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    """An operand at e4m3; its cotangent passes through unchanged."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_product(y):
    """A product, unchanged; its cotangent rounds to e5m2 before the
    backward products use it."""
    return y


_fp8_product.defvjp(lambda y: (y, None),
                    lambda _, g: (_round(g, jnp.float8_e5m2, 57344.0),))


def mm(spec: str, a, b, dtype: str = "f32"):
    a, b = a.astype(F32), b.astype(F32)
    if dtype == "fp8":
        return _fp8_product(jnp.einsum(spec, _fp8_operand(a), _fp8_operand(b),
                                       precision=jax.lax.Precision.HIGHEST))
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(F32))


def rope(x, positions, theta):
    """x (S, H, dh): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def dense_layer(sz: Sizes, p, x, dtype="f32"):
    """One attention + gated-MLP layer over x (S, D), causal."""
    S = x.shape[0]
    H, KV, dh = sz.n_heads, sz.n_kv_heads, sz.d_head
    h = rms_norm(x, p["ln1"], sz.norm_eps)
    pos = jnp.arange(S)
    q = rope(mm("sd,dk->sk", h, p["wq"], dtype).reshape(S, H, dh), pos,
             sz.rope_theta)
    k = rope(mm("sd,dk->sk", h, p["wk"], dtype).reshape(S, KV, dh), pos,
             sz.rope_theta)
    v = mm("sd,dk->sk", h, p["wv"], dtype).reshape(S, KV, dh)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = mm("qhd,khd->hqk", q, k, dtype) * dh ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm("hqk,khd->qhd", probs, v, dtype).reshape(S, H * dh)
    x = x + mm("sk,kd->sd", att, p["wo"], dtype)
    h = rms_norm(x, p["ln2"], sz.norm_eps)
    g = mm("sd,df->sf", h, p["w_gate"], dtype)
    u = mm("sd,df->sf", h, p["w_up"], dtype)
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], dtype)


def ssd(x, dt, A, Bm, Cm, dtype="f32"):
    """The selective state-space recurrence in its quadratic (dual) form:
    y_t = sum_{k<=t} (C_t . B_k) exp(sum_{j=k+1..t} dt_j A) dt_k x_k.
    x (S, nh, hd), dt (S, nh), A (nh,), Bm/Cm (S, ns)."""
    S = x.shape[0]
    dA = dt * A[None, :]                                    # (S, nh)
    later = jnp.arange(S)[:, None] > jnp.arange(S)[None, :]  # t > k
    # segment sums per (t, k): a cumulative sum down t from each k
    seg = jnp.cumsum(jnp.where(later[:, :, None], dA[:, None, :], 0.0), 0)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    decay = jnp.where(causal[:, :, None], jnp.exp(seg), 0.0)  # (S, S, nh)
    cb = mm("ts,ks->tk", Cm, Bm, dtype)
    return mm("tkh,khd->thd", cb[:, :, None] * decay, x * dt[:, :, None],
              dtype)


def ssm_layer(sz: Sizes, p, x, dtype="f32"):
    """One Mamba-2 layer over x (S, D)."""
    S = x.shape[0]
    di, ns, nh, hd = sz.d_inner, sz.d_state, sz.ssm_heads, sz.headdim
    h = rms_norm(x, p["ln1"], sz.norm_eps)
    zxbcdt = mm("sd,dp->sp", h, p["in_proj"], dtype)
    z, xin, Bm, Cm, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + ns, 2 * di + 2 * ns], axis=-1)
    u = jnp.concatenate([xin, Bm, Cm], axis=-1)
    K = p["conv_w"].shape[0]
    up = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u], axis=0)
    conv = sum(up[i:i + S] * p["conv_w"][i].astype(F32) for i in range(K))
    conv = jax.nn.silu(conv)
    xs, Bm, Cm = jnp.split(conv, [di, di + ns], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xs = xs.reshape(S, nh, hd)
    y = ssd(xs, dt, A, Bm, Cm, dtype) + xs * p["D"][None, :, None]
    g = y.reshape(S, di) * jax.nn.silu(z)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + sz.norm_eps)
    g = g * (1.0 + p["norm"])
    return x + mm("si,id->sd", g, p["out_proj"], dtype)


def layer_fn(sz: Sizes):
    return dense_layer if sz.kind == "dense" else ssm_layer


def _layer(w, i):
    return {k: v[i] for k, v in w.items() if k in LAYER_LEAVES}


# ------------------------------------------------------------- serving
def forward_logits(sz: Sizes, w, tokens: np.ndarray, pad_to: int,
                   dtype: str = "f32"):
    """(len(tokens), V) logits, float32, computed a layer at a time over
    the sequence padded at its end to ``pad_to`` (causal, so padding never
    reaches a real position)."""
    n = len(tokens)
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = tokens
    step = _layer_step(sz, dtype)
    x = _embed(w["embed"], jnp.asarray(toks))
    for i in range(sz.n_layers):
        x = step(_layer(w, i), x)
    return _head(sz, dtype)(w["final_norm"], w["embed"], x)[:n]


@functools.lru_cache(maxsize=8)
def _layer_step(sz: Sizes, dtype: str):
    f = layer_fn(sz)
    return jax.jit(lambda p, x: f(sz, p, x, dtype))


@jax.jit
def _embed(embed, toks):
    return jnp.take(embed, toks, axis=0).astype(F32)


@functools.lru_cache(maxsize=8)
def _head(sz: Sizes, dtype: str):
    def head(final_norm, embed, x):
        return mm("sd,vd->sv", rms_norm(x, final_norm, sz.norm_eps), embed,
                  dtype)
    return jax.jit(head)


# ------------------------------------------------------------- training
def row_loss(sz: Sizes, w, tokens, dtype: str = "f32"):
    """Summed next-token negative log-likelihood of one row (S,)."""
    f = layer_fn(sz)
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    layers = {k: v for k, v in w.items() if k in LAYER_LEAVES}

    def body(x, p):
        return f(sz, p, x, dtype), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, layers)
    logits = mm("sd,vd->sv", rms_norm(x, w["final_norm"], sz.norm_eps),
                w["embed"], dtype)[:-1]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


@functools.lru_cache(maxsize=8)
def _row_grad(sz: Sizes, dtype: str):
    return jax.jit(jax.value_and_grad(
        lambda w, t: row_loss(sz, w, t, dtype)))


def loss_and_grad(sz: Sizes, w, batch: np.ndarray, dtype: str = "f32"):
    """Mean token loss of the batch and its float32 gradient, one row at a
    time (the rows' sums added, then divided by the token count)."""
    f = _row_grad(sz, dtype)
    n_tok = batch.shape[0] * (batch.shape[1] - 1)
    total, grad = 0.0, None
    for row in batch:
        l, g = f(w, jnp.asarray(row))
        total += float(l)
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    return total / n_tok, jax.tree.map(lambda g: g / n_tok, grad)


def lr_at(step: int, job: dict) -> float:
    """The job's cosine schedule: linear warmup over ``warmup_steps`` from
    peak/warmup, then cosine to floor_ratio * peak."""
    kw = job["schedule_kw"]
    peak, warm, total = kw["peak_lr"], kw["warmup_steps"], kw["total_steps"]
    floor = kw.get("floor_ratio", 0.1)
    if step < warm:
        return peak * (step + 1.0) / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (floor + (1 - floor) * 0.5 * (1.0 + np.cos(np.pi * t)))


def train_readings(sz: Sizes, seed: int, batches: List[np.ndarray],
                   job: dict, dtype: str = "f32", frozen: bool = False
                   ) -> Dict:
    """Follow the job's first ``len(batches)`` AdamW steps from the seed's
    weights.  Returns the loss of each step and the per-leaf norm of the
    first (clipped) gradient, of the weights' change after the last step
    and of the first raw gradient.
    ``frozen`` is the fault of a step that returns its state unchanged:
    the weights and the moments stay as they started."""
    opt = job["optimizer"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    wd, clip_to = opt["weight_decay"], opt["grad_clip"]
    w0 = init_weights(sz, seed)
    master = jax.tree.map(lambda x: x.astype(F32), w0)
    m = jax.tree.map(jnp.zeros_like, master)
    v = jax.tree.map(jnp.zeros_like, master)
    losses, first_grad, raw_grad = [], None, None

    @jax.jit
    def adam(master, m, v, g, lr, t, coef):
        def upd(p, m, v, g):
            g = g * coef
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
            p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
            return p, m, v
        out = jax.tree.map(upd, master, m, v, g)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    norms = jax.jit(lambda t: jax.tree.map(jnp.linalg.norm, t))
    for step, batch in enumerate(batches):
        # the program computes with bfloat16 weights: so does the reference
        # (the master copy stays float32)
        w = jax.tree.map(lambda mst, x0: mst.astype(x0.dtype), master, w0)
        loss, g = loss_and_grad(sz, w, batch, dtype)
        losses.append(loss)
        gnorm = float(jnp.sqrt(sum(jnp.sum(x * x)
                                   for x in jax.tree.leaves(g))))
        coef = min(1.0, clip_to / (gnorm + 1e-12)) if clip_to else 1.0
        if step == 0:
            raw_grad = {k: float(x) for k, x in norms(g).items()}
            first_grad = {k: x * coef for k, x in raw_grad.items()}
        if not frozen:
            master, m, v = adam(master, m, v, g, lr_at(step, job),
                                float(step + 1), coef)
        del g
    if frozen:  # the moments never took the gradient in
        first_grad = {k: 0.0 for k in first_grad}
    change = jax.tree.map(lambda a, b: a - b.astype(F32), master, w0)
    return {"losses": losses, "first_grad": first_grad,
            "raw_grad": raw_grad,
            "change": {k: float(x) for k, x in norms(change).items()}}
