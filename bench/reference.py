"""Plain reference of what a training cell computes, and its control.

A decoder-only GPT-2 language model and the Pier training loop around it,
written out in ``jax.numpy`` and float32 at ``precision="highest"``, from a
configuration file and the cell's traffic alone. It imports nothing of the
program under test and takes nothing the program made: the weights are drawn
again from the seed, by the same rule the program states for them (below),
and the batches come again from ``bench/traffic.py``.

The model, per layer: pre-norm LayerNorm, causal multi-head attention with
no biases, a residual add, LayerNorm, a GELU MLP (tanh form, as GPT-2) with
no biases, a residual add; a final LayerNorm and logits through the tied
token embedding; learned absolute positions. (Published GPT-2 has biases in
its projections; the program's model has none, and neither has this.)

Training: next-token cross-entropy averaged over every token of a group's
rows; gradients clipped to a global norm; AdamW with decoupled weight decay
on the matrices and the token embedding (not on norms or positions); the
inner learning rate warms up linearly, then follows a cosine. Every
``sync_interval`` steps the groups' mean change since the anchor, Δ, makes
the outer Nesterov step (PyTorch form): ``M ← μM + Δ``,
``θ ← anchor + lr·(μM + Δ)``, and every group restarts from that θ.

Weights, as the program draws them (``jax.random`` from ``PRNGKey(seed)``):
the key splits into ``num_layers + 3``; the first gives the embeddings
(token table from its first sub-key, positions from its second, std 0.02),
the ``2 + i``-th gives layer ``i``. Each layer key splits into 6: the
second gives the attention projections (q, k, v, o from its first four
sub-keys), the sixth the MLP (up from its second sub-key, down from its
third). Matrices are truncated normals on [-3, 3] with std 0.02, and
0.02/√(2L) for the two projections back into the residual stream; norm
scales are 1 and biases 0.

``precision="fp8"`` is the control: every matrix multiplication, forward
and backward, takes its inputs rounded to 8-bit floats with one scale per
tensor (e4m3 forward, e5m2 for gradients), the recipe of fp8 training.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# matrix multiplication in the reference's precision
# ---------------------------------------------------------------------------


def _round8(x, dtype):
    """x rounded to ``dtype`` (an fp8 type) with one scale for the tensor."""
    amax = jnp.max(jnp.abs(x))
    top = float(jnp.finfo(dtype).max)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(spec, a, b):
    return jnp.einsum(spec, _round8(a, jnp.float8_e4m3fn),
                      _round8(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _einsum8_fwd(spec, a, b):
    return _einsum8(spec, a, b), (a, b)


def _einsum8_bwd(spec, res, g):
    a, b = res
    a8 = _round8(a, jnp.float8_e4m3fn)
    b8 = _round8(b, jnp.float8_e4m3fn)
    g8 = _round8(g, jnp.float8_e5m2)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     a8, b8)
    return vjp(g8)


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def einsum(spec, a, b, precision):
    if precision == "fp8":
        return _einsum8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return std * jax.random.truncated_normal(key, -3.0, 3.0, shape,
                                             jnp.float32)


def init_params(seed: int, cfg: dict):
    """The model's float32 weights drawn from ``seed`` (module docstring)."""
    L, d, h = cfg["num_layers"], cfg["d_model"], cfg["num_heads"]
    hkv, f = cfg["num_kv_heads"], cfg["d_ff"]
    hd = cfg.get("head_dim") or d // h
    out_std = 0.02 / math.sqrt(2 * L)

    def draw(key):
        ks = jax.random.split(key, L + 3)
        ek = jax.random.split(ks[0], 3)
        norm = lambda: {"scale": jnp.ones((d,), jnp.float32),  # noqa: E731
                        "bias": jnp.zeros((d,), jnp.float32)}
        layers = []
        for i in range(L):
            lk = jax.random.split(ks[2 + i], 6)
            ak = jax.random.split(lk[1], 6)
            mk = jax.random.split(lk[5], 3)
            layers.append({
                "norm1": norm(),
                "mix": {"wq": _normal(ak[0], (d, h, hd), 0.02),
                        "wk": _normal(ak[1], (d, hkv, hd), 0.02),
                        "wv": _normal(ak[2], (d, hkv, hd), 0.02),
                        "wo": _normal(ak[3], (h, hd, d), out_std)},
                "norm2": norm(),
                "mlp": {"w_up": _normal(mk[1], (d, f), 0.02),
                        "w_down": _normal(mk[2], (f, d), out_std)},
            })
        return {
            "embed": {"tokens": _normal(ek[0], (cfg["vocab_size"], d), 0.02),
                      "positions": _normal(
                          ek[1], (cfg["max_position_embeddings"], d), 0.02)},
            "final_norm": norm(),
            "layers": layers,
        }

    return jax.jit(draw)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------


def _layernorm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (x + 0.044715 * x ** 3)))


def row_nll(params, tokens, labels, cfg: dict, precision: str):
    """Summed next-token negative log-likelihood of one row (S,)."""
    eps = cfg["norm_eps"]
    S = tokens.shape[0]
    mm = partial(einsum, precision=precision)
    x = params["embed"]["tokens"][tokens] + params["embed"]["positions"][:S]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for lp in params["layers"]:
        a = lp["mix"]
        h = _layernorm(lp["norm1"], x, eps)
        q = mm("sd,dhk->hsk", h, a["wq"])
        k = mm("sd,dhk->hsk", h, a["wk"])
        v = mm("sd,dhk->hsk", h, a["wv"])
        if k.shape[0] != q.shape[0]:  # grouped keys and values
            rep = q.shape[0] // k.shape[0]
            k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
        s = mm("hqk,hsk->hqs", q, k) / math.sqrt(q.shape[-1])
        s = jnp.where(causal[None], s, -jnp.inf)
        o = mm("hqs,hsk->hqk", jax.nn.softmax(s, axis=-1), v)
        x = x + mm("hsk,hkd->sd", o, a["wo"])
        h = _layernorm(lp["norm2"], x, eps)
        x = x + mm("sf,fd->sd", _gelu(mm("sd,df->sf", h, lp["mlp"]["w_up"])),
                   lp["mlp"]["w_down"])
    x = _layernorm(params["final_norm"], x, eps)
    logits = mm("sd,vd->sv", x, params["embed"]["tokens"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


# ---------------------------------------------------------------------------
# one group's training step, and the outer step
# ---------------------------------------------------------------------------


def inner_lr(tc: dict, step: int) -> float:
    """Linear warm-up over ``lr_warmup_frac`` of the run, then a cosine."""
    total = float(tc["total_steps"])
    warm = max(tc["lr_warmup_frac"] * total, 1.0)
    peak, floor = tc["inner_lr"], tc["inner_min_lr"]
    if step < warm:
        return peak * (step + 1.0) / warm
    prog = min(max((step - warm) / max(total - warm, 1.0), 0.0), 1.0)
    return floor + 0.5 * (peak - floor) * (1.0 + math.cos(math.pi * prog))


def outer_mu(tc: dict, step: int) -> float:
    """Outer momentum at the sync after ``step``: the decay table or μ."""
    frac = step / max(tc["total_steps"], 1)
    for lo, hi, mu in tc["momentum_decay"]:
        if lo <= frac < hi:
            return mu
    return tc["outer_momentum"]


def outer_lr(tc: dict, step: int) -> float:
    """Outer learning rate at the sync after ``step``."""
    frac = step / max(tc["total_steps"], 1)
    p = tc["warmup_frac"]
    if frac < p:
        return 0.0
    if frac < tc["outer_lr_warmup_end"]:
        return (frac - p) / max(tc["outer_lr_warmup_end"] - p, 1e-9)
    if frac < tc["outer_lr_mid_end"]:
        return tc["outer_lr_mid"]
    return tc["outer_lr_final"]


def _decays(path) -> bool:
    name = str(getattr(path[-1], "key", path[-1]))
    return name not in ("scale", "bias", "positions")


def leaf_names(tree) -> list:
    """'/'-joined key paths of ``tree``'s leaves, in flattening order."""
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _grad_sum(params, tokens, labels, cfg_items, precision):
    """(summed NLL, summed gradient) over the rows of (R, S), row by row."""
    cfg = dict(cfg_items)
    one = jax.value_and_grad(row_nll)

    def body(acc, row):
        nll, g = one(params, row[0], row[1], cfg, precision)
        return (acc[0] + nll, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (nll, g), _ = jax.lax.scan(body, zero, (tokens, labels))
    return nll, g


@partial(jax.jit, static_argnames=("hp",))
def _adamw(params, m, v, count, grads, lr, hp):
    b1, b2, eps, wd, clip = hp
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count

    def upd(path, p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        step = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        if _decays(path):
            step = step + wd * p
        return p - lr * step, m_, v_

    out = jax.tree_util.tree_map_with_path(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), leaf_norms(grads)


def follow(seed: int, cfg: dict, tc: dict, traffic, steps: int, *,
           precision: str = "fp32") -> dict:
    """Train ``steps`` steps from the seed, as the cell's program does.

    Returns per-step losses (mean over groups), each group's per-leaf norms
    of the first clipped gradient, and of the change of its parameters over
    the ``steps`` steps. Every group runs in turn on the default device.
    """
    _supported(cfg, tc)
    G = traffic.groups
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    hp = (tc["adam_beta1"], tc["adam_beta2"], tc["adam_eps"],
          tc["weight_decay"], tc["clip_grad"])
    init = init_params(seed, cfg)
    zeros = jax.tree.map(jnp.zeros_like, init)
    params, ms, vs = [init] * G, [zeros] * G, [zeros] * G
    anchor, momentum = init, zeros
    n_tok = traffic.rows_per_group * traffic.seq_len
    losses, first_grad = [], None
    for step in range(steps):
        b = traffic.batch(step)
        lr = jnp.float32(inner_lr(tc, step))
        count = jnp.float32(step + 1)
        out = []
        for g in range(G):
            rows = slice(g * traffic.rows_per_group,
                         (g + 1) * traffic.rows_per_group)
            tok, lab = b["tokens"][rows], b["labels"][rows]
            nll, grads = _grad_sum(params[g], tok, lab, cfg_items, precision)
            grads = jax.tree.map(lambda x: x / n_tok, grads)
            params[g], ms[g], vs[g], gn = _adamw(params[g], ms[g], vs[g],
                                                 count, grads, lr, hp)
            out.append((nll / n_tok, gn))
        losses.append(float(np.mean([float(o[0]) for o in out])))
        if first_grad is None:
            first_grad = np.stack([np.asarray(o[1]) for o in out])
        if (step + 1) % tc["sync_interval"] == 0:
            anchor, momentum = _outer_step(
                params, anchor, momentum, jnp.float32(outer_mu(tc, step)),
                jnp.float32(outer_lr(tc, step)))
            params = [anchor] * G
    change = np.stack([np.asarray(leaf_norms(_sub(params[g], init)))
                       for g in range(G)])
    return {"loss": losses, "grad_norms": first_grad,
            "change_norms": change, "leaves": leaf_names(init)}


IMPLEMENTS = {
    "activation": "gelu", "norm": "layernorm", "positional": "learned",
    "tie_embeddings": True, "lr_schedule": "cosine", "sync_delay": 0,
    "outer_optimizer": "nesterov_torch", "opt_state_dtype": "float32",
    "optimizer": "pier",
}


def _supported(cfg: dict, tc: dict) -> None:
    """Raise where the cell asks for more than this reference writes out."""
    for key, want in IMPLEMENTS.items():
        got = cfg.get(key, tc.get(key))
        if got != want:
            raise ValueError(f"the reference implements {key}={want!r}, "
                             f"the cell states {got!r}")
    if tc.get("outer_comm", {}).get("compression", "none") != "none":
        raise ValueError("the reference implements the fp32 exchange only")


@jax.jit
def _sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


@jax.jit
def _outer_step(local, anchor, momentum, mu, lr):
    delta = jax.tree.map(lambda *ps: sum(ps) / len(ps), *local)
    delta = jax.tree.map(jnp.subtract, delta, anchor)
    m = jax.tree.map(lambda m_, d: mu * m_ + d, momentum, delta)
    new = jax.tree.map(lambda a, m_, d: a + lr * (mu * m_ + d),
                       anchor, m, delta)
    return new, m
