"""Model registry: the public entry points every subsystem uses.

``init_params`` / ``forward`` / ``loss_fn`` for training;
``init_decode_state`` / ``prefill`` / ``decode_step`` for serving;
``count_params`` for 6ND roofline accounting.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import rglru as RG
from repro.models import ssm as SSM
from repro.models import transformer as T

init_params = T.init_params
forward = T.forward


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def loss_fn(
    params,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    *,
    use_pallas: bool = False,
    remat: str = "none",
):
    """Next-token cross-entropy (+ MoE aux losses). batch["labels"]: (B, S).

    Positions with label < 0 are masked out.
    """
    logits, aux = forward(
        params, cfg, batch, use_pallas=use_pallas, remat=remat)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # A masked sum, not take_along_axis: XLA's SPMD partitioner CHECK-fails
    # (spmd_partitioner_util.cc, HandleGather) on the batched gather when
    # the batch dim and the vocab dim are both auto-sharded inside the
    # partial-manual shard_map. Exactly one term is non-zero, so the sum
    # is the gathered logit bit for bit.
    vocab_ids = jnp.arange(logits.shape[-1], dtype=labels_safe.dtype)
    gold = jnp.sum(jnp.where(labels_safe[..., None] == vocab_ids, logits, 0.0),
                   axis=-1)
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll) / denom
    total = loss
    if cfg.is_moe:
        total = total + cfg.router_aux_loss_coef * aux["moe_aux"]
        total = total + 1e-4 * aux["moe_z"]
    metrics = {
        "lm_loss": loss,
        "moe_aux": aux["moe_aux"],
        "moe_z": aux["moe_z"],
        "tokens": jnp.sum(mask),
    }
    return total, metrics


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _layer_state(cfg: ModelConfig, layer_idx: int, batch: int, max_len: int):
    kind = cfg.block_kind(layer_idx)
    if kind in ("attn", "local_attn"):
        if cfg.attention_kind == "mla":
            return MLA.init_mla_cache(cfg, batch, max_len)
        window = cfg.local_window if kind == "local_attn" else cfg.sliding_window
        return A.init_cache(cfg, batch, max_len, window=window)
    if kind == "mlstm":
        return SSM.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return SSM.init_slstm_state(cfg, batch)
    if kind == "rglru":
        return RG.init_rglru_state(cfg, batch)
    raise ValueError(kind)


def _cross_kv_zeros(cfg: ModelConfig, batch: int):
    hd = cfg.resolved_head_dim
    dt = L.compute_dtype(cfg)
    z = lambda: jnp.zeros((batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dt)
    return (z(), z())


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, *,
                      scan_layers: bool = False):
    """Decode state pytree: per-layer caches/states + global position.

    With ``scan_layers`` the per-layer states mirror the stacked param
    layout: {"prefix": [...], "scan": [stacked (n, ...) per cycle pos],
    "suffix": [...]}.
    """
    def mk(i):
        return _layer_state(cfg, i, batch, max_len)

    if scan_layers:
        prefix, C, n, suffix = T.layer_segments(cfg)
        layers = {
            "prefix": [mk(i) for i in range(prefix)],
            "scan": (T._stack_trees(
                [[mk(prefix + j * C + c) for c in range(C)]
                 for j in range(n)]) if n > 0 else None),
            "suffix": [mk(cfg.num_layers - suffix + i) for i in range(suffix)],
        }
    else:
        layers = [mk(i) for i in range(cfg.num_layers)]

    state: Dict[str, Any] = {
        "layers": layers,
        "position": jnp.zeros((), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        if scan_layers:
            prefix, C, n, suffix = T.layer_segments(cfg)
            state["cross_kv"] = {
                "prefix": [_cross_kv_zeros(cfg, batch) for _ in range(prefix)],
                "scan": (T._stack_trees(
                    [[_cross_kv_zeros(cfg, batch) for _ in range(C)]
                     for _ in range(n)]) if n > 0 else None),
                "suffix": [_cross_kv_zeros(cfg, batch) for _ in range(suffix)],
            }
        else:
            state["cross_kv"] = [
                _cross_kv_zeros(cfg, batch) for _ in range(cfg.num_layers)
            ]
    return state


def decode_step(params, cfg: ModelConfig, state, tokens):
    """One serving step: tokens (B, 1) -> (logits (B, 1, V), new_state)."""
    pos = state["position"]
    positions = pos[None].astype(jnp.int32)  # (1,)
    x = L.embed_tokens(params["embed"], tokens, cfg, position_offset=pos)
    layers = params["layers"]
    is_encdec = cfg.is_encoder_decoder

    if T.is_scanned(layers):
        prefix, C, n, suffix = T.layer_segments(cfg)
        new_layers = {"prefix": [], "scan": None, "suffix": []}
        for i, lp in enumerate(layers["prefix"]):
            enc_kv = state["cross_kv"]["prefix"][i] if is_encdec else None
            x, extra, _ = T._decoder_layer_fwd(
                lp, x, cfg, i, positions=positions, encoder_kv=enc_kv,
                state=state["layers"]["prefix"][i])
            new_layers["prefix"].append(extra)

        if layers["scan"] is not None and n > 0:
            xs = (layers["scan"], state["layers"]["scan"])
            if is_encdec:
                xs = xs + (state["cross_kv"]["scan"],)

            def body(x, inputs):
                cycle_lp, cycle_st = inputs[0], inputs[1]
                enc_kvs = inputs[2] if is_encdec else None
                new_sts = []
                for c in range(C):
                    x, extra, _ = T._decoder_layer_fwd(
                        cycle_lp[c], x, cfg, prefix + c,
                        positions=positions,
                        encoder_kv=enc_kvs[c] if enc_kvs else None,
                        state=cycle_st[c])
                    new_sts.append(extra)
                return x, new_sts

            x, new_scan_states = jax.lax.scan(body, x, xs)
            new_layers["scan"] = new_scan_states

        for j, lp in enumerate(layers["suffix"]):
            idx = cfg.num_layers - suffix + j
            enc_kv = state["cross_kv"]["suffix"][j] if is_encdec else None
            x, extra, _ = T._decoder_layer_fwd(
                lp, x, cfg, idx, positions=positions, encoder_kv=enc_kv,
                state=state["layers"]["suffix"][j])
            new_layers["suffix"].append(extra)
    else:
        new_layers = []
        for i, lp in enumerate(layers):
            enc_kv = state["cross_kv"][i] if is_encdec else None
            x, extra, _ = T._decoder_layer_fwd(
                lp, x, cfg, i, positions=positions, encoder_kv=enc_kv,
                state=state["layers"][i])
            new_layers.append(extra)

    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.lm_logits(params["embed"], x, cfg)
    new_state = dict(state)
    new_state["layers"] = new_layers
    new_state["position"] = pos + 1
    return logits, new_state


def prefill(params, cfg: ModelConfig, batch, *, max_len: int,
            use_pallas: bool = False):
    """Process a full prompt, returning (logits, decode_state).

    Attention layers collect their (k, v)/latent streams during the forward
    and assemble caches; recurrent layers re-run their scan to produce the
    final state (cheap relative to the forward).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    logits, aux = forward(
        params, cfg, batch, use_pallas=use_pallas, collect_kv=True)
    positions = jnp.arange(S, dtype=jnp.int32)

    def build_state(layer_idx, stream):
        """Turn a collected (k, v)/latent/recurrent stream into decode state."""
        kind = cfg.block_kind(layer_idx)
        if kind in ("attn", "local_attn"):
            if cfg.attention_kind == "mla":
                ckv, krope = stream
                return MLA.mla_cache_from_kv(
                    cfg, ckv, krope, positions, max_len=max_len)
            k, v = stream
            window = (cfg.local_window if kind == "local_attn"
                      else cfg.sliding_window)
            return A.cache_from_kv(
                cfg, k, v, positions, max_len=max_len, window=window)
        # recurrent blocks already returned their final state
        return stream

    layers = params["layers"]
    streams = aux["kv"]
    if T.is_scanned(layers):
        prefix, C, n, suffix = T.layer_segments(cfg)
        new_layers = {
            "prefix": [build_state(i, s)
                       for i, s in enumerate(streams["prefix"])],
            "scan": None,
            "suffix": [build_state(cfg.num_layers - suffix + j, s)
                       for j, s in enumerate(streams["suffix"])],
        }
        if streams["scan"] is not None:
            # streams["scan"] is a list (per cycle position c) of stacked
            # (n, ...) streams; vmap the cache builder over the cycle axis.
            new_layers["scan"] = [
                jax.vmap(lambda s, c=c: build_state(prefix + c, s))(sc)
                for c, sc in enumerate(streams["scan"])
            ]
    else:
        new_layers = [build_state(i, s) for i, s in enumerate(streams)]

    state = {"layers": new_layers,
             "position": jnp.asarray(S, jnp.int32)}
    if cfg.is_encoder_decoder:
        enc_out = T.encode(params, cfg, batch["frames"])
        if T.is_scanned(layers):
            prefix, C, n, suffix = T.layer_segments(cfg)
            cross = {
                "prefix": [A.encoder_kv(lp["cross"], enc_out, cfg)
                           for lp in layers["prefix"]],
                "scan": None,
                "suffix": [A.encoder_kv(lp["cross"], enc_out, cfg)
                           for lp in layers["suffix"]],
            }
            if layers["scan"] is not None:
                cross["scan"] = [
                    jax.vmap(
                        lambda lpc: A.encoder_kv(lpc["cross"], enc_out, cfg)
                    )(layers["scan"][c])
                    for c in range(C)
                ]
            state["cross_kv"] = cross
        else:
            state["cross_kv"] = [
                A.encoder_kv(lp["cross"], enc_out, cfg) for lp in layers
            ]
    return logits, state


# ---------------------------------------------------------------------------
# parameter counting (analytic via eval_shape — exact by construction)
# ---------------------------------------------------------------------------


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = 0
    routed = 0

    def visit(path, leaf):
        nonlocal total, routed
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if cfg.is_moe and any(k in ("w_gate", "w_up", "w_down") for k in keys):
            if leaf.ndim == 3 and leaf.shape[0] == cfg.num_experts:
                routed += n

    jax.tree_util.tree_map_with_path(visit, shapes)
    if active_only and cfg.is_moe and cfg.num_experts > 0:
        frac = cfg.num_experts_per_tok / cfg.num_experts
        return int(total - routed * (1.0 - frac))
    return int(total)
