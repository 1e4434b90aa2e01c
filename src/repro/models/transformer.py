"""Composable decoder (and encoder-decoder) stack covering all families.

A decoder layer is built from the config's block pattern:

  - "attn"        pre-norm self-attention (+ MLP/MoE sub-block)
  - "local_attn"  sliding-window attention (window = cfg.local_window)
  - "mla"         selected via cfg.attention_kind == "mla" for attn blocks
  - "mlstm"/"slstm"  xLSTM blocks (self-contained: no separate MLP if d_ff==0)
  - "rglru"       Griffin recurrent block (+ MLP sub-block)

MoE architectures replace the MLP with the routed-experts layer from layer
``first_dense_layers`` onward.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import ssm as SSM
from repro.parallel.axes import logical_constraint


def _layer_has_mlp(cfg: ModelConfig, kind: str) -> bool:
    if kind in ("mlstm", "slstm"):
        return False
    return cfg.d_ff > 0 or cfg.is_moe


def _layer_uses_moe(cfg: ModelConfig, layer_idx: int) -> bool:
    return cfg.is_moe and layer_idx >= cfg.first_dense_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_decoder_layer(key, cfg: ModelConfig, layer_idx: int, *, cross: bool = False):
    kind = cfg.block_kind(layer_idx)
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": L.init_norm(ks[0], cfg)}
    if kind in ("attn", "local_attn"):
        if cfg.attention_kind == "mla":
            p["mix"] = MLA.init_mla(ks[1], cfg)
        else:
            p["mix"] = A.init_attention(ks[1], cfg)
    elif kind == "mlstm":
        p["mix"] = SSM.init_mlstm(ks[1], cfg)
    elif kind == "slstm":
        p["mix"] = SSM.init_slstm(ks[1], cfg)
    elif kind == "rglru":
        p["mix"] = RG.init_rglru(ks[1], cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if cross:
        p["norm_cross"] = L.init_norm(ks[2], cfg)
        p["cross"] = A.init_attention(ks[3], cfg, cross=True)
    if _layer_has_mlp(cfg, kind):
        p["norm2"] = L.init_norm(ks[4], cfg)
        if _layer_uses_moe(cfg, layer_idx):
            p["mlp"] = MOE.init_moe(ks[5], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[5], cfg)
    return p


def init_encoder_layer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "norm1": L.init_norm(ks[0], cfg),
        "mix": A.init_attention(ks[1], cfg),
        "norm2": L.init_norm(ks[2], cfg),
        "mlp": L.init_mlp(ks[3], cfg),
    }


def layer_segments(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(prefix, cycle_len, n_cycles, suffix) for the scan-layers layout.

    Layers [0, prefix) and [prefix + n*C, L) stay unrolled (structure
    differs / remainder); the middle n cycles of C layers are stacked and
    executed with ``lax.scan`` — one compiled cycle body regardless of depth.
    """
    C = len(cfg.block_pattern)
    prefix = cfg.first_dense_layers if cfg.is_moe else 0
    rest = cfg.num_layers - prefix
    n_cycles = rest // C
    suffix = rest - n_cycles * C
    return prefix, C, n_cycles, suffix


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def is_scanned(layers) -> bool:
    return isinstance(layers, dict) and "scan" in layers


def init_params(key, cfg: ModelConfig, *, scan_layers: bool = False):
    ks = jax.random.split(key, cfg.num_layers + cfg.encoder_layers + 3)
    cross = cfg.is_encoder_decoder

    def mk(i):
        return init_decoder_layer(ks[2 + i], cfg, i, cross=cross)

    if scan_layers:
        prefix, C, n, suffix = layer_segments(cfg)
        cycles = [
            [mk(prefix + j * C + c) for c in range(C)] for j in range(n)
        ]
        layers = {
            "prefix": [mk(i) for i in range(prefix)],
            "scan": _stack_trees(cycles) if n > 0 else None,
            "suffix": [mk(cfg.num_layers - suffix + i)
                       for i in range(suffix)],
        }
    else:
        layers = [mk(i) for i in range(cfg.num_layers)]

    params: Dict[str, Any] = {
        "embed": L.init_embeddings(ks[0], cfg),
        "final_norm": L.init_norm(ks[1], cfg),
        "layers": layers,
    }
    if cfg.is_encoder_decoder:
        off = 2 + cfg.num_layers
        enc_layers = [init_encoder_layer(ks[off + i], cfg)
                      for i in range(cfg.encoder_layers)]
        if scan_layers:
            enc_layers = {"prefix": [], "suffix": [],
                          "scan": _stack_trees([[l] for l in enc_layers])}
        params["encoder"] = {
            "layers": enc_layers,
            "final_norm": L.init_norm(ks[off + cfg.encoder_layers], cfg),
            "positions": L.dense_init(
                ks[off + cfg.encoder_layers],
                (cfg.encoder_seq_len, cfg.d_model),
                dtype=jnp.dtype(cfg.param_dtype)),
        }
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_mix(
    lp, x, cfg: ModelConfig, kind: str, *, positions, state=None,
    use_pallas=False, return_kv=False,
):
    window = cfg.local_window if kind == "local_attn" else cfg.sliding_window
    if kind in ("attn", "local_attn"):
        if cfg.attention_kind == "mla":
            return MLA.apply_mla(
                lp, x, cfg, positions=positions, cache=state,
                use_pallas=use_pallas, return_kv=return_kv)
        return A.apply_self_attention(
            lp, x, cfg, positions=positions, window=window, cache=state,
            use_pallas=use_pallas, return_kv=return_kv)
    if kind == "mlstm":
        return SSM.apply_mlstm(lp, x, cfg, state=state, return_state=return_kv)
    if kind == "slstm":
        return SSM.apply_slstm(lp, x, cfg, state=state, return_state=return_kv)
    if kind == "rglru":
        return RG.apply_rglru(lp, x, cfg, state=state, return_state=return_kv)
    raise ValueError(kind)


def _decoder_layer_fwd(
    lp, x, cfg: ModelConfig, layer_idx: int, *, positions,
    encoder_kv=None, enc_out=None, state=None, use_pallas=False,
    return_kv=False,
):
    """One decoder layer. Returns (x, extra, aux).

    Cross-attention K/V comes either precomputed (``encoder_kv``, decode) or
    is projected here from ``enc_out`` (training/prefill — scan-compatible).
    """
    kind = cfg.block_kind(layer_idx)
    h = L.apply_norm(lp["norm1"], x, cfg)
    mix_out, extra = _apply_mix(
        lp["mix"], h, cfg, kind, positions=positions, state=state,
        use_pallas=use_pallas, return_kv=return_kv)
    x = x + mix_out
    if encoder_kv is None and enc_out is not None:
        encoder_kv = A.encoder_kv(lp["cross"], enc_out, cfg)
    if encoder_kv is not None:
        h = L.apply_norm(lp["norm_cross"], x, cfg)
        x = x + A.apply_cross_attention(lp["cross"], h, encoder_kv, cfg)
    aux = None
    if _layer_has_mlp(cfg, kind):
        h = L.apply_norm(lp["norm2"], x, cfg)
        if _layer_uses_moe(cfg, layer_idx):
            mlp_out, aux = MOE.apply_moe(lp["mlp"], h, cfg)
        else:
            mlp_out = L.apply_mlp(lp["mlp"], h, cfg)
        x = x + mlp_out
    x = logical_constraint(x, "batch", None, None)
    return x, extra, aux


def encode(params, cfg: ModelConfig, frames):
    """Whisper encoder on (stubbed) frame embeddings (B, S_enc, D)."""
    enc = params["encoder"]
    x = frames.astype(L.compute_dtype(cfg))
    x = x + L.cast(enc["positions"], cfg)[None, : x.shape[1]]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def enc_layer(lp, x):
        h = L.apply_norm(lp["norm1"], x, cfg)
        mix, _ = A.apply_self_attention(
            lp["mix"], h, cfg, positions=positions, window=0, cache=None,
            causal=False)  # bidirectional encoder
        x = x + mix
        h = L.apply_norm(lp["norm2"], x, cfg)
        return x + L.apply_mlp(lp["mlp"], h, cfg)

    layers = enc["layers"]
    if is_scanned(layers):
        def body(x, lp):
            return enc_layer(lp[0], x), None
        x, _ = jax.lax.scan(body, x, layers["scan"])
    else:
        for lp in layers:
            x = enc_layer(lp, x)
    return L.apply_norm(enc["final_norm"], x, cfg)


def forward(
    params,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    *,
    use_pallas: bool = False,
    remat: str = "none",
    collect_kv: bool = False,
):
    """Training/prefill forward. batch: {"tokens": (B,S)[, "frames": ...]}.

    Returns (logits, aux) where aux = {"moe_aux", "moe_z", "kv" (if collected)}.
    """
    x, aux_out = _blocks(params, cfg, batch, use_pallas=use_pallas,
                         remat=remat, collect_kv=collect_kv)
    return L.lm_logits(params["embed"], x, cfg), aux_out


@jax.named_scope("pier.blocks")
def _blocks(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            use_pallas: bool, remat: str, collect_kv: bool):
    """The embedding, every decoder layer and the final norm: (x, aux)."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x = L.embed_tokens(params["embed"], tokens, cfg)

    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["frames"])

    # Zero-valued but VMA-varying like x: under shard_map the layer-scan
    # carry must have consistent varying-axes annotations between the init
    # and the body output (the aux losses depend on x inside the body).
    _vma_zero = jnp.sum(x[:0].astype(jnp.float32))
    moe_aux = _vma_zero
    moe_z = _vma_zero

    def run_layer(lp, x, idx):
        return _decoder_layer_fwd(
            lp, x, cfg, idx, positions=positions, enc_out=enc_out,
            state=None, use_pallas=use_pallas, return_kv=collect_kv)

    def _ckpt(fn, static_argnums=()):
        if remat == "full":
            return jax.checkpoint(fn, static_argnums=static_argnums)
        if remat == "selective":
            # save matmul outputs, recompute elementwise/norm chains —
            # the standard "dots saveable" policy: ~no extra matmul FLOPs,
            # most of full remat's activation-memory savings
            return jax.checkpoint(
                fn, static_argnums=static_argnums,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return fn

    run_unrolled = _ckpt(run_layer, static_argnums=(2,))

    layers = params["layers"]
    if is_scanned(layers):
        prefix, C, n, suffix = layer_segments(cfg)
        kv = {"prefix": [], "scan": None, "suffix": []}
        for i, lp in enumerate(layers["prefix"]):
            x, extra, aux = run_unrolled(lp, x, i)
            if aux is not None:
                moe_aux, moe_z = moe_aux + aux["aux_loss"], moe_z + aux["z_loss"]
            kv["prefix"].append(extra)

        if layers["scan"] is not None and n > 0:
            def cycle_body(carry, cycle_lp):
                x, a_aux, a_z = carry
                extras = []
                for c in range(C):
                    x, extra, aux = run_layer(cycle_lp[c], x, prefix + c)
                    if aux is not None:
                        a_aux = a_aux + aux["aux_loss"]
                        a_z = a_z + aux["z_loss"]
                    extras.append(extra)
                ys = extras if collect_kv else None
                return (x, a_aux, a_z), ys

            body = _ckpt(cycle_body)
            (x, moe_aux, moe_z), ys = jax.lax.scan(
                body, (x, moe_aux, moe_z), layers["scan"])
            if collect_kv:
                kv["scan"] = ys  # list per c of stacked (n, ...) pytrees

        for j, lp in enumerate(layers["suffix"]):
            idx = cfg.num_layers - suffix + j
            x, extra, aux = run_unrolled(lp, x, idx)
            if aux is not None:
                moe_aux, moe_z = moe_aux + aux["aux_loss"], moe_z + aux["z_loss"]
            kv["suffix"].append(extra)
        kv_streams = kv
    else:
        kv_streams = []
        for i, lp in enumerate(layers):
            x, extra, aux = run_unrolled(lp, x, i)
            if aux is not None:
                moe_aux = moe_aux + aux["aux_loss"]
                moe_z = moe_z + aux["z_loss"]
            if collect_kv:
                kv_streams.append(extra)

    x = L.apply_norm(params["final_norm"], x, cfg)
    aux_out = {"moe_aux": moe_aux, "moe_z": moe_z}
    if collect_kv:
        aux_out["kv"] = kv_streams
    return x, aux_out
