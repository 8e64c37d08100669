"""Decoder-LM assembly for all assigned architectures.

Layers are grouped into *stages* so repeated block patterns lower as a
``lax.scan`` over stacked parameters (small HLO even for 88-layer models):

    lead  — unscanned leading layers (e.g. deepseek's first dense-FFN layer)
    scan  — (pattern of len p) x (repeats k), params stacked on a 'stack' dim
    tail  — unscanned remainder (e.g. gemma3-27b: 62 = 6*10 + 2)

The LM head is *always* a separate parameter ("lm_head") — the PHSFL frozen
random classifier requires an untied head even for configs whose source
model ties embeddings (noted in DESIGN.md).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, LOCAL_ATTN, MLA_ATTN, MLSTM, RGLRU,
                                SLSTM, ModelConfig)
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.init_utils import (dense, dense_axes, embedding,
                                     embedding_axes, norm, norm_axes,
                                     stack_axes)
from repro.models.layers import apply_norm, mlp_apply, mlp_axes, mlp_init, softcap

LOSS_CHUNK = 512  # seq chunk for the memory-bounded LM loss


# ------------------------------------------------------------- stages ------
@dataclasses.dataclass(frozen=True)
class Stage:
    which: str                 # "lead" | "scan" | "tail"
    layer_ids: tuple[int, ...] # absolute layer indices (first repeat for scan)
    repeats: int = 1


def compute_stages(cfg: ModelConfig) -> list[Stage]:
    kinds = cfg.layer_kinds()
    L = cfg.num_layers
    p = len(cfg.block_pattern)
    # lead layers are unscanned: (a) structurally distinct layers (deepseek's
    # first dense-FFN layer) and (b) the PHSFL *client-side* layers, so the
    # client/body split is a plain pytree partition even under layer scan.
    lead = max(cfg.moe.first_dense_layers if cfg.moe else 0,
               cfg.n_client_layers)
    lead = min(lead, L)
    k = (L - lead) // p
    rem = (L - lead) - k * p
    stages = []
    if lead:
        stages.append(Stage("lead", tuple(range(lead))))
    if k:
        first = tuple(range(lead, lead + p))
        # sanity: the pattern must actually repeat
        for r in range(k):
            for j in range(p):
                assert kinds[lead + r * p + j] == kinds[lead + j], (r, j)
        stages.append(Stage("scan", first, repeats=k))
    if rem:
        stages.append(Stage("tail", tuple(range(lead + k * p, L))))
    return stages


def _layer_is_moe(cfg: ModelConfig, layer_id: int) -> bool:
    return (cfg.moe is not None
            and layer_id >= (cfg.moe.first_dense_layers or 0))


def _layer_kind(cfg: ModelConfig, layer_id: int) -> str:
    return cfg.layer_kinds()[layer_id]


def _rope_theta_for(cfg: ModelConfig, kind: str) -> float:
    return cfg.local_rope_theta if kind == LOCAL_ATTN else cfg.rope_theta


# -------------------------------------------------------- layer params -----
def init_layer(key, cfg: ModelConfig, layer_id: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    kind = _layer_kind(cfg, layer_id)
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in (SLSTM, MLSTM):
        block_init = (xlstm_mod.slstm_init if kind == SLSTM
                      else xlstm_mod.mlstm_init)
        return {"ln1": norm(cfg.d_model, cfg.norm, dtype),
                "block": block_init(k1, cfg, dtype)}
    p = {"ln1": norm(cfg.d_model, cfg.norm, dtype),
         "ln2": norm(cfg.d_model, cfg.norm, dtype)}
    if kind == MLA_ATTN:
        p["mla"] = mla_mod.mla_init(k1, cfg, dtype)
    elif kind == RGLRU:
        p["rec"] = rglru_mod.rglru_init(k1, cfg, dtype)
    else:
        p["attn"] = attn_mod.attn_init(k1, cfg, dtype)
    if _layer_is_moe(cfg, layer_id):
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    else:
        d_ff = cfg.d_ff
        if cfg.moe is not None and not _layer_is_moe(cfg, layer_id):
            d_ff = cfg.moe.d_ff_dense
        p["mlp"] = mlp_init(k3, cfg, d_ff=d_ff, dtype=dtype)
    return p


def layer_axes(cfg: ModelConfig, layer_id: int):
    kind = _layer_kind(cfg, layer_id)
    if kind in (SLSTM, MLSTM):
        block_axes = (xlstm_mod.slstm_axes if kind == SLSTM
                      else xlstm_mod.mlstm_axes)
        return {"ln1": norm_axes(cfg.norm), "block": block_axes(cfg)}
    a = {"ln1": norm_axes(cfg.norm), "ln2": norm_axes(cfg.norm)}
    if kind == MLA_ATTN:
        a["mla"] = mla_mod.mla_axes(cfg)
    elif kind == RGLRU:
        a["rec"] = rglru_mod.rglru_axes(cfg)
    else:
        a["attn"] = attn_mod.attn_axes(cfg)
    if _layer_is_moe(cfg, layer_id):
        a["moe"] = moe_mod.moe_axes(cfg)
    else:
        a["mlp"] = mlp_axes()
    return a


# -------------------------------------------------------- layer apply ------
def apply_layer(p, cfg: ModelConfig, kind: str, layer_is_moe: bool, x, *,
                positions=None, positions3=None, impl: str = "auto"):
    """Full-sequence layer.  Returns (x, moe_aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (SLSTM, MLSTM):
        fn = (xlstm_mod.slstm_block_apply if kind == SLSTM
              else xlstm_mod.mlstm_block_apply)
        y, _ = fn(p["block"], cfg, apply_norm(p["ln1"], x, cfg.norm))
        return x + y, aux
    h = apply_norm(p["ln1"], x, cfg.norm)
    if kind == MLA_ATTN:
        y = mla_mod.mla_apply(p["mla"], cfg, h, positions=positions, impl=impl)
    elif kind == RGLRU:
        y, _ = rglru_mod.rglru_block_apply(p["rec"], cfg, h)
    else:
        window = cfg.sliding_window if kind == LOCAL_ATTN else 0
        y = attn_mod.attn_apply(
            p["attn"], cfg, h, window=window,
            rope_theta=_rope_theta_for(cfg, kind),
            softcap=cfg.attn_logit_softcap, positions=positions,
            positions3=positions3, impl=impl)
    x = x + y
    h = apply_norm(p["ln2"], x, cfg.norm)
    if layer_is_moe:
        y, aux = moe_mod.moe_apply(p["moe"], cfg, h)
    else:
        y = mlp_apply(p["mlp"], h, cfg.act)
    return x + y, aux


def decode_layer(p, cfg: ModelConfig, kind: str, layer_is_moe: bool, x,
                 cache, index, *, positions3=None):
    """One-token decode through a layer.  Returns (x, new_cache, aux)."""
    if kind in (SLSTM, MLSTM):
        fn = (xlstm_mod.slstm_block_apply if kind == SLSTM
              else xlstm_mod.mlstm_block_apply)
        y, new_cache = fn(p["block"], cfg, apply_norm(p["ln1"], x, cfg.norm),
                          cache=cache, index=index)
        return x + y, new_cache
    h = apply_norm(p["ln1"], x, cfg.norm)
    if kind == MLA_ATTN:
        y, new_cache = mla_mod.mla_decode_attend(p["mla"], cfg, h, cache, index)
    elif kind == RGLRU:
        y, new_cache = rglru_mod.rglru_block_apply(p["rec"], cfg, h,
                                                   cache=cache, index=index)
    else:
        window = cfg.sliding_window if kind == LOCAL_ATTN else 0
        y, new_cache = attn_mod.decode_attend(
            p["attn"], cfg, h, cache, index, window=window,
            rope_theta=_rope_theta_for(cfg, kind),
            softcap=cfg.attn_logit_softcap, positions3=positions3)
    x = x + y
    h = apply_norm(p["ln2"], x, cfg.norm)
    if layer_is_moe:
        y, _ = moe_mod.moe_apply(p["moe"], cfg, h)
    else:
        y = mlp_apply(p["mlp"], h, cfg.act)
    return x + y, new_cache


def init_layer_cache(cfg: ModelConfig, layer_id: int, batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    kind = _layer_kind(cfg, layer_id)
    if kind == SLSTM:
        return xlstm_mod.init_slstm_cache(cfg, batch)
    # recurrent conv states take ``dtype`` too: decode writes them back in
    # the activations' dtype, and a cache that changes dtype recompiles
    if kind == MLSTM:
        return xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
    if kind == RGLRU:
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    if kind == MLA_ATTN:
        return mla_mod.init_mla_cache(cfg, batch, max_len, dtype)
    window = cfg.sliding_window if kind == LOCAL_ATTN else 0
    return attn_mod.init_kv_cache(cfg, batch, max_len, window=window,
                                  dtype=dtype)


# --------------------------------------------------------- whole model -----
def init(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    stages = compute_stages(cfg)
    keys = jax.random.split(key, cfg.num_layers + 3)
    params = {
        "embed": embedding(keys[-1], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": norm(cfg.d_model, cfg.norm, dtype),
        # the PHSFL head: randomly initialized; frozen during global training
        "lm_head": dense(keys[-2], cfg.d_model, cfg.padded_vocab, dtype=dtype),
    }
    for si, st in enumerate(stages):
        if st.which == "scan":
            blocks = {}
            for j, lid in enumerate(st.layer_ids):
                lkeys = jnp.stack([keys[lid + r * len(st.layer_ids)]
                                   for r in range(st.repeats)])
                blocks[f"b{j}"] = jax.vmap(
                    lambda k, lid=lid: init_layer(k, cfg, lid, dtype))(lkeys)
            params[f"stage{si}"] = blocks
        else:
            params[f"stage{si}"] = {
                f"b{j}": init_layer(keys[lid], cfg, lid, dtype)
                for j, lid in enumerate(st.layer_ids)}
    return params


def axes(cfg: ModelConfig):
    stages = compute_stages(cfg)
    ax = {
        "embed": embedding_axes(),
        "final_norm": norm_axes(cfg.norm),
        "lm_head": dense_axes(("embed", "vocab")),
    }
    for si, st in enumerate(stages):
        blocks = {}
        for j, lid in enumerate(st.layer_ids):
            la = layer_axes(cfg, lid)
            blocks[f"b{j}"] = stack_axes(la) if st.which == "scan" else la
        ax[f"stage{si}"] = blocks
    return ax


def embed_tokens(params, cfg: ModelConfig, tokens, patch_embeds=None):
    x = params["embed"]["table"][tokens]
    if cfg.embed_scale:
        x = x * jnp.sqrt(float(cfg.d_model)).astype(x.dtype)
    if patch_embeds is not None:
        # VLM stub frontend: precomputed patch embeddings occupy the first
        # num_patch_tokens positions of the sequence.
        np_ = patch_embeds.shape[1]
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, np_:]], axis=1)
    return x


def remat_wrapper(remat: bool, remat_policy: str | None = None):
    """Activation-checkpoint wrapper factory.

    remat_policy: None/'full' — save only block boundaries (max recompute);
    'dots' — save dot/matmul outputs (recompute only cheap elementwise ops,
    the §Perf selective-remat iteration).
    """
    if not remat:
        return lambda f: f
    if remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return lambda f: jax.checkpoint(f, policy=pol)
    return jax.checkpoint


def apply(params, cfg: ModelConfig, batch, *, impl: str = "auto",
          remat: bool = False, remat_policy: str | None = None):
    """Full-sequence forward to final hidden states (B,S,D).

    batch: {"tokens": (B,S) int32, optional "patch_embeds", "positions3"}.
    Returns (hidden, moe_aux_loss).
    """
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens, batch.get("patch_embeds"))
    positions3 = batch.get("positions3")
    aux_total = jnp.zeros((), jnp.float32)
    stages = compute_stages(cfg)

    def one_layer(p, x, kind, is_moe):
        return apply_layer(p, cfg, kind, is_moe, x,
                           positions3=positions3, impl=impl)

    maybe_remat = remat_wrapper(remat, remat_policy)

    for si, st in enumerate(stages):
        sp = params[f"stage{si}"]
        kinds = [_layer_kind(cfg, lid) for lid in st.layer_ids]
        moes = [_layer_is_moe(cfg, lid) for lid in st.layer_ids]
        if st.which == "scan":
            @maybe_remat
            def body_fn(x, pslice, kinds=kinds, moes=moes):
                aux = jnp.zeros((), jnp.float32)
                for j in range(len(kinds)):
                    x, a = one_layer(pslice[f"b{j}"], x, kinds[j], moes[j])
                    aux = aux + a
                return x, aux

            x, auxs = jax.lax.scan(lambda c, p: body_fn(c, p), x, sp)
            aux_total = aux_total + auxs.sum()
        else:
            for j in range(len(kinds)):
                fn = maybe_remat(partial(one_layer, kind=kinds[j],
                                         is_moe=moes[j]))
                x, a = fn(sp[f"b{j}"], x)
                aux_total = aux_total + a
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, aux_total


def logits_from_hidden(params, cfg: ModelConfig, hidden):
    lg = hidden @ params["lm_head"]["w"]
    return softcap(lg.astype(jnp.float32), cfg.final_logit_softcap)


def lm_loss(params, cfg: ModelConfig, hidden, labels):
    """Memory-bounded cross-entropy: logits materialized per seq chunk."""
    b, s, d = hidden.shape
    chunk = LOSS_CHUNK if s % LOSS_CHUNK == 0 else s
    nc = s // chunk
    hc = hidden.reshape(b, nc, chunk, d)
    lc = labels.reshape(b, nc, chunk)

    @jax.checkpoint
    def chunk_loss(h, l):
        lg = logits_from_hidden(params, cfg, h)            # (B,c,V) f32
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, l[..., None], axis=-1)[..., 0]
        return (lse - gold).sum()

    def body(acc, inp):
        h, l = inp
        return acc + chunk_loss(h, l), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)))
    return total / (b * s)


# --------------------------------------------------------------- decode ----
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    stages = compute_stages(cfg)
    cache = {}
    for si, st in enumerate(stages):
        blocks = {}
        for j, lid in enumerate(st.layer_ids):
            c = init_layer_cache(cfg, lid, batch, max_len, dtype)
            if st.which == "scan":
                c = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (st.repeats,) + a.shape), c)
            blocks[f"b{j}"] = c
        cache[f"stage{si}"] = blocks
    return cache


def decode_step(params, cfg: ModelConfig, token, cache, index, *,
                positions3=None, return_hidden: bool = False):
    """One decode step.  token: (B,1) int32; index: scalar int32 = current
    position.  Returns (logits (B,1,V), new_cache); with return_hidden the
    first element is the final hidden state (B,1,D) instead (used by the
    personalized-head serving path)."""
    x = embed_tokens(params, cfg, token)
    stages = compute_stages(cfg)
    new_cache = {}
    for si, st in enumerate(stages):
        sp = params[f"stage{si}"]
        sc = cache[f"stage{si}"]
        kinds = [_layer_kind(cfg, lid) for lid in st.layer_ids]
        moes = [_layer_is_moe(cfg, lid) for lid in st.layer_ids]
        if st.which == "scan":
            def body(x, slices, kinds=kinds, moes=moes):
                pslice, cslice = slices
                ncs = {}
                for j in range(len(kinds)):
                    x, nc = decode_layer(pslice[f"b{j}"], cfg, kinds[j],
                                         moes[j], x, cslice[f"b{j}"], index,
                                         positions3=positions3)
                    ncs[f"b{j}"] = nc
                return x, ncs

            x, ncs = jax.lax.scan(body, x, (sp, sc))
            new_cache[f"stage{si}"] = ncs
        else:
            ncs = {}
            for j in range(len(kinds)):
                x, nc = decode_layer(sp[f"b{j}"], cfg, kinds[j], moes[j], x,
                                     sc[f"b{j}"], index,
                                     positions3=positions3)
                ncs[f"b{j}"] = nc
            new_cache[f"stage{si}"] = ncs
    x = apply_norm(params["final_norm"], x, cfg.norm)
    if return_hidden:
        return x, new_cache
    return logits_from_hidden(params, cfg, x), new_cache


def prefill(params, cfg: ModelConfig, batch, *, max_len: int | None = None,
            impl: str = "auto"):
    """Full-sequence forward + populated decode cache.

    Implemented as apply() for hidden states plus per-layer cache fill for
    attention layers (recurrent layers re-scan their state).  Used by the
    serving example at small scale; the dry-run prefill shape lowers apply().
    """
    hidden, _ = apply(params, cfg, batch, impl=impl)
    return logits_from_hidden(params, cfg, hidden[:, -1:, :]), hidden
