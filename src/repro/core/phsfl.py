"""PHSFL training rounds on the TPU mesh.

Two distribution strategies (see DESIGN.md §2/§5):

1. ``make_phsfl_round`` — paper-faithful (SFL-V1 semantics).  Every client
   owns a full model replica: parameters carry a leading client dim C
   (= pods * clients_per_pod) sharded over the manual ('pod','data') axes;
   the 'model' axis stays *automatic* so GSPMD tensor-parallelizes each
   client's replica.  One call = one edge round:

       kappa0 local SGD steps (lax.scan, NO cross-client collectives)
       -> weighted psum over 'data'   (edge aggregation, Eqs. 14-15)
       -> [every kappa1 calls] weighted psum over 'pod' (global agg, Eq. 16)

   The frozen head (Eq. 12) is an optimizer mask, so the head leaves never
   move and the psum leaves them bit-identical across clients.

2. ``make_shared_server_step`` — beyond-paper (SFL-V2-like).  The server-side
   body is ONE shared copy (FSDP-sharded over ('pod','data') x 'model');
   only the small client block + head carry the per-client dim (vmapped).
   Body gradients sync every step; client blocks still aggregate on the
   kappa0/kappa1 schedule.  This removes the dominant per-client memory and
   the full-model edge all-reduce — the datacenter analogue of the paper's
   Remark-1 communication saving (ship activations, not the model).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import HierarchyConfig, ModelConfig, TrainConfig
from repro.core.hierarchy import (edge_aggregate_mesh, global_aggregate_mesh,
                                  masked_psum_weighted)
from repro.core.split import (GLOBAL_TRAIN, HSFL_TRAIN, split_spec_for,
                              trainable_mask, part_masks)
from repro.models.registry import Model
from repro.optim import apply_updates, make_optimizer, masked
from repro.sharding.rules import data_axes, params_specs


# --------------------------------------------------------------- common ----
def _client_axes(mesh: Mesh):
    ca = data_axes(mesh)
    return ca if len(ca) > 1 else ca[0]


def _squeeze0(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _unsqueeze0(tree):
    return jax.tree.map(lambda x: x[None], tree)


def abstract_params(model: Model, *, stacked_clients: int | None = None):
    """ShapeDtypeStruct params tree (no allocation)."""
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    if stacked_clients is not None:
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((stacked_clients,) + s.shape,
                                           s.dtype), shapes)
    return shapes


def _local_scan(model: Model, tcfg: TrainConfig, opt):
    """One client's kappa0 local SGD steps — the SINGLE definition shared by
    the mesh and host rounds, so their numerics cannot drift apart."""
    def scan(p, s, batch_c):
        def local_step(carry, mb):
            pp, ss = carry
            pol = None if tcfg.remat_policy == "full" else tcfg.remat_policy
            loss, g = jax.value_and_grad(
                lambda q: model.loss(q, mb, remat=tcfg.remat,
                                     remat_policy=pol))(pp)
            upd, ss = opt.update(g, ss, pp)
            return (apply_updates(pp, upd), ss), loss

        (p, s), losses = jax.lax.scan(local_step, (p, s), batch_c)
        return p, s, losses

    return scan


def build_optimizer(model: Model, tcfg: TrainConfig, cut=None):
    """Masked optimizer implementing the PHSFL frozen head (Eq. 12).

    ``cut`` re-partitions the client/body boundary (see ``split_spec_for``);
    the head — the only part the optimizer mask distinguishes — is the same
    at every cut, which is exactly the paper's Remark 2: the round numerics
    cannot depend on the cut, only the comm accounting does."""
    spec = split_spec_for(model.cfg, cut)
    phase = GLOBAL_TRAIN if tcfg.freeze_head else HSFL_TRAIN
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    mask = trainable_mask(shapes, spec, phase)
    opt = make_optimizer(tcfg.optimizer, tcfg.learning_rate,
                         weight_decay=tcfg.weight_decay)
    return masked(opt, mask), mask


# ------------------------------------------------ paper-faithful round -----
@dataclass
class PHSFLRound:
    """One compiled edge round (optionally with global sync)."""
    fn: Callable            # (params, opt_state, batch, alpha_u, alpha_b
                            #  [, mask]) -> (params, opt_state, metrics)
    params_spec: Any        # PartitionSpec tree for the stacked params
    num_clients: int


def make_phsfl_round(model: Model, hcfg: HierarchyConfig, tcfg: TrainConfig,
                     mesh: Mesh, *, global_sync: bool,
                     participation: bool = False, cut=None) -> PHSFLRound:
    """One compiled edge round.

    With ``participation=True`` the returned fn takes a sixth argument: a
    (num_clients,) 0/1 mask from the wireless scheduler.  Aggregation
    weights renormalize over the participating clients (Eqs. 14-16 over the
    survivors); an ES with zero participants keeps its pre-round edge model.
    An all-ones mask is bit-identical to the unmasked round.

    ``cut`` declares the client/body split boundary (for LMs, the client
    depth).  By Remark 2 it cannot change the round's numerics — the
    compiled fn is identical for every cut — but it keeps the declared
    split in sync with the wireless cut controller's byte accounting.
    """
    cfg = model.cfg
    opt, _ = build_optimizer(model, tcfg, cut)
    ca = _client_axes(mesh)
    manual = set(data_axes(mesh))
    num_clients = 1
    for a in data_axes(mesh):
        num_clients *= mesh.shape[a]

    local_scan = _local_scan(model, tcfg, opt)

    def per_client(params, opt_state, batch_c, au, ab, mask):
        p = _squeeze0(params)
        s = _squeeze0(opt_state)
        batch_c = _squeeze0(batch_c)
        p_prev = p                  # edge model before this round's steps

        p, s, losses = local_scan(p, s, batch_c)

        # ---- edge aggregation: weighted psum over clients of this ES ----
        agg_dtype = jnp.dtype(tcfg.agg_dtype)
        if mask is None:
            p = edge_aggregate_mesh(p, au[0], agg_dtype)
            if global_sync and "pod" in mesh.axis_names:
                # ---- global aggregation: weighted psum over edge servers --
                p = global_aggregate_mesh(p, ab[0], agg_dtype)
        else:
            m = mask[0].astype(agg_dtype)
            p = masked_psum_weighted(p, au[0], m, p_prev, "data", agg_dtype)
            if global_sync and "pod" in mesh.axis_names:
                # an ES joins the global round iff it had >= 1 participant
                es_m = (jax.lax.psum(m, "data") > 0).astype(agg_dtype)
                p = masked_psum_weighted(p, ab[0], es_m, p, "pod", agg_dtype)
        # true mean over ALL clients (the P() out-spec otherwise surfaces
        # shard 0's local loss with the replication check disabled)
        mean_loss = losses.mean()
        for a in data_axes(mesh):
            mean_loss = jax.lax.pmean(mean_loss, a)
        return _unsqueeze0(p), _unsqueeze0(s), mean_loss

    lead = P(ca)
    nargs = 6 if participation else 5
    body = per_client if participation else (
        lambda pr, st, b, au, ab: per_client(pr, st, b, au, ab, None))
    shd = jax.shard_map(
        body, mesh=mesh,
        in_specs=(lead,) * nargs,
        out_specs=(lead, lead, P()),
        axis_names=manual, check_vma=False)

    if participation:
        def round_fn(params, opt_state, batch, alpha_u, alpha_b, mask):
            new_p, new_s, loss = shd(params, opt_state, batch,
                                     alpha_u, alpha_b, mask)
            return new_p, new_s, {"loss": loss}
    else:
        def round_fn(params, opt_state, batch, alpha_u, alpha_b):
            new_p, new_s, loss = shd(params, opt_state, batch,
                                     alpha_u, alpha_b)
            return new_p, new_s, {"loss": loss}

    pspec = params_specs(abstract_params(model), model.axes(), mesh, mode="tp")
    pspec = jax.tree.map(lambda s: P(ca, *tuple(s)), pspec,
                        is_leaf=lambda x: isinstance(x, P))
    return PHSFLRound(fn=round_fn, params_spec=pspec, num_clients=num_clients)


# --------------------------------------------- host mirror (single device) --
def make_host_round(model: Model, hcfg: HierarchyConfig, tcfg: TrainConfig,
                    *, num_clients: int, global_sync: bool,
                    participation: bool = False, cut=None) -> PHSFLRound:
    """Mesh-free mirror of :func:`make_phsfl_round` for single-device runs.

    Same semantics, same numerics: vmapped clients run the identical local
    scan, then edge aggregation is a weighted mean over each ES's client
    group in ``agg_dtype`` (and, when ``global_sync``, a weighted mean over
    ES groups by alpha_b) — exactly what the psum path computes, so a parity
    test can compare the two bit-for-bit at f32.  Optimizer states stay
    per-client, matching the mesh path.  ``hcfg.num_edge_servers`` groups
    the leading client dim; alpha_u must be normalized within each group.
    ``cut`` declares the split boundary exactly as in make_phsfl_round
    (a Remark-2 no-op on numerics).
    """
    opt, _ = build_optimizer(model, tcfg, cut)
    B = hcfg.num_edge_servers
    assert num_clients % B == 0, (num_clients, B)
    Ub = num_clients // B
    agg_dtype = jnp.dtype(tcfg.agg_dtype)

    local_scan = _local_scan(model, tcfg, opt)

    def one_client(p, s, bc):
        p, s, losses = local_scan(p, s, bc)
        return p, s, losses.mean()

    def _edge(p, p_prev, au, mask):
        w = au.astype(agg_dtype).reshape(B, Ub)
        if mask is not None:
            m = mask.astype(agg_dtype).reshape(B, Ub)
            w = w * m
            tot = w.sum(axis=1, keepdims=True)
            n = m.sum(axis=1, keepdims=True)
            one = jnp.asarray(1.0, agg_dtype)
            denom = jnp.where(n >= Ub, one, jnp.where(tot > 0, tot, one))

        def agg(x, fb):
            xr = x.astype(agg_dtype).reshape((B, Ub) + x.shape[1:])
            wexp = w.reshape((B, Ub) + (1,) * (x.ndim - 1))
            acc = (xr * wexp).sum(axis=1, keepdims=True)
            if mask is not None:
                acc = acc / denom.reshape((B, 1) + (1,) * (x.ndim - 1))
            out = jnp.broadcast_to(acc, xr.shape).astype(x.dtype)
            if mask is not None:
                sel = (n > 0).reshape((B, 1) + (1,) * (x.ndim - 1))
                out = jnp.where(sel, out, fb.reshape(xr.shape))
            return out.reshape(x.shape)

        return jax.tree.map(agg, p, p_prev)

    def _global(p, ab, mask):
        wb = ab.astype(agg_dtype).reshape(B, Ub)[:, :1]      # (B, 1)
        if mask is not None:
            m = (mask.astype(agg_dtype).reshape(B, Ub).sum(
                axis=1, keepdims=True) > 0).astype(agg_dtype)  # ES mask (B,1)
            wb = wb * m
            tot = wb.sum()
            n = m.sum()
            one = jnp.asarray(1.0, agg_dtype)
            denom = jnp.where(n >= B, one, jnp.where(tot > 0, tot, one))

        def agg(x):
            xr = x.astype(agg_dtype).reshape((B, Ub) + x.shape[1:])
            wexp = wb.reshape((B, 1) + (1,) * (x.ndim - 1))
            acc = (xr * wexp).sum(axis=0, keepdims=True)
            if mask is not None:
                acc = acc / denom
                acc = jnp.where(n > 0, acc, xr)   # nobody synced: keep edges
            out = jnp.broadcast_to(acc, xr.shape).astype(x.dtype)
            return out.reshape(x.shape)

        return jax.tree.map(agg, p)

    def round_body(params, opt_state, batch, au, ab, mask):
        p_prev = params
        p, s, losses = jax.vmap(one_client)(params, opt_state, batch)
        p = _edge(p, p_prev, au, mask)
        if global_sync:
            p = _global(p, ab, mask)
        return p, s, {"loss": losses.mean()}

    if participation:
        round_fn = round_body
    else:
        round_fn = lambda pr, st, b, au, ab: round_body(pr, st, b, au, ab,
                                                        None)
    return PHSFLRound(fn=round_fn, params_spec=None, num_clients=num_clients)


def init_stacked_params(model: Model, key, num_clients: int):
    """Materialize identical per-client replicas (host-side, small scale)."""
    p = model.init(key)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_clients,) + x.shape), p)


# ---------------------------------------------- shared-server (SFL-V2) -----
@dataclass
class SharedServerStep:
    fn: Callable            # (params, opt_state, batch) -> (params, opt, metrics)
    sync_clients: Callable  # (params, do_global: bool static) -> params
    client_mask: Any


def make_shared_server_step(model: Model, hcfg: HierarchyConfig,
                            tcfg: TrainConfig, mesh: Mesh,
                            num_clients: int) -> SharedServerStep:
    """Beyond-paper mode: shared body, per-client client-block + head.

    params: client-part leaves carry a leading (num_clients,) dim; body/head
    leaves are shared.  Plain pjit (no manual axes) — GSPMD shards the
    client dim over ('pod','data') and the body FSDP-style.
    """
    cfg = model.cfg
    spec = split_spec_for(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    masks = part_masks(shapes, spec)
    client_mask = masks["client"]
    opt, _ = build_optimizer(model, tcfg)

    in_axes_tree = jax.tree.map(lambda c: 0 if c else None, client_mask)

    def _merged_loss(params, cp, b):
        return model.loss(
            jax.tree.map(lambda m, c, s: c if m else s, client_mask, cp,
                         params), b, remat=tcfg.remat)

    def loss_fn(params, batch):
        if cfg.moe is not None:
            # jax.lax.ragged_dot (MoE grouped matmul) does not support vmap
            # over non-leading dims yet; map clients sequentially by index
            # instead — identical math, and the scan body costs once in HLO.
            def one(i):
                cp = jax.tree.map(lambda m, x: x[i] if m else x,
                                  client_mask, params)
                b = jax.tree.map(lambda x: x[i], batch)
                return model.loss(cp, b, remat=tcfg.remat)

            losses = jax.lax.map(one, jnp.arange(num_clients))
        else:
            losses = jax.vmap(
                lambda cp, b: _merged_loss(params, cp, b),
                in_axes=(in_axes_tree, 0))(params, batch)
        return losses.mean()

    def step(params, opt_state, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        upd, opt_state = opt.update(g, opt_state, params)
        params = apply_updates(params, upd)
        return params, opt_state, {"loss": loss}

    def sync_clients(params, do_global: bool):
        """kappa0-boundary aggregation of the per-client client blocks."""
        pods = mesh.shape.get("pod", 1)
        per_pod = num_clients // pods

        def agg(m, x):
            if not m:
                return x
            if do_global:
                mean = x.mean(axis=0, keepdims=True)
                return jnp.broadcast_to(mean, x.shape)
            xr = x.reshape((pods, per_pod) + x.shape[1:])
            mean = xr.mean(axis=1, keepdims=True)
            return jnp.broadcast_to(mean, xr.shape).reshape(x.shape)

        return jax.tree.map(agg, client_mask, params)

    return SharedServerStep(fn=step, sync_clients=sync_clients,
                            client_mask=client_mask)


def init_shared_server_params(model: Model, key, num_clients: int):
    p = model.init(key)
    spec = split_spec_for(model.cfg)
    masks = part_masks(p, spec)
    return jax.tree.map(
        lambda m, x: jnp.broadcast_to(x[None], (num_clients,) + x.shape)
        if m else x, masks["client"], p)
