"""End-to-end PHSFL training driver (deliverable b's e2e example backend).

Runs REAL training on the devices JAX sees: the clients sit on the
'data' axis of a (C, 1) mesh when there are at least C devices, and are
vmapped on one device otherwise.  By default the architecture is cut to a
tiny same-family variant (``ModelConfig.reduced``); ``--published-widths``
trains the registry config as published, in its own dtype:

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-350m \
        --rounds 20 --clients 4 --seq 128

After global training it fine-tunes per-client heads (Eq. 18) and reports
global vs personalized loss per client.  ``main`` returns the summary dict
it prints last.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs.base import (FaultConfig, HierarchyConfig, TrainConfig,
                                WirelessConfig)
from repro.configs.registry import get_arch
from repro.core import (build_optimizer, init_stacked_params,
                        make_host_round, make_phsfl_round,
                        personalize_head_bank, personalized_eval)
from repro.core.comm import comm_for_lm, comm_table_for_lm
from repro.core.hierarchy import es_assignment
from repro.data.synthetic import synthetic_token_batch
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.telemetry import MetricLogger, Telemetry
from repro.wireless import make_scheduler


def _client_round_batch(cfg, C, k, micro, seq, seed):
    """Stacked per-client batches; each client gets a DIFFERENT token
    distribution (client id shifts the vocab) => non-IID federated data."""
    toks, labs = [], []
    for c in range(C):
        nb = synthetic_token_batch(seed * 1000 + c, k * micro, seq,
                                   max(cfg.vocab_size // 2, 2))
        shift = (c * cfg.vocab_size) // (2 * max(C, 1))
        toks.append((nb["tokens"] + shift) % cfg.vocab_size)
        labs.append((nb["labels"] + shift) % cfg.vocab_size)
    batch = {
        "tokens": jnp.asarray(np.stack(toks)).reshape(C, k, micro, seq),
        "labels": jnp.asarray(np.stack(labs)).reshape(C, k, micro, seq),
    }
    if cfg.encdec is not None:
        batch["source_embeds"] = 0.02 * jnp.ones(
            (C, k, micro, cfg.encdec.max_source_len, cfg.d_model),
            jnp.float32)
    if cfg.vlm is not None:
        batch["patch_embeds"] = 0.02 * jnp.ones(
            (C, k, micro, cfg.vlm.num_patch_tokens, cfg.d_model), jnp.float32)
        batch["positions3"] = jnp.tile(
            jnp.arange(seq, dtype=jnp.int32)[None, None, None, :, None],
            (C, k, micro, 1, 3))
    return batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-350m")
    ap.add_argument("--published-widths", action="store_true",
                    help="train the architecture at its published widths "
                         "and dtype (default: the tiny reduced variant)")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--micro", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--hsfl", action="store_true",
                    help="baseline: do NOT freeze the head")
    ap.add_argument("--finetune-steps", type=int, default=5)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="write a FULL training-state checkpoint (params, "
                         "optimizer, round cursor, scheduler RNG/energy "
                         "state) into {ckpt-dir}/state every N rounds; a "
                         "killed run then resumes bit-identically (0 = "
                         "final-params checkpoint only)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest state checkpoint in "
                         "{ckpt-dir}/state (fresh start if none exists)")
    ap.add_argument("--abort-after", type=int, default=None,
                    help="kill the run right after this round's state "
                         "checkpoint (crash simulation for the resume "
                         "smoke test)")
    ap.add_argument("--seed", type=int, default=0)
    # ---- population-scale cohorts (repro.wireless.population) ----
    ap.add_argument("--population", type=int, default=0,
                    help="register N clients in a persistent population and "
                         "sample a cohort per round; the scheduler then "
                         "prices ALL N channels/budgets while only the "
                         "cohort trains (0 = classic fixed-client mode). "
                         "Requires a non-ideal --channel")
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="clients trained per round in population mode "
                         "(default: --clients); becomes the slot count of "
                         "the training mesh")
    ap.add_argument("--sampling", default="uniform",
                    choices=["uniform", "rate", "pareto"],
                    help="cohort sampling rule: uniform, biased toward "
                         "good channels (rate), or a Pareto-style "
                         "participation cap (least-sampled first)")
    # ---- wireless scenario (repro.wireless) ----
    ap.add_argument("--channel", default="ideal",
                    choices=["ideal", "static", "rayleigh"],
                    help="per-client channel model (ideal = pre-wireless)")
    ap.add_argument("--deadline", type=float, default=float("inf"),
                    help="edge-round deadline in seconds; stragglers drop")
    ap.add_argument("--mean-rate-mbps", type=float, default=100.0,
                    help="mean per-client uplink rate")
    ap.add_argument("--energy-budget", type=float, default=float("inf"),
                    help="lifetime per-client uplink energy budget (J)")
    ap.add_argument("--es-uplink-mbps", type=float, default=float("inf"),
                    help="shared ES uplink capacity, split among that "
                         "round's scheduled clients (inf = private uplinks)")
    ap.add_argument("--cut-policy", default="fixed",
                    choices=["fixed", "greedy", "deadline"],
                    help="per-round cut-layer selection policy "
                         "(repro.wireless.cutter)")
    ap.add_argument("--cut-candidates", type=int, nargs="+", default=None,
                    help="candidate client depths (n_client_layers), "
                         "shallow to deep; default: the model's depth only")
    # ---- device (compute) model (repro.wireless.device) ----
    ap.add_argument("--compute-gflops", type=float, default=float("inf"),
                    help="per-client compute rate in GFLOP/s; client-block "
                         "FLOPs then cost round time and energy (inf = "
                         "free compute, the bits-only accounting)")
    ap.add_argument("--compute-heterogeneity", type=float, default=0.0,
                    help="lognormal sigma of a fixed per-client compute "
                         "scale (0 = identical devices)")
    ap.add_argument("--compute-power-w", type=float, default=0.0,
                    help="power drawn while computing; joins tx energy in "
                         "the per-client budget gate")
    ap.add_argument("--codec-cycles", type=float, default=0.0,
                    help="FLOPs per element crossing a lossy codec "
                         "(encode/decode compute; 0 = codecs compute-free)")
    # ---- fault injection (repro.wireless.faults) ----
    ap.add_argument("--erasure-prob", type=float, default=0.0,
                    help="per-attempt payload erasure probability; erased "
                         "transmissions retransmit (HARQ) as real timeline "
                         "segments, priced in the deadline/energy/bits "
                         "accounting")
    ap.add_argument("--harq-retries", type=int, default=2,
                    help="max retransmissions per payload before it FAILS")
    ap.add_argument("--harq-backoff", type=float, default=0.0,
                    help="radio-idle seconds before each retransmission")
    ap.add_argument("--crash-hazard", type=float, default=0.0,
                    help="per-round probability a scheduled client dies "
                         "mid-round (timeline frozen at the crash instant)")
    ap.add_argument("--pipeline", action="store_true",
                    help="overlap client compute with uplink streaming at "
                         "minibatch granularity (repro.wireless.timeline); "
                         "the deadline/energy gates and the accounting "
                         "price the overlapped timeline.  Staleness-"
                         "weighted async aggregation (staleness_lambda) is "
                         "a FedSim-side fold and is not exposed here — this "
                         "driver prices the scheduler side only")
    # ---- compression (repro.compress) ----
    ap.add_argument("--codec", default="fp32",
                    choices=["fp32", "int8", "int4", "topk", "fp8"],
                    help="codec for the split-learning wire payloads "
                         "(activations up, gradients down, offloads); this "
                         "driver prices it in the wireless accounting — the "
                         "CNN simulator (benchmarks/compress_sweep.py) "
                         "additionally applies it in the dataflow")
    ap.add_argument("--codec-bits", type=int, default=None,
                    help="override the uniform quantizer's bit width")
    ap.add_argument("--topk-frac", type=float, default=0.05,
                    help="kept fraction for --codec topk")
    # ---- observability (repro.telemetry) ----
    ap.add_argument("--trace-dir", default=None,
                    help="write telemetry into this directory: a streamed "
                         "Chrome/Perfetto trace of every wireless round "
                         "(trace.json — open at https://ui.perfetto.dev), "
                         "typed metrics snapshots (metrics.jsonl), a run "
                         "manifest (manifest.json), and a run-end summary "
                         "table (summary.txt).  Default: telemetry off, "
                         "bit-identical to a run without it")
    ap.add_argument("--metrics-every", type=int, default=1,
                    help="flush a metrics.jsonl snapshot every N rounds "
                         "(with --trace-dir)")
    args = ap.parse_args(argv)
    use_compile_cache()

    tel = (Telemetry(args.trace_dir, metrics_every=args.metrics_every)
           if args.trace_dir else Telemetry.disabled())
    log = MetricLogger("train", telemetry=tel)
    cfg = get_arch(args.arch)
    if not args.published_widths:
        cfg = cfg.reduced()
    model = build_model(cfg)
    C = args.clients
    population = None
    if args.population:
        if args.channel == "ideal":
            ap.error("--population requires a non-ideal --channel (the "
                     "cohort sampler lives on the wireless scheduler)")
        from repro.wireless.population import Population
        C = args.cohort_size or C
        if args.population < C:
            ap.error("--population must be >= the cohort size")
        population = Population(args.population, seed=args.seed)

    # single-host mesh: all clients on the 'data' axis of a (C,1) mesh if we
    # have C devices, else a (1,1) mesh with client dim = C still carried in
    # the arrays (shard_map over size-1 axes; aggregation becomes a segment
    # mean in the host round below).
    ndev = jax.device_count()
    if ndev >= C:
        mesh = jax.make_mesh((C, 1), ("data", "model"))
    else:
        mesh = jax.make_mesh((1, 1), ("data", "model"))

    hcfg = HierarchyConfig(num_edge_servers=1, clients_per_es=C,
                           kappa0=args.local_steps, kappa1=1,
                           global_rounds=args.rounds)
    tcfg = TrainConfig(learning_rate=args.lr, freeze_head=not args.hsfl,
                       local_steps_in_step=args.local_steps, remat=False,
                       finetune_steps=args.finetune_steps,
                       finetune_lr=args.lr)

    # wireless scenario: channel + participation scheduler (None = ideal)
    scheduler = None
    if args.channel != "ideal":
        from repro.compress import link_codecs
        codecs = None
        if args.codec != "fp32":
            codecs = link_codecs(args.codec, bits=args.codec_bits,
                                 topk_frac=args.topk_frac)
        candidates = tuple(args.cut_candidates or ())
        wcfg = WirelessConfig(model=args.channel,
                              mean_uplink_mbps=args.mean_rate_mbps,
                              mean_downlink_mbps=4 * args.mean_rate_mbps,
                              deadline_s=args.deadline,
                              energy_budget_j=args.energy_budget,
                              es_uplink_mbps=args.es_uplink_mbps,
                              cut_policy=args.cut_policy,
                              cut_candidates=candidates,
                              compute_gflops=args.compute_gflops,
                              compute_heterogeneity=args.compute_heterogeneity,
                              compute_power_w=args.compute_power_w,
                              codec_cycles_per_element=args.codec_cycles,
                              pipeline=args.pipeline,
                              faults=FaultConfig(
                                  erasure_prob=args.erasure_prob,
                                  max_retries=args.harq_retries,
                                  backoff_s=args.harq_backoff,
                                  crash_hazard=args.crash_hazard),
                              seed=args.seed)
        comm_kw = dict(seq_len=args.seq,
                       dataset_size=args.rounds * args.local_steps *
                       args.micro, batch_size=args.micro,
                       batches_per_epoch=1, codecs=codecs)
        if population is not None:
            from repro.wireless.population import CohortScheduler
            sched_u = population.N
            es_assign = population.es_assign
            sched_extra = dict(cls=CohortScheduler, population=population,
                               cohort_size=C, sampling=args.sampling)
        else:
            sched_u = C
            es_assign = es_assignment(C, hcfg.clients_per_es)
            sched_extra = {}
        if wcfg.cut_policy != "fixed" or candidates:
            table = comm_table_for_lm(
                cfg, cuts=candidates or (cfg.n_client_layers,), **comm_kw)
            if wcfg.cut_policy == "fixed" and cfg.n_client_layers not in table:
                raise ValueError(
                    f"--cut-policy fixed would price one of {tuple(table)} "
                    f"but the model's client depth is {cfg.n_client_layers}; "
                    f"include it in --cut-candidates")
            scheduler = make_scheduler(
                wcfg, sched_u, kappa0=hcfg.kappa0, comm_table=table,
                es_assign=es_assign,
                fixed_cut=cfg.n_client_layers
                if cfg.n_client_layers in table else 0,
                telemetry=tel, **sched_extra)
        else:
            comm = comm_for_lm(cfg, **comm_kw)
            scheduler = make_scheduler(wcfg, sched_u, comm, hcfg.kappa0,
                                       es_assign=es_assign, telemetry=tel,
                                       **sched_extra)
    participation = scheduler is not None
    tel.write_manifest(config=vars(args),
                       seeds={"seed": args.seed},
                       extra={"arch": args.arch, "clients": C})

    with jax.set_mesh(mesh):
        if mesh.shape["data"] == C:
            round_ = make_phsfl_round(model, hcfg, tcfg, mesh,
                                      global_sync=False,
                                      participation=participation,
                                      cut=cfg.n_client_layers)
        else:
            # degenerate 1-device path: the mesh-free mirror of
            # make_phsfl_round (same local scan, same weighted aggregation
            # in agg_dtype, same per-client optimizer states)
            round_ = make_host_round(model, hcfg, tcfg, num_clients=C,
                                     global_sync=False,
                                     participation=participation,
                                     cut=cfg.n_client_layers)
        round_fn = jax.jit(round_.fn)

        params = init_stacked_params(model, jax.random.PRNGKey(args.seed),
                                     C)
        opt, _ = build_optimizer(model, tcfg)
        state1 = opt.init(jax.tree.map(lambda x: x[0], params))
        opt_state = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape),
            state1)
        au = jnp.full((C,), 1.0 / C, jnp.float32)
        ab = jnp.ones((C,), jnp.float32)

        # ---- full-state checkpointing (kill + --resume = bit-identical):
        # the state tree carries params, optimizer state, the round cursor,
        # the simulated clock, and the scheduler's mutable state (energy
        # budgets, stale bank, channel/thinning/fault RNG streams).  Per-
        # round batches are seeded ``args.seed + r``, so nothing else is
        # needed to replay the uninterrupted trajectory.
        sim_time = 0.0
        start_round = 0
        state_dir = (os.path.join(args.ckpt_dir, "state")
                     if args.ckpt_dir else None)

        def run_state(r):
            st = {"params": params, "opt_state": opt_state,
                  "round": np.int64(r), "sim_time_s": np.float64(sim_time)}
            if scheduler is not None:
                st["scheduler"] = scheduler.state_dict()
            return st

        if args.resume and state_dir:
            step = latest_step(state_dir)
            if step is not None:
                st = load_checkpoint(state_dir, step, run_state(0))
                params = jax.tree.map(jnp.asarray, st["params"])
                opt_state = jax.tree.map(jnp.asarray, st["opt_state"])
                start_round = int(st["round"])
                sim_time = float(st["sim_time_s"])
                if scheduler is not None:
                    scheduler.load_state_dict(st["scheduler"])
                log.log(resumed_from_round=float(start_round))

        t0 = time.time()
        metrics = {"loss": float("nan")}       # already-complete resume
        round_loss, round_s = [], []
        for r in range(start_round, args.rounds):
            t_r = time.time()
            batch = _client_round_batch(cfg, C, args.local_steps, args.micro,
                                        args.seq, seed=args.seed + r)
            if scheduler is not None:
                rep = scheduler.step(r)
                if population is not None:
                    # (N,)-wide report -> this round's C training slots
                    from repro.wireless.population import cohort_report
                    rep = cohort_report(rep, scheduler.last_cohort)
                sim_time += rep.round_time_s
                mask = jnp.asarray(rep.mask, jnp.float32)
                params, opt_state, metrics = round_fn(
                    params, opt_state, batch, au, ab, mask)
                extra = {}
                if rep.mean_cut is not None:
                    extra["mean_cut"] = rep.mean_cut
                if rep.compute_s is not None and rep.compute_s.any():
                    extra["compute_s_max"] = float(rep.compute_s.max())
                log.log(step=r, loss=metrics["loss"],
                        participants=rep.num_participants,
                        round_time_s=rep.round_time_s,
                        sim_time_s=sim_time, bits_tx=rep.bits_tx,
                        s_per_round=(time.time() - t0) / (r + 1), **extra)
            else:
                params, opt_state, metrics = round_fn(params, opt_state,
                                                      batch, au, ab)
                log.log(step=r, loss=metrics["loss"],
                        s_per_round=(time.time() - t0) / (r + 1))
            round_loss.append(float(metrics["loss"]))   # waits for the round
            round_s.append(time.time() - t_r)
            if (state_dir and args.ckpt_every > 0
                    and (r + 1) % args.ckpt_every == 0):
                save_checkpoint(state_dir, r + 1, run_state(r + 1))
            if args.abort_after is not None and r + 1 >= args.abort_after:
                # simulated crash for the resume smoke test: die right
                # after this round's checkpoint, skipping the final save
                tel.close()
                out = {"aborted_after_round": r + 1}
                print(json.dumps(out))
                return out

        # ---- personalization (Eq. 18) ----
        global_params = jax.tree.map(lambda x: x[0], params)
        ft = _client_round_batch(cfg, C, 1, args.micro, args.seq, seed=777)
        ft = {k: v[:, 0] for k, v in ft.items()}       # (C, micro, ...)
        heads, ft_losses = jax.jit(
            lambda p, b: personalize_head_bank(model, p, b, tcfg))(
                global_params, ft)
        evaluate = jax.jit(
            lambda p, h, b: personalized_eval(model, p, h, b))
        ev_pers = evaluate(global_params, heads, ft)
        base_head = jnp.broadcast_to(global_params["lm_head"]["w"][None],
                                     heads.shape)
        ev_glob = evaluate(global_params, base_head, ft)
        for c in range(C):
            log.log(client=c, global_loss=ev_glob[c],
                    personalized_loss=ev_pers[c])
        gain = float((ev_glob - ev_pers).mean())
        log.log(personalization_gain=gain)

        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.rounds, global_params)
            log.log(ckpt=1.0)

    tel.close()
    out = {"final_loss": float(metrics["loss"]),
           "personalization_gain": gain,
           "round_loss": round_loss, "round_s": round_s}
    if scheduler is not None:
        out["sim_time_s"] = sim_time
        out["energy_left_j_min"] = float(scheduler.energy_left.min())
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
