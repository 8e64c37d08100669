"""Personalized serving driver: batched decode with per-request heads.

Serves a model with a *head bank*: each request carries a client
profile id; the trunk (client block + body, = w*) is shared across the
batch, and the final projection uses the request's own personalized
classifier w_{u,1,hd}^K (paper Sec. III-B).  This is the serving-side
contract of PHSFL — one shared trunk, many heads.  The model is the tiny
reduced variant unless ``--published-widths`` is given.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b \
        --batch 4 --steps 16

``main`` returns the summary dict it prints last.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.configs.registry import get_arch
from repro.core import personalize_head_bank
from repro.data.synthetic import synthetic_token_batch
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.models.layers import softcap
from repro.telemetry import MetricLogger


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--published-widths", action="store_true",
                    help="serve the architecture at its published widths "
                         "and dtype (default: the tiny reduced variant)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    log = MetricLogger("serve")
    cfg = get_arch(args.arch)
    if not args.published_widths:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    # ---- build a personalized head bank (Eq. 18) ----
    tcfg = TrainConfig(finetune_lr=0.2, finetune_steps=4)
    nbs = [synthetic_token_batch(c, 2, 32, cfg.vocab_size)
           for c in range(args.clients)]
    batches = {k: jnp.stack([jnp.asarray(nb[k]) for nb in nbs])
               for k in nbs[0]}
    if cfg.encdec is not None:
        batches["source_embeds"] = 0.02 * jnp.ones(
            (args.clients, 2, cfg.encdec.max_source_len, cfg.d_model),
            jnp.float32)
    head_bank, _ = jax.jit(
        lambda p, b: personalize_head_bank(model, p, b, tcfg))(params, batches)
    log.log(head_bank_clients=head_bank.shape[0])

    # ---- batched decode; per-request personalized final projection ----
    rng = np.random.default_rng(args.seed)
    profile_ids = jnp.asarray(rng.integers(0, args.clients, args.batch))
    heads = head_bank[profile_ids]                    # (B, D, V)
    max_len = args.prompt_len + args.steps
    # the cache holds what decode_step writes back (the model's dtype), so
    # the step's input and output caches match and it compiles once
    cache_dtype = jnp.dtype(cfg.dtype)
    cache = model.init_cache(args.batch, max_len, dtype=cache_dtype)
    if cfg.encdec is not None:
        from repro.models import encdec as ed
        src = 0.02 * jnp.ones((args.batch, cfg.encdec.max_source_len,
                               cfg.d_model), jnp.float32)
        memory = ed.encode(params, cfg, src)
        cache["cross"] = ed.precompute_cross(params, cfg, memory,
                                             dtype=cache_dtype)

    prompt = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32))

    # params are an argument, not a closure: closed-over weights would be
    # baked into the executable as constants
    @jax.jit
    def step(params, tok, cache, index, heads):
        hidden, cache = model.decode_step(params, tok, cache, index,
                                          return_hidden=True)
        lg = jnp.einsum("bqd,bdv->bqv", hidden.astype(jnp.float32),
                        heads.astype(jnp.float32))
        lg = softcap(lg, cfg.final_logit_softcap)
        return lg, cache

    t0 = time.time()
    for i in range(args.prompt_len - 1):              # prefill via stepping
        _, cache = step(params, prompt[:, i:i + 1], cache,
                        jnp.asarray(i, jnp.int32), heads)
    generated = []
    tok = prompt[:, -1:]
    for s in range(args.steps):
        idx = jnp.asarray(args.prompt_len - 1 + s, jnp.int32)
        logits, cache = step(params, tok, cache, idx, heads)
        tok = logits[:, :, :cfg.vocab_size].argmax(-1).astype(jnp.int32)
        generated.append(np.asarray(tok[:, 0]))
    wall = time.time() - t0
    toks = args.batch * (args.steps + args.prompt_len - 1)
    log.log(tokens=toks, tok_per_s=toks / wall, wall_s=wall)
    out = {"generated": np.stack(generated, 1).tolist(),
           "profiles": profile_ids.tolist(),
           "tok_per_s": round(toks / wall, 1)}
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
