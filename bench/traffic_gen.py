"""The one traffic generator: a pool of edge-round batches from the seed.

Each client's tokens are drawn over half the vocabulary and shifted by
``c * V / (2 C)``, so every client sees a different token distribution
(the non-IID scheme of ``launch/train.py``'s ``_client_round_batch``);
every other token repeats its predecessor plus one, and the labels are
the tokens shifted by one.  An encoder-decoder cell also gets frame
embeddings from a standard normal.  Every seed gets the same shapes.
"""

from __future__ import annotations

import numpy as np


def make_pool(seed: int, traffic: dict, config: dict) -> list:
    """``traffic["pool_rounds"]`` host batches, each a dict of arrays
    shaped (clients, local_steps, micro, ...)."""
    C, k0, micro, S = (traffic[k] for k in
                       ("clients", "local_steps", "micro", "seq"))
    V = config["vocab_size"]
    half = max(V // 2, 2)
    rng = np.random.default_rng([seed, 1])
    pool = []
    for _ in range(traffic["pool_rounds"]):
        base = rng.integers(0, half, size=(C, k0, micro, S), dtype=np.int32)
        base[..., 1::2] = (base[..., 0:-1:2] + 1) % half
        shift = (np.arange(C, dtype=np.int64) * V // (2 * C)).astype(
            np.int32)[:, None, None, None]
        tokens = (base + shift) % V
        batch = {"tokens": tokens, "labels": np.roll(tokens, -1, axis=-1)}
        if "encdec" in config:
            batch["source_embeds"] = rng.standard_normal(
                (C, k0, micro, config["encdec"]["max_source_len"],
                 config["d_model"]), dtype=np.float32)
        pool.append(batch)
    return pool
