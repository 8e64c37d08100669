"""Model FLOPs of one xLSTM edge round, from the shapes.

Per token, a matrix product of an (m, k) by a (k, n) operand counts 2kn
forward.  Backward counts twice the forward for every trained matrix (the
input's gradient and the weight's), and once for the frozen head (its
input's gradient only).  Nothing recomputed counts, and elementwise work
does not count.  The mLSTM mixes tokens in the chunkwise form the program
runs (``models/xlstm.py``, chunks of 256): per token and head the causal
half of the chunk's scores and values, the causal half of its normalizer,
and the chunk carry's read and write, each dh x dh.
"""

MLSTM_CHUNK = 256


def forward_per_token(config: dict) -> tuple[float, float]:
    """(trained, head) forward FLOPs per token."""
    D = config["d_model"]
    xl = config["xlstm"]
    di = int(D * xl["proj_factor_mlstm"])
    H = xl["num_heads"]
    dh = di // H
    c = MLSTM_CHUNK
    mlstm = (2 * D * 2 * di + 3 * 2 * di * di + 2 * 2 * di * H
             + 2 * di * D + 2 * xl["conv_kernel"] * di
             + H * (2 * (c / 2) * dh * 2 + 2 * (c / 2) * dh + 2 * 2 * dh * dh))
    sdh = D // H
    dff = int(D * xl["proj_factor_slstm"])
    slstm = 2 * D * 4 * D + H * 2 * sdh * 4 * sdh + 3 * 2 * D * dff
    per = {"mlstm": mlstm, "slstm": slstm}
    pattern = config["block_pattern"]
    trained = sum(per[pattern[i % len(pattern)]]
                  for i in range(config["num_layers"]))
    vpad = -(-config["vocab_size"] // 256) * 256
    return trained, 2 * D * vpad


def round_flops(config: dict, traffic: dict) -> float:
    trained, head = forward_per_token(config)
    tokens = (traffic["clients"] * traffic["local_steps"] * traffic["micro"]
              * traffic["seq"])
    return tokens * (3 * trained + 2 * head)
