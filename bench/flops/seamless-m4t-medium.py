"""Model FLOPs of one SeamlessM4T-medium edge round, from the shapes.

Counting as ``xlstm-350m.py``: 2kn per token for a (k, n) matrix, backward
twice the forward for trained matrices and once for the frozen head.
Attention counts the scores and the weighted values, 2 * keys * (H * hd)
each, over the keys a query sees (half the sequence on average when
causal).  Per sequence: the encoder over the source frames, the decoder
over the target tokens, the cross-attention keys and values once per
frame and layer.
"""


def forward_per_sequence(config: dict, seq: int) -> tuple[float, float]:
    """(trained, head) forward FLOPs of one sequence."""
    D, F = config["d_model"], config["d_ff"]
    A = config["num_heads"] * config["head_dim"]
    KV = config["num_kv_heads"] * config["head_dim"]
    src = config["encdec"]["max_source_len"]
    proj = 2 * D * A * 2 + 2 * D * KV * 2          # q, o and k, v
    ffn = 3 * 2 * D * F
    enc = src * (2 * D * D + config["encdec"]["num_encoder_layers"]
                 * (proj + 2 * 2 * src * A + ffn))
    dec_tok = (proj + 2 * 2 * (seq / 2) * A            # causal self
               + 2 * 2 * D * A + 2 * 2 * src * A      # cross q, o; scores
               + ffn)
    dec = config["num_layers"] * (seq * dec_tok + src * 2 * 2 * D * KV)
    vpad = -(-config["vocab_size"] // 256) * 256
    return enc + dec, seq * 2 * D * vpad


def round_flops(config: dict, traffic: dict) -> float:
    trained, head = forward_per_sequence(config, traffic["seq"])
    seqs = traffic["clients"] * traffic["local_steps"] * traffic["micro"]
    return seqs * (3 * trained + 2 * head)
