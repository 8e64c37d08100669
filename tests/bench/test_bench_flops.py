"""The FLOPs functions against a count by hand at a tiny size."""

from __future__ import annotations

from bench_tiny import bench_at


def test_xlstm_flops_by_hand():
    flops = bench_at().module("flops", "xlstm-350m")
    cfg = {"d_model": 8, "vocab_size": 300, "num_layers": 2,
           "block_pattern": ["mlstm", "slstm"],
           "xlstm": {"proj_factor_mlstm": 2.0, "proj_factor_slstm": 1.5,
                     "conv_kernel": 4, "num_heads": 2}}
    # mLSTM: di=16, dh=8; up 2*8*32, q k v 3*2*16*16, gates 2*2*16*2,
    # down 2*16*8, conv 2*4*16; per head: scores+values 2*(2*128*8),
    # normalizer 2*128*8, carry 2*2*8*8
    mlstm = (512 + 1536 + 128 + 256 + 128
             + 2 * (4096 + 2048 + 256))
    # sLSTM: dh=4, dff=12; W 2*8*32, R 2 heads * 2*4*16, up/gate/down 3*2*8*12
    slstm = 512 + 256 + 576
    head = 2 * 8 * 512                       # vocab 300 padded to 512
    trained, h = flops.forward_per_token(cfg)
    assert trained == mlstm + slstm
    assert h == head
    traffic = {"clients": 2, "local_steps": 3, "micro": 1, "seq": 5}
    assert flops.round_flops(cfg, traffic) == 30 * (3 * trained + 2 * head)


def test_seamless_flops_by_hand():
    flops = bench_at().module("flops", "seamless-m4t-medium")
    cfg = {"d_model": 4, "d_ff": 6, "num_heads": 2, "head_dim": 2,
           "num_kv_heads": 2, "vocab_size": 10, "num_layers": 1,
           "encdec": {"num_encoder_layers": 1, "max_source_len": 3}}
    seq = 2
    proj = 4 * 2 * 4 * 4                     # q, k, v, o: 2*4*4 each
    ffn = 3 * 2 * 4 * 6
    enc = 3 * (2 * 4 * 4 + proj + 2 * 2 * 3 * 4 + ffn)
    dec_tok = proj + 2 * 2 * 1 * 4 + 2 * 2 * 4 * 4 + 2 * 2 * 3 * 4 + ffn
    dec = seq * dec_tok + 3 * 2 * 2 * 4 * 4
    trained, head = flops.forward_per_sequence(cfg, seq)
    assert trained == enc + dec
    assert head == seq * 2 * 4 * 256
