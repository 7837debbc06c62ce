"""The FLOP and byte counters against hand counts at a tiny size."""
from bench import flops

CFG = {"d_hidden": 4, "n_layers": 1, "n_labels": 3, "seq_a": 2, "feat_a": 5,
       "seq_b": 3, "feat_b": 2}


def test_forward_rows():
    enc_a = 2 * 2 * 5 * 4 + 2 * 4 * 4  # input layer over 2 steps + 1 block
    enc_b = 2 * 3 * 2 * 4 + 2 * 4 * 4
    head = 2 * 4 * 3
    fusion = 2 * 8 * 4 + 2 * 4 * 3
    assert flops.forward_row(CFG, "unimodal_A") == enc_a + head == 136
    assert flops.forward_row(CFG, "unimodal_B") == enc_b + head
    assert flops.forward_row(CFG, "vfl_fallback") == enc_a + enc_b + fusion
    assert flops.forward_row(CFG, "multimodal") == enc_a + enc_b + fusion


def test_train_round_hand_count():
    live = {"partial_a": [1, 0], "partial_b": [0, 2], "paired": [1, 1],
            "aligned": 1.0}
    # encoder row in training: input layer fwd + weight grad (2x), hidden
    # layer fwd + weight grad + input grad (3x)
    tr_a = 2 * 80 + 3 * 32
    tr_b = 2 * 48 + 3 * 32
    head3, fus3 = 3 * 24, 3 * (64 + 24)
    train = 1 * (tr_a + head3) + 2 * (tr_b + head3) + 3 * (tr_a + tr_b + fus3)
    enc_a, enc_b, head, fusion = 112, 80, 24, 88
    n_val, C = 5, 2
    score = n_val * ((C + 1) * (enc_a + head + enc_b + head) + enc_a + enc_b
                     + (C + 2) * fusion)
    p = (5 * 4 + 4 + 20 + 4) + (2 * 4 + 4 + 20 + 4) + 2 * (12 + 3) \
        + (32 + 4) + (12 + 3)
    assert flops.n_params(CFG) == p
    assert flops.train_round(CFG, live, n_val) == train + score + 2 * 3 * p


def test_wire_codec_bytes():
    assert flops.wire_codec_bytes(2, 256) == 4 * (2 * 2 * 256 + 4)
