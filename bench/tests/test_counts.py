"""The operation and byte counts against values worked out by hand for
one small shape each."""

from bench import counts

# L=2, D=8, H=2 (hd 4), K=1, F=16, V=10
TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10}


def test_matmul_params():
    # per layer: q 8*8 + kv 2*8*4 + o 8*8 + mlp 3*8*16 = 64+64+64+384 = 576
    assert counts.matmul_params(TINY) == 2 * 576 + 10 * 8


def test_serve_token_flops():
    # 2 N + 4 * (pos + 1) * H * hd * L at pos 3: 4 * 4 * 2 * 4 * 2 = 256
    assert counts.serve_token_flops(TINY, 3) == 2 * 1232 + 256


def test_train_step_flops():
    # seq 4: causal pairs 10; attention 4*2*4*10*2 = 640 a forward
    assert counts.causal_pairs(4) == 10
    assert counts.train_step_flops(TINY, 1, 4) == 6 * 1232 * 4 + 3 * 640
    assert counts.train_step_flops(TINY, 3, 4) == \
        3 * counts.train_step_flops(TINY, 1, 4)


def test_flash_call():
    flops, nbytes = counts.flash_call(TINY, 1, 4)
    assert flops == 4 * 2 * 4 * 10
    # q, o: 4 * 2 * 4 each; k, v: 4 * 1 * 4 each; 2 bytes an element
    assert nbytes == (2 * 32 + 2 * 16) * 2


def test_paged_step_bytes():
    # contexts 3 and 5: kv 8 positions * 2 * 1 * 4 * 2 B = 128 a layer;
    # q, o: 2 slots * 2 * 2 * 4 * 2 B = 64 a layer
    assert counts.paged_step_bytes(TINY, [3, 5]) == 2 * (128 + 64)


def test_phi4_mini_parameters_match_the_published_size():
    import json
    from bench.harness import BENCH
    c = json.load(open(BENCH / "configs" / "phi4-mini-3.8b.json"))
    n = counts.matmul_params(c)
    # 3.84e9 products a token (the published 3.8B, embedding tied)
    assert 3.80e9 < n < 3.87e9
