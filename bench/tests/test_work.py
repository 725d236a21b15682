"""Operation and byte counts of one step, against hand-computed shapes."""
import pytest

from work import (Shape, attn_bytes, attn_flops, layer_matmul_params,
                  least_time, shape_of, step_flops)

S = Shape(layers=2, d_model=8, d_ff=16, heads=4, kv_heads=2, head_dim=2,
          vocab=10, page=4)


def test_layer_params():
    # q,o: 8*4*2 each; k,v: 8*2*2 each; MLP 3*8*16
    assert layer_matmul_params(S) == 64 + 64 + 32 + 32 + 384


def test_attention_flops_count_live_context_only():
    # rows at ctx 0 and 5 attend 1 and 6 tokens: 4*H*D*(1+6) per layer
    assert attn_flops(S, [0, 5]) == 2 * 4 * 4 * 2 * 7


def test_attention_bytes_read_each_request_once():
    # request 7: rows at ctx 4..6 (a chunk) -> 7 tokens -> 2 pages of 4;
    # request 9: one decode row at ctx 0 -> 1 page
    ctxs, owners = [4, 5, 6, 0], [7, 7, 7, 9]
    kv = 3 * 4 * 2 * 2 * 2 * 2          # pages*page*Hkv*D*bytes*(K,V)
    qo = 4 * 4 * 2 * 2 * 2              # rows*Hq*D*bytes*(q,out)
    assert attn_bytes(S, ctxs, owners) == 2 * (kv + qo)


def test_padding_and_past_context_count_nothing():
    # the same real rows, whatever the padded row count or table width:
    # the counts take only real rows and their live pages
    one = attn_bytes(S, [3], [1]), attn_flops(S, [3])
    assert one == (2 * (1 * 4 * 2 * 2 * 2 * 2 + 1 * 4 * 2 * 2 * 2),
                   2 * 4 * 4 * 2 * 4)


def test_step_flops():
    dense = 2 * 2 * layer_matmul_params(S) * 3
    assert step_flops(S, [0, 1, 2], 1) == (dense + attn_flops(S, [0, 1, 2])
                                           + 2 * 8 * 10)


def test_least_time_picks_the_bound():
    assert least_time(100.0, 1.0, 10.0, 10.0) == (10.0, "compute")
    assert least_time(1.0, 100.0, 10.0, 10.0) == (10.0, "memory")


def test_shape_of_config():
    c = {"num_hidden_layers": 40, "hidden_size": 2048,
         "intermediate_size": 8192, "num_attention_heads": 32,
         "num_key_value_heads": 8, "vocab_size": 49155}
    s = shape_of(c, 16)
    assert (s.head_dim, s.page, s.kv_bytes) == (64, 16, 2)
    # Granite-3.0-2B: 2.53e9 parameters with the tied embedding
    n = s.layers * layer_matmul_params(s) + s.vocab * s.d_model
    assert n == pytest.approx(2.53e9, rel=0.01)
