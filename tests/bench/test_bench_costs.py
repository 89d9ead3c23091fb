"""The FLOP and byte counts of the yardstick, against counts made by hand
for one layer of each configuration, through the configurations'
architecture module (`bench/archs/dense_gqa.py`)."""
import pytest

from bench import costs, spec

DENSE = spec.arch("dense_gqa")
QWEN = DENSE.arch(spec.cell("qwen3-1.7b.jd1000.decode").config)
MISTRAL = DENSE.arch(spec.cell("mistral-7b-16l.jd1000.decode").config)
JD = {"mode": "jd", "rank": 16, "targets": ["q", "k", "v", "o"], "clusters": 1}
LORA = {"mode": "lora", "rank": 16, "targets": ["q", "k", "v", "o"]}


def test_layer_weights_by_hand():
    # q and o: d*H*hd each; k and v: d*Kv*hd each; gate, up, down: d*d_ff
    assert QWEN.layer_matmul_params == (2 * 2048 * 2048 + 2 * 2048 * 1024
                                        + 3 * 2048 * 6144) == 50_331_648
    assert MISTRAL.layer_matmul_params == (2 * 4096 * 4096 + 2 * 4096 * 1024
                                           + 3 * 4096 * 14336) == 218_103_808
    assert (QWEN.L, MISTRAL.L, QWEN.vocab, MISTRAL.vocab) == \
        (28, 16, 151936, 32000)


def test_adapter_flops_by_hand():
    # rank 16: shrink 2*r*d_in, expand 2*r*d_out; jd adds the r x r Sigma
    q = 2 * 16 * (2048 + 2048)
    kv = 2 * 16 * (2048 + 1024)
    assert costs.adapter_token_flops(QWEN, LORA, LORA["targets"]) == \
        2 * q + 2 * kv == 458_752
    assert costs.adapter_token_flops(QWEN, JD, JD["targets"]) == \
        458_752 + 4 * 2 * 256


def test_fused_jd_call_by_hand():
    # one request attending 513 tokens, adapter 5, one layer of qwen3
    flops, nbytes = QWEN.fused_decode_call(JD, [513], [5])
    assert flops == 4 * 2048 * 513 + (2 * 16 * 4096 + 2 * 256)
    kv = 2 * 513 * 8 * 128 * 2                    # K and V, bf16
    q_out = 2 * 2048 * 2                          # q in, attention out
    delta = 2048 * 4                              # f32 delta
    basis = (16 * (2048 + 2048) + 256) * 2        # U, V of the cluster; Sigma
    assert nbytes == kv + q_out + delta + basis == 2_249_216


def test_fused_lora_call_counts_each_adapter_once():
    one = MISTRAL.fused_decode_call(LORA, [100, 100], [3, 3])[1]
    two = MISTRAL.fused_decode_call(LORA, [100, 100], [3, 4])[1]
    assert two - one == 16 * (4096 + 4096) * 2


def test_decode_step_by_hand():
    kv = [600, 700]
    per_tok = 16 * (2 * 218_103_808 + costs.adapter_token_flops(
        MISTRAL, JD, JD["targets"])) + 2 * 4096 * 32000
    attn = 16 * 4 * 4096 * (600 + 700)
    assert MISTRAL.decode_step_flops(JD, kv) == 2 * per_tok + attn
    weights = (16 * 218_103_808 + 4096 * 32000) * 2
    kvb = 16 * 2 * (600 + 700) * 8 * 128 * 2
    ad = 16 * costs.adapter_layer_bytes(MISTRAL, JD, JD["targets"], [1, 2])
    assert MISTRAL.decode_step_bytes(JD, kv, [1, 2]) == \
        weights + kvb + ad


def test_prefill_by_hand():
    P = 512
    per_tok = 2 * 50_331_648 + costs.adapter_token_flops(QWEN, JD,
                                                         JD["targets"])
    causal = 4 * 2048 * (P * (P + 1) // 2)
    assert QWEN.prefill_flops(JD, P) == \
        28 * (P * per_tok + causal) + 2 * 2048 * 151936


@pytest.mark.parametrize("flops,nbytes,which", [(197e12, 1.0, "compute"),
                                                (1.0, 819e9, "memory")])
def test_roofline_names_its_bound(flops, nbytes, which):
    t, bound = costs.least_seconds(flops, nbytes,
                                   {"flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9})
    assert bound == which and t == pytest.approx(1.0)
