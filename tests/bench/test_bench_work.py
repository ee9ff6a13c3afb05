"""Operation and byte counts against hand counts for both configurations:
the dense module's (arch/dense_gqa.py) and work.py's GeMM roofline."""

import pytest

from benchlib import cells, work

NEMO_CONF = cells.load_config("mistral-nemo-12b-d8")
NEMO = NEMO_CONF.dims
QWEN = cells.load_config("qwen3-14b-d8").dims
DENSE = NEMO_CONF.arch
V5E = work.PEAKS["TPU v5 lite"]

# Hand counts from the published widths (8 layers):
#   nemo: attn 5120*128*(32+2*8) + 32*128*5120 = 52,428,800
#         mlp  3*5120*14336                    = 220,200,960
#   qwen: attn 5120*128*(40+2*8) + 40*128*5120 = 62,914,560
#         mlp  3*5120*17408                    = 267,386,880
HAND = {
    "nemo": (NEMO, 272_629_760, 671_088_640),
    "qwen": (QWEN, 330_301_440, 777_912_320),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_param_counts(name):
    m, layer, head = HAND[name]
    assert m.layer_params == layer
    assert m.trunk_params == 8 * layer
    assert m.head_params == head


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_flops(name):
    m, layer, head = HAND[name]
    attn_per_key = 4 * m.n_heads * 128 * 8
    want = 2 * (8 * layer + head) * 2 + attn_per_key * (100 + 200)
    assert DENSE.decode_flops(m, [100, 200]) == want
    assert DENSE.decode_flops(m, []) == 0


@pytest.mark.parametrize("name", sorted(HAND))
def test_prefill_chunk_flops(name):
    m, layer, head = HAND[name]
    # Tokens at positions 1000..1511 attend over 1001..1512 keys.
    keys = sum(range(1001, 1513))
    want = 2 * 8 * layer * 512 + 4 * m.n_heads * 128 * 8 * keys + 2 * head
    assert DENSE.prefill_chunk_flops(m, 1000, 512) == want


@pytest.mark.parametrize("name", sorted(HAND))
def test_step_gemms_cover_the_weights(name):
    m, layer, head = HAND[name]
    shapes = DENSE.step_gemms(m, 512, 1)
    assert len(shapes) == 7 * 8 + 1
    assert sum(k * n for _, k, n in shapes) == 8 * layer + head
    assert shapes[-1] == (1, 5120, m.vocab)


def test_gemm_least_time_picks_the_binding_roof():
    # A 512-row projection is bound by compute, a 32-row one by bytes.
    big = (512, 5120, 14336)
    assert work.gemm_least_s(big, V5E) == pytest.approx(
        2 * 512 * 5120 * 14336 / 197e12)
    small = (32, 5120, 14336)
    assert work.gemm_least_s(small, V5E) == pytest.approx(
        2 * (32 * 5120 + 5120 * 14336 + 32 * 14336) / 819e9)


def test_decode_attention_bytes():
    # Two rows of 1000 and 3000 keys: K and V of 8 kv heads of 128, 2 bytes,
    # 8 layers, plus each row's 32x128 query in and output out.
    kv = 2 * (1000 + 3000) * 8 * 128
    qo = 2 * 2 * 32 * 128
    want = 2 * (kv + qo) * 8 / 819e9
    assert DENSE.decode_attn_least_s(NEMO, [1000, 3000], V5E) == \
        pytest.approx(want)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9 imaginary")
