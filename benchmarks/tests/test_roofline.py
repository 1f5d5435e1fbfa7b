"""The count functions against shapes worked by hand."""
import pytest

from benchmarks.harness import roofline
from benchmarks.harness.peaks import peaks_for
from benchmarks.harness.roofline import Row

DOC = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
       "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 10}
# per layer: wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + three 8x16 matrices
P_LAYER = 64 + 32 + 32 + 64 + 3 * 128


def test_layer_params():
    assert roofline.layer_matmul_params(DOC) == P_LAYER == 576


def test_flops_of_one_decode_row_and_one_prefill_chunk():
    rows = [Row(n=1, start=5, head=1), Row(n=3, start=0, head=0)]
    matmul = 2 * P_LAYER * 2 * 4                     # 4 tokens, 2 layers
    ctx = (1 * 5 + 1) + (0 + 3 * 4 // 2)             # 6 positions + (1 + 2 + 3)
    attn = 2 * 2 * 2 * 4 * ctx * 2                   # QK and PV, 2 heads of 4, 2 layers
    head = 2 * 8 * 10 * 1
    assert roofline.step_flops(DOC, rows) == matmul + attn + head


def test_bytes_read_weights_once_and_each_sequence_cache_once():
    rows = [Row(n=1, start=5, head=1), Row(n=3, start=0, head=0)]
    weights = (P_LAYER + 16) * 2 * 2 + 8 * 2          # matrices + 2 norms a layer, final norm
    head = 8 * 10 * 2
    embed = 4 * 8 * 2
    kv_read = (6 + 3) * 2 * 4 * 2 * 2                 # positions x (K, V) x width x layers x bf16
    kv_write = 4 * 2 * 4 * 2 * 2
    assert roofline.step_bytes(DOC, rows) == weights + head + embed + kv_read + kv_write
    no_head = [Row(n=3, start=0, head=0)]
    assert roofline.step_bytes(DOC, rows) - roofline.step_bytes(DOC, no_head) > head


def test_which_peak_binds():
    peaks = peaks_for("TPU v5 lite")
    t, bound = roofline.least_seconds(DOC, [Row(n=1, start=0, head=1)], peaks)
    assert bound == "bandwidth" and t == pytest.approx(
        roofline.step_bytes(DOC, [Row(1, 0, 1)]) / 819e9)
    # a decode row of a 7B model at real widths: weight-bound, about 9 ms for 16 layers
    import json
    import os

    from benchmarks.harness.cells import BENCH_DIR
    doc = json.load(open(os.path.join(BENCH_DIR, "configs", "mistral-7b-v0.3.json")))
    t, bound = roofline.least_seconds(doc, [Row(n=1, start=100, head=1)], peaks)
    assert bound == "bandwidth" and 0.008 < t < 0.011
    # 4096 prefill tokens in one step would be compute-bound
    assert roofline.least_seconds(doc, [Row(n=4096, start=0, head=1)], peaks)[1] == "flops"


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
