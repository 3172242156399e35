"""The functions that count a kernel's operations and bytes."""

import pytest

from chipbench import device, roofline

PEAKS = device.peaks_for("TPU v5 lite")


def test_peaks_table_has_no_default():
    assert PEAKS["bf16_flops_per_s"] == 197e12
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks_for("TPU v9 imaginary")


def test_flash_attention_costs():
    cost = roofline.flash_attention_calls(2, 16, 2048, 128)
    product = 2 * 2 * 16 * 2048 * 2048 * 128 / 2
    assert cost["fwd"][0] == 2 * product and cost["dq"][0] == 3 * product
    assert cost["dkv"][0] == 4 * product
    assert roofline.bound_seconds(*cost["fwd"], PEAKS)[1] == "compute"
    model = {"micro_batch": 2, "heads": 16, "seq": 2048, "head_dim": 128,
             "gas": 2, "n_layers": 16}
    three = roofline.flash_attention_train_seconds(model, 3, 1, PEAKS)
    four = roofline.flash_attention_train_seconds(model, 4, 1, PEAKS)
    assert three == pytest.approx(2 * 16 * 9 * product / 197e12)
    assert four == pytest.approx(2 * 16 * 11 * product / 197e12)


def test_ragged_paged_costs():
    model = {"heads": 16, "kv_heads": 16, "head_dim": 128, "page_size": 128,
             "kv_bytes": 2, "n_layers": 16}
    flops, nbytes = roofline.ragged_paged_dispatch(1, [300, 1], model)
    # 300 tokens sit in 3 pages, 1 token in one: 4 pages of keys and values
    page = 128 * 16 * 128 * 2
    assert nbytes == 2 * 4 * page + 2 * 2 * 16 * 128 * 2
    assert flops == 4 * (300 + 1) * 16 * 128
    assert roofline.bound_seconds(flops, nbytes, PEAKS)[1] == "memory"
    prefill = {"phase": "prefill", "real": 1000, "context": 1000}
    decode = {"phase": "decode", "contexts": [300, 1]}
    unsampled = {"phase": "prefill"}
    total = roofline.ragged_paged_serve_seconds(
        model, [prefill, decode, unsampled], PEAKS)
    p_flops, p_bytes = roofline.ragged_paged_dispatch(1000, [1000], model)
    assert p_flops == 4 * (1000 * 1001 / 2) * 16 * 128
    assert total == pytest.approx(16 * (
        roofline.bound_seconds(p_flops, p_bytes, PEAKS)[0]
        + roofline.bound_seconds(flops, nbytes, PEAKS)[0]))
