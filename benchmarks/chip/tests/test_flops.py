"""FLOP and byte counts for both configurations, against hand arithmetic."""

import pytest

import flops
import peaks
import spec


@pytest.fixture(scope="module")
def mamba():
    return spec.load_cell("mamba2-370m.train.commit").model_config()


@pytest.fixture(scope="module")
def minicpm():
    return spec.load_cell("minicpm-2b.serve.chat").model_config()


def test_mamba2_train_step(mamba):
    # per layer: in_proj 1024 x (2*2048 + 2*128 + 32) + out_proj 2048 x 1024
    per_layer = 1024 * 4384 + 2048 * 1024
    head = 1024 * 50288  # 50277 padded to a multiple of 16
    assert flops.matmul_params(mamba) == 48 * per_layer + head
    # SSD per token and layer at chunk 256: mean causal keys 128.5
    ssd = 2 * 128.5 * 128 + 2 * 128.5 * 32 * 64 + 4 * 32 * 64 * 128
    assert flops.ssd_flops_per_token(mamba, 2048) == ssd
    fwd = (2 * (48 * per_layer + head) + 48 * ssd) * 2048
    assert flops.forward_flops(mamba, 2048) == fwd
    step = flops.train_step_flops(mamba, 4, 2048)
    assert step == 3 * 4 * fwd
    assert step / (4 * 2048) == pytest.approx(2.4368e9, rel=1e-3)


def test_minicpm_decode_interval(minicpm):
    D, L, V, F = 2304, 40, 122753, 5760
    per_layer = 4 * D * D + 3 * D * F
    assert flops.matmul_params(minicpm) == L * per_layer + D * V
    # weights: 2,724.5M parameters in bfloat16
    assert flops.weight_bytes(minicpm) == minicpm.param_count() * 2
    assert minicpm.param_count() == pytest.approx(2.7245e9, rel=1e-3)
    # K and V of one position over 40 layers, 36 heads of 64, bfloat16
    assert flops.kv_bytes_per_position(minicpm) == 40 * 2 * 2304 * 2
    w = flops.decode_interval(minicpm, [99, 9])
    assert w["bytes"] == flops.weight_bytes(minicpm) + 368640 * (100 + 10)
    attn = 4 * 36 * 64 * L * (100 + 10)
    assert w["flops"] == 2 * 2 * (L * per_layer + D * V) + attn
    t, bound = flops.roofline_s(w["flops"], w["bytes"],
                                peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(w["bytes"] / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
