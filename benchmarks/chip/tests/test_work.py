"""Work and peak arithmetic, pinned at the configurations' published
widths."""

import pytest

import chip_paths  # noqa: F401  (before the modules below)
import work


@pytest.fixture(scope="module")
def glm():
    return work.dims(work.load_config("chatglm3-6b"))


@pytest.fixture(scope="module")
def granite():
    return work.dims(work.load_config("granite-3-8b-tp4"))


def test_chatglm3_parameter_and_cache_bytes(glm):
    assert glm.vocab_padded == 65024     # already a multiple of 256
    assert work.param_bytes(glm) == 12_486_909_952
    assert round(work.param_bytes(glm) / 1e9, 2) == 12.49
    assert work.kv_bytes_per_token(glm) == 28 * 2 * 2 * 128 * 2 == 28_672


def test_granite_parameter_and_per_chip_cache_bytes(granite):
    assert granite.chips == 4 and granite.vocab_padded == 49408
    assert round(work.param_bytes(granite) / 1e9, 2) == 16.75
    assert work.kv_bytes_per_token(granite) == 40 * 2 * 8 * 128 * 2 // 4 \
        == 40_960


def test_request_flops_is_prefill_plus_each_decode_step(glm):
    p, g = 100, 7
    steps = sum(work.decode_flops(glm, p + k) for k in range(1, g))
    assert work.request_flops(glm, p, g) == work.prefill_flops(glm, p) + steps
    # 2 FLOPs per weight per token: 5.98e9 weights multiply each token.
    assert 2 * work.matmul_params(glm) == 11_953_766_400


def test_decode_live_positions_counts_each_step():
    # prompt 10: steps 1..3 read 11, 12, 13 positions.
    assert work.decode_live_positions([10], [4]) == 36
    assert work.decode_live_positions([10, 5], [4, 1]) == 36


def test_decode_step_bytes_skip_the_embedding_table(glm, granite):
    weights = work.param_bytes(glm) - glm.vocab_padded * glm.d * 2
    assert work.decode_step_bytes(glm, 8, 0) == weights + 8 * glm.d * 2
    assert work.decode_step_bytes(granite, 16, 100) == pytest.approx(
        (work.param_bytes(granite) - granite.vocab_padded * granite.d * 2
         + 16 * granite.d * 2) / 4 + 40_960 * 100)


def test_roofline_names_its_bound():
    peak = work.load_peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = work.roofline_s(197e12, 819e9 / 2, peak)
    assert (t, bound) == (1.0, "flops")
    t, bound = work.roofline_s(4 * 197e12, 2 * 819e9, peak, chips=4)
    assert (t, bound) == (2.0, "bytes")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary")
